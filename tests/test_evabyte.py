"""EvaByte's kind, the MODEL: a whole forward of four windows a chunk (a
window) at a time against the plain reference, all prediction heads; the
description's checks; and the pooling's controls, each of which has to FAIL
the tolerance the stated program passes.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import eva
from calfkit_tpu.inference.config import CACHE_KINDS, EVA, ModelConfig, preset
from tests.arch_harness import EVABYTE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy
W, C = TOY.window_size, TOY.chunk_size


def _chunked_forward(params, tokens, heads: int) -> np.ndarray:
    """The program's forward of ``tokens`` [B, S] (whole windows), a window at
    a time through the wave's scratch as the engine's chunk lane runs it."""
    B, S = tokens.shape
    scratch = eva.make_scratch(TOY, B, S, jnp.float32)
    step = jax.jit(lambda p, t, pos, s: eva.eva_forward(p, TOY, t, pos, s, heads=heads))
    out = []
    for at in range(0, S, W):
        pos = at + jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (B, W))
        logits, scratch = step(params, jnp.asarray(tokens[:, at:at + W]), pos, scratch)
        out.append(np.asarray(logits))
    return np.concatenate(out, axis=1)


def _worst(params, seed: int = 0) -> float:
    tokens = np.asarray([FAMILY.prompt_of(4 * W, seed=seed), FAMILY.prompt_of(4 * W, seed=seed + 1)])
    got = _chunked_forward(params, tokens, TOY.num_pred_heads)
    want = ARCH.forward_heads(params, TOY, tokens, np.full((2,), 4 * W, np.int32))
    return float(np.abs(got.reshape(want.shape) - want).max())


def test_the_forward_of_four_windows_holds_every_head_to_the_reference():
    """128 positions = 4 windows of 32 in chunks of 4: the last window's
    queries see 31 exact keys and 24 pooled ones; both prediction heads."""
    assert TOY.num_pred_heads == 2 and 4 * W >= 3 * W
    assert _worst(FAMILY.seeded()) < LOGIT_TOL


def test_head_zero_is_the_first_vocab_rows_of_the_head():
    params = FAMILY.seeded()
    tokens = np.asarray([FAMILY.prompt_of(2 * W, seed=5)])
    both = _chunked_forward(params, tokens, 2)
    first = _chunked_forward(params, tokens, 1)
    assert first.shape[-1] == TOY.vocab_size and both.shape[-1] == 2 * TOY.vocab_size
    np.testing.assert_allclose(first, both[..., : TOY.vocab_size], atol=1e-6)


def _pool_control(how: str):
    """``eva.pool_chunks`` with one FAULT (``evabyte-eva.py``'s ``pooled`` names them)."""
    def pool(k, v, phi, mu, chunk):
        *lead, S, hd = k.shape
        k32 = k.astype(jnp.float32).reshape(*lead, S // chunk, chunk, hd)
        v32 = v.astype(jnp.float32).reshape(*lead, S // chunk, chunk, hd)
        phi32 = phi.astype(jnp.float32)[..., None, None, :]
        if how == "bfloat16 softmax":
            logits = jnp.sum(k32.astype(jnp.bfloat16) * phi32.astype(jnp.bfloat16), axis=-1)
            w = jax.nn.softmax(logits * jnp.bfloat16(hd ** -0.5), axis=-1).astype(jnp.float32)
        else:
            w = jax.nn.softmax(jnp.sum(k32 * phi32, axis=-1) * hd ** -0.5, axis=-1)
        if how == "uniform weights":
            w = jnp.full_like(w, 1.0 / chunk)
        kk = jnp.sum(w[..., None] * k32, axis=-2)
        if how != "no mu":
            kk = kk + mu.astype(jnp.float32)[..., None, :]
        return kk.astype(k.dtype), jnp.sum(w[..., None] * v32, axis=-2).astype(v.dtype)

    return pool


@pytest.mark.parametrize("how", ["bfloat16 softmax", "uniform weights", "no mu"])
def test_a_wrong_pooling_fails_the_tolerance(monkeypatch, how):
    """The pooling softmax taken in bfloat16, uniform weights in its place, a
    pooled key without ``mu``: each moves the logits past the tolerance."""
    monkeypatch.setattr(eva, "pool_chunks", _pool_control(how))
    assert _worst(FAMILY.seeded()) > LOGIT_TOL, how


def test_the_control_that_changes_nothing_passes(monkeypatch):
    monkeypatch.setattr(eva, "pool_chunks", _pool_control("as published"))
    assert _worst(FAMILY.seeded()) < LOGIT_TOL


def test_the_description_and_its_hand_count():
    """``param_count`` of the published description against a count written
    here by hand; the cache kind; what the description refuses."""
    full = preset("evabyte")
    by_hand = (32 * (202_375_168 + 8_192 + 8_192)  # seven matrices, two norms, phi and mu
               + 4_096 + 1_310_720 + 10_485_760)  # final norm, embedding, head of 8 x 320 rows
    assert full.param_count == by_hand == 6_488_330_240
    assert 4 * 4096 * 4096 + 3 * 4096 * 11008 == 202_375_168
    assert CACHE_KINDS[EVA] == "window+summaries" and full.eva and full.windowed
    assert (full.window_size, full.chunk_size, full.num_pred_heads, full.vocab_size) == (
        2048, 16, 8, 320)
    assert full.n_global_layers == full.n_window_layers == 32
    assert full.window_ring_pages(64, 8) == 34 and full.summary_entries(27_648) == 1_728
    tree = jax.eval_shape(lambda: FAMILY.seeded())
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree)) == TOY.param_count
    for bad, match in (
        (dict(layer_types=(EVA, EVA, "attention")), "another kind"),
        (dict(chunk_size=5), "chunk_size"),
        (dict(window_size=0), "chunk_size"),
        (dict(tie_embeddings=True), "untied"),
    ):
        with pytest.raises(ValueError, match=match):
            ModelConfig(**{**TOY.__dict__, **bad})
    with pytest.raises(ValueError, match='"eva"'):
        ModelConfig(window_size=32)
