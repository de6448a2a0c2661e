"""Window layers beside global ones that rotate BY KIND (Mellum 2's kind): the
program's mathematics against the plain reference on both sides of the window,
of the ring's wrap and of YaRN's original context; the program's YaRN law
against the reference's transcription at the PUBLISHED sizes; ``rope_tables``
bit for bit what it was for every preset there is; each control FAILING the
tolerance.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import ATTENTION, PRESETS, WINDOW, RopeScaling, preset
from tests.arch_harness import MELLUM_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

W, ORIGINAL = TOY.sliding_window, TOY.rope_scaling_global.original_max_position_embeddings


def _tokens(rows: int = 2, width: int = 128, seed: int = 2) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, TOY.vocab_size, (rows, width)).astype(np.int32)


def _worst(logits, want, lens) -> float:
    return max(float(np.abs(np.asarray(logits[r, :n]) - want[r, :n]).max())
               for r, n in enumerate(lens))


# ------------------------------------------------ the description
def test_the_description_is_the_window_stack_with_the_rotation_by_kind():
    assert TOY.layer_period == (WINDOW, WINDOW, WINDOW, ATTENTION)
    assert (TOY.norm, TOY.parallel_block, TOY.tie_embeddings, TOY.position_embedding) == (
        "rms", False, False, "rope")
    assert (TOY.scoring_func, TOY.topk_method, TOY.n_shared_experts, TOY.expert_share) == (
        "softmax", "greedy", 0, False)
    assert W < FAMILY.runtime().prefill_chunk and ORIGINAL < TOY.max_seq_len
    big = preset("mellum2-12b-a2.5b-instruct")
    assert big.param_count == 12_149_915_904  # the published 12B-A2.5B, counted by hand in ISSUE 47
    assert big.window_ring_pages(64, 4) == big.window_ring_pages(64, 8) == 18
    assert (big.head_dim, big.rotary_dim, big.n_window_layers, big.n_global_layers) == (
        128, 128, 21, 7)
    assert big.rope_scaling_global.scale == 1.2772588722239782
    assert math.isclose(RopeScaling(factor=16.0, original_max_position_embeddings=8192).scale,
                        0.1 * math.log(16) + 1)


@pytest.mark.parametrize("fields, why", [
    (dict(rope_scaling_global=RopeScaling(factor=4.0, original_max_position_embeddings=64),
          position_embedding="rope_window"), "both rotate"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(n_routed_experts=0, n_experts_per_tok=0), "expert block"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "router"),
])
def test_a_description_that_is_not_this_model_is_refused_with_its_reason(fields, why):
    with pytest.raises(ValueError, match=why):
        replace(TOY, **fields)


def test_the_scaled_rotation_belongs_to_the_window_stack_and_knows_two_laws():
    scaling = RopeScaling(factor=4.0, original_max_position_embeddings=64)
    for name in ("debug", "debug-gdn-moe", "debug-lfm2-moe"):
        with pytest.raises(ValueError, match="rope_scaling_global belongs to a window stack"):
            replace(preset(name), rope_scaling_global=scaling)
    for rope_type in ("llama3", "linear", "dynamic", "longrope"):
        with pytest.raises(ValueError, match="only 'default' and 'yarn'"):
            RopeScaling(rope_type=rope_type, factor=4.0, original_max_position_embeddings=64)
    with pytest.raises(ValueError, match="yarn needs"):
        RopeScaling(factor=4.0)
    plain = RopeScaling(rope_type="default")
    assert plain.scale == 1.0 and replace(TOY, rope_scaling_global=plain).windowed


# ------------------------------------------------ the rotation's laws
def test_the_program_s_yarn_is_the_reference_s_at_the_published_sizes():
    """``low``, ``high``, the scale and all 64 frequencies of the global
    layers' law as the program computes them (``config.RopeScaling``,
    ``model.rope_frequencies``) against the reference's own transcription of
    the equations (``mellum-moe-swa.py``), at head 128, theta 500000, factor
    16 over 8,192; by hand: low 18, high 35."""
    big = preset("mellum2-12b-a2.5b-instruct")
    s = big.rope_scaling_global
    assert s.correction_range(128, 500000.0) == (18, 35) == ARCH.yarn_range(
        128, 500000.0, 8192, 32.0, 1.0)
    law = ARCH._law(big, ATTENTION)
    assert law == (128, 500000.0, (16.0, 8192, 32.0, 1.0, 1.2772588722239782))
    want, want_scale = ARCH.frequencies(law)
    got, got_scale = M.rope_frequencies(128, 500000.0, s)
    assert got_scale == want_scale == 1.2772588722239782
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=2e-7, atol=0)
    plain, one = M.rope_frequencies(128, 500000.0)
    assert one == 1.0 and ARCH._law(big, WINDOW) == (128, 500000.0, None)
    assert np.allclose(np.asarray(plain), np.asarray(ARCH.frequencies(ARCH._law(big, WINDOW))[0]),
                       rtol=2e-7, atol=0)
    ratio = np.asarray(got) / np.asarray(plain)
    assert np.allclose(ratio[:19], 1.0) and np.allclose(ratio[35:], 1 / 16, rtol=1e-6)
    assert (np.diff(ratio[18:36]) < 0).all()  # the ramp between: pair by pair slower
    # the toy's law stands on both sides of its ramp too
    toy_ratio = np.asarray(M.rope_frequencies(8, 10000.0, TOY.rope_scaling_global)[0]) / np.asarray(
        M.rope_frequencies(8, 10000.0)[0])
    assert np.allclose(toy_ratio, [1.0, 0.625, 0.25, 0.25])


def _tables_as_they_were(positions, head_dim: int, theta: float):
    """``model.rope_tables`` as every caller had it through PR 46."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_every_preset_s_plain_tables_are_bit_for_bit_what_they_were(name):
    """``rope_tables`` takes a frequency vector and a scale where it took
    ``theta``: for every preset there is, the plain law's tables (what every
    stack but the scaled kind builds) are the same bits."""
    c = preset(name)
    dim = c.qk_rope_head_dim if c.latent else c.rotary_dim
    positions = jnp.asarray([[0, 1, 2, 63, 64, 1023, 1024, 8191, 8192, 16000, 131071]])
    got = M.rope_tables(positions, *M.rope_frequencies(dim, c.rope_theta))
    want = _tables_as_they_were(positions, dim, c.rope_theta)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(np.asarray(g), np.asarray(w))
    same = M.rope_tables(positions, *M.rope_frequencies(dim, c.rope_theta, RopeScaling("default")))
    assert all(np.array_equal(np.asarray(g), np.asarray(w)) for g, w in zip(same, want))


def test_the_two_kinds_take_two_tables():
    """The stack's jaxpr names both scopes, and the global kind's table is the
    window kind's only below the ramp."""
    params = FAMILY.seeded(key=1)
    tokens = _tokens()[:1, :32]
    text = jax.make_jaxpr(lambda p: FAMILY.forward(p, TOY, tokens)[0])(params).pretty_print(
        name_stack=True)
    assert "rope/window" in text and "rope/global" in text
    nope = replace(preset("debug-window-moe"))
    text = jax.make_jaxpr(lambda p: M.forward(
        p, nope, jnp.asarray(tokens), jnp.arange(32)[None], M.make_empty_cache(nope, 1, 32),
        jnp.asarray([32]), n_valid=jnp.asarray([32]))[0])(
            M.init_params(nope, jax.random.key(0))).pretty_print(name_stack=True)
    assert "rope/window" in text and "rope/global" not in text  # no position: no table


# ------------------------------------------------ the program against the reference
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_full_forward_agrees_with_the_reference(monkeypatch, form):
    """The whole forward (one chunk of 128: five windows, past the toy's
    original context of 64, key blocks with the running maximum, the lower
    bound, both forms of the expert products) against the reference at every
    own position of two ragged rows, one on each side of the original context."""
    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
    monkeypatch.setattr(M, "CHUNK_KEY_BLOCK", 16)  # eight key blocks; the window spans two
    params = FAMILY.seeded(key=1)
    tokens, lens = _tokens(), np.asarray([128, ORIGINAL - 9], np.int32)
    logits, (k, v), stats = FAMILY.forward(
        params, TOY, tokens, lens, moe=moe.moe_stats_init(TOY))
    counts = stats[0]
    assert len(stats) == 2  # counts and experts hit: no expert is absent, so none is counted so
    assert moe.dense_form(2 * 128, TOY) == (form == "dense")
    assert k.shape == v.shape == (8, 2, 2, 128, 8)
    assert _worst(logits, ARCH.forward_logits(params, TOY, tokens, lens), lens) < LOGIT_TOL
    assert int(counts.sum()) == (128 + ORIGINAL - 9) * 3 * 8  # ALL held: every choice lands here


def _control(monkeypatch, name: str):
    """Each a piece of wrong mathematics (or a lower precision than stated)."""
    s = TOY.rope_scaling_global
    if name == "the plain rotation on the global layers":
        return replace(TOY, rope_scaling_global=None)
    if name == "yarn without its attention factor":
        return replace(TOY, rope_scaling_global=replace(s, attention_factor=1.0))
    if name == "yarn on the window layers too":
        frequencies = M.rope_frequencies
        monkeypatch.setattr(M, "rope_frequencies", lambda hd, theta, scaling=None:
                            frequencies(hd, theta, s))
        return TOY
    if name == "no position on the global layers":
        return replace(TOY, position_embedding="rope_window", rope_scaling_global=None)
    if name == "no lower bound in the prefill":
        blocked = M.blocked_attention
        monkeypatch.setattr(M, "blocked_attention", lambda *a, window=0, **kw:
                            blocked(*a, window=0, **kw))
        return TOY
    if name == "the parallel block":
        return replace(TOY, parallel_block=True)
    if name == "a sigmoid gate":
        return replace(TOY, scoring_func="sigmoid")
    route = moe.route
    if name == "weights not renormalised over the chosen":
        return replace(TOY, norm_topk_prob=False)
    if name == "gate in bfloat16":
        monkeypatch.setattr(moe, "route", lambda h, lp, c: route(
            h.astype(jnp.bfloat16), {**lp, "router": lp["router"].astype(jnp.bfloat16)}, c))
    elif name == "kv in float8":
        qkv = M._window_qkv

        def narrow(h, lp, cos, sin):
            q, k, v = qkv(h, lp, cos, sin)
            return q, jax.lax.reduce_precision(k, 4, 3), jax.lax.reduce_precision(v, 4, 3)
        monkeypatch.setattr(M, "_window_qkv", narrow)
    return TOY


@pytest.mark.parametrize("name", [
    "the plain rotation on the global layers", "yarn without its attention factor",
    "yarn on the window layers too", "no position on the global layers",
    "no lower bound in the prefill", "the parallel block", "a sigmoid gate",
    "weights not renormalised over the chosen", "gate in bfloat16", "kv in float8",
])
def test_each_control_fails_the_tolerance(monkeypatch, name):
    """The same comparison with one thing wrong: 10 to 10,000 times the
    tolerance, so the tolerance tells each of them."""
    config = _control(monkeypatch, name)
    params = FAMILY.seeded(key=1)
    if config.parallel_block:
        params["layers"]["moe"].pop("mlp_norm")
    tokens, lens = _tokens(), np.asarray([128, ORIGINAL - 9], np.int32)
    logits = FAMILY.forward(params, config, tokens, lens)[0]
    assert _worst(logits, ARCH.forward_logits(FAMILY.seeded(key=1), TOY, tokens, lens),
                  lens) > 10 * LOGIT_TOL


def test_the_reference_s_rotation_is_rotate_half_with_both_tables_scaled():
    """``_rotate_halves`` against ``transformers``' form written out: ``q cos +
    rotate_half(q) sin`` with ``cos = cat(freqs, freqs)`` times the factor."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 2, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 5, 63, 64, 200])
    law = ARCH._law(TOY, ATTENTION)
    inv_freq, scale = ARCH.frequencies(law)
    emb = jnp.concatenate([pos[:, None] * inv_freq, pos[:, None] * inv_freq], axis=-1)
    cos, sin = jnp.cos(emb)[:, None] * scale, jnp.sin(emb)[:, None] * scale
    rotated_half = jnp.concatenate([-x[..., 4:], x[..., :4]], axis=-1)
    want = x * cos + rotated_half * sin
    assert float(jnp.abs(ARCH._rotate_halves(x, pos, law) - want).max()) < 1e-6
    got = M.apply_rope(x[None], *M.rope_tables(
        pos[None], *M.rope_frequencies(8, TOY.rope_theta, TOY.rope_scaling_global)))[0]
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert math.isclose(scale, 0.1 * math.log(4.0) + 1)
