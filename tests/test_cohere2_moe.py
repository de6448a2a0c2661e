"""Window layers beside global ones (command-a-plus's kind): the program's
mathematics against the plain reference, each control FAILING the tolerance,
the kernel's window form against the XLA read, the share test.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import ATTENTION, CACHE_KINDS, WINDOW, ModelConfig, preset
from calfkit_tpu.inference.pallas_attention import (
    PallasShapeError,
    paged_decode_attention_pallas,
)
from tests.arch_harness import MELLUM_MOE
from tests.arch_harness import WINDOW_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

W = TOY.sliding_window


def _tokens(rows: int = 2, width: int = 64, seed: int = 2) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, TOY.vocab_size, (rows, width)).astype(np.int32)


def _worst(logits, want, lens) -> float:
    return max(float(np.abs(np.asarray(logits[r, :n]) - want[r, :n]).max())
               for r, n in enumerate(lens))


# ------------------------------------------------ the description
def test_the_description_lists_layer_kinds_with_their_cache_kinds():
    assert CACHE_KINDS == {"attention": "global", "window": "window", "mamba": "state",
                           "gdn": "state", "kda": "state", "conv": "state",
                           "eva": "window+summaries"}
    assert TOY.layer_period == (WINDOW, WINDOW, WINDOW, ATTENTION)
    assert (TOY.n_window_layers, TOY.n_global_layers, TOY.n_kv_layers) == (6, 2, 8)
    assert TOY.window_layer_ids == (0, 1, 2, 4, 5, 6) and TOY.global_layer_ids == (3, 7)
    # ceil((window + what a dispatch writes ahead) / page) + 1
    assert TOY.window_ring_pages(8, 4) == 5
    big = preset("command-a-plus-05-2026")
    assert big.window_ring_pages(64, 8) == 66 and big.head_dim == 128
    assert round(big.param_count / 1e9, 1) == 218.3  # the published 218B-A25B


@pytest.mark.parametrize("fields, why", [
    (dict(sliding_window=0), "sliding_window"),
    (dict(norm="batch"), "unknown norm"),
    (dict(n_routed_experts=0, n_experts_total=0, n_experts_per_tok=0, n_shared_experts=0,
          shared_expert_combine="sum"), "expert block"),
    (dict(layer_types=(WINDOW, "mamba") * 4), "recurrent"),
    (dict(shared_expert_combine="mean"), "shared_expert_combine"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "router"),
])
def test_a_description_that_is_not_this_model_is_refused_with_its_reason(fields, why):
    with pytest.raises(ValueError, match=why):
        replace(TOY, **fields)


@pytest.mark.parametrize("fields", [
    dict(sliding_window=16), dict(parallel_block=True), dict(norm="layer"),
])
def test_the_window_fields_belong_to_the_window_stack(fields):
    with pytest.raises(ValueError, match="window stack"):
        replace(preset("debug"), **fields)


@pytest.mark.parametrize("fields", [
    dict(norm="rms"), dict(parallel_block=False), dict(norm="rms", parallel_block=False),
])
def test_the_stack_runs_the_block_the_description_names(fields):
    """The window kind composes with the other norm and the sequential block:
    ``norm`` picks the norm of every layer and of the head, ``parallel_block``
    whether the FFN reads the attention's norm or one of its own AFTER the
    attention's residual add.  Against the reference's own pieces (its
    attention under each kind's mask, its expert block) put together by hand."""
    config = replace(TOY, n_layers=4, layer_types=TOY.layer_types[:4], **fields)
    params = FAMILY.seeded(config, key=2)
    if not config.parallel_block:  # a second norm a layer, off 1 like the first
        assert params["layers"]["moe"]["mlp_norm"].shape == (4, config.d_model)
        params["layers"]["moe"]["mlp_norm"] = params["layers"]["attn"]["attn_norm"][::-1] * 1.05
    else:
        assert "mlp_norm" not in params["layers"]["moe"]
    assert config.param_count - replace(config, parallel_block=True).param_count == (
        0 if config.parallel_block else 4 * config.d_model)
    tokens = _tokens()[:1, :48]
    logits, _ = FAMILY.forward(params, config, tokens, np.asarray([48], np.int32))

    def norm(x, w):
        x = x - jnp.mean(x, -1, keepdims=True) if config.norm == "layer" else x
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + config.norm_eps) * w

    layers = params["layers"]
    experts = ARCH._expert_ffn(config.n_experts_per_tok, True, 0, config.n_shared_experts)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens[0]]
        for il, kind in enumerate(config.layer_types):
            attention = ARCH._attention(kind, config.n_heads, config.n_kv_heads, config.head_dim,
                                        config.sliding_window, float(config.rope_theta), 16)
            h = norm(x, layers["attn"]["attn_norm"][il])
            a = attention(h, layers["attn"], jnp.int32(il))
            if config.parallel_block:
                x = x + a + experts(h, layers["moe"], jnp.int32(il))
            else:
                x = x + a
                x = x + experts(norm(x, layers["moe"]["mlp_norm"][il]), layers["moe"], jnp.int32(il))
        want = norm(x, params["final_norm"]) @ params["embed"].T
    assert float(jnp.max(jnp.abs(logits[0] - want))) < LOGIT_TOL


def test_a_position_within_the_routing_tie_is_left_undecided():
    """The reference's gate marks the positions whose choice among the HELD
    experts the served stream may rightly make otherwise: the last expert
    inside the top k and the first one outside within the tie, one of them
    held here.  (What the later layers' keys of such a position would be held
    to is one expert off: ``_keys_error`` leaves it out.)"""
    sent = ARCH._sent(3, 0, 4, 0.1)  # k = 3; experts 0-3 of 8 are held
    logits = jnp.asarray([
        [9.0, 8.0, 7.0, 6.95, 0.0, 0.0, 0.0, 0.0],  # the held 3 is within the tie of the held 2
        [9.0, 8.0, 0.0, 0.0, 0.0, 6.95, 7.0, 0.0],  # the tie is between 6 and 5: both held elsewhere
        [9.0, 8.0, 7.0, 0.0, 0.0, 6.95, 0.0, 0.0],  # the held 2 may lose its place to the absent 5
        [9.0, 8.0, 7.0, 6.0, 0.0, 0.0, 0.0, 0.0],  # no tie
    ], jnp.float32)
    counts, tied = sent(logits, jnp.int32(4))
    assert np.asarray(tied).tolist() == [True, False, True, False]
    assert np.asarray(counts).tolist() == [[3, 3, 2, 0], [4, 4, 3, 0]]  # first 3 and all 4 positions
    _, tied = ARCH._sent(3, 0, 4, 0.0)(logits, jnp.int32(4))
    assert not np.asarray(tied).any()  # no tie stated: every position is decided


# ------------------------------------------------ the program against the reference
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_full_forward_agrees_with_the_reference(monkeypatch, form):
    """The whole forward (one chunk past two windows: key blocks with the
    running maximum, the lower bound, both forms of the expert products)
    against the reference at every own position of two ragged rows."""
    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
    monkeypatch.setattr(M, "CHUNK_KEY_BLOCK", 16)  # four key blocks; the window spans two
    params = FAMILY.seeded(key=1)
    tokens, lens = _tokens(), np.asarray([64, 41], np.int32)
    logits, (k, v), (counts, _, absent) = FAMILY.forward(
        params, TOY, tokens, lens, moe=moe.moe_stats_init(TOY))
    assert moe.dense_form(2 * 64, TOY) == (form == "dense")
    assert k.shape == v.shape == (8, 2, 2, 64, 8)  # every layer, every position: the scratch
    assert _worst(logits, ARCH.forward_logits(params, TOY, tokens, lens), lens) < LOGIT_TOL
    assert int(counts.sum()) + int(absent) == (64 + 41) * 3 * 8
    assert 0.3 < int(counts.sum()) / ((64 + 41) * 3 * 8) < 0.7  # about half are held here


def _control(monkeypatch, name: str):
    """Each a piece of wrong mathematics (or a lower precision than stated)."""
    if name == "no lower bound":
        monkeypatch.setattr(M, "blocked_attention", lambda *a, window=0, **kw:
                            _BLOCKED(*a, window=0, **kw))
        return TOY
    if name == "rotary on the global layers":
        return replace(TOY, position_embedding="rope")
    if name == "shared experts summed":
        return replace(TOY, shared_expert_combine="sum")
    if name == "rms norm":
        monkeypatch.setattr(M, "layer_norm", lambda x, w, eps: M.rms_norm(x, w, eps))
        return TOY
    if name == "two norms":  # a sequential block's second norm in place of the ONE
        original = M.moe_ffn
        monkeypatch.setattr(M, "moe_ffn", lambda h, lp, c, *a: original(
            M.layer_norm(h, jnp.ones(h.shape[-1]), c.norm_eps), lp, c, *a))
        return TOY
    route = moe.route
    if name == "renormalised over the held":
        def held_only(h, lp, c):
            chosen, w = route(h, lp, c)
            held = (chosen >= c.expert_first) & (chosen < c.expert_first + c.n_routed_experts)
            return chosen, w / jnp.maximum(jnp.sum(jnp.where(held, w, 0), -1, keepdims=True), 1e-9)
        monkeypatch.setattr(moe, "route", held_only)
    elif name == "gate in bfloat16":
        monkeypatch.setattr(moe, "route", lambda h, lp, c: route(
            h.astype(jnp.bfloat16), {**lp, "router": lp["router"].astype(jnp.bfloat16)}, c))
    elif name == "kv in bfloat16":
        qkv = M._window_qkv

        def narrow(h, lp, cos, sin):
            q, k, v = qkv(h, lp, cos, sin)
            return q, k.astype(jnp.bfloat16).astype(k.dtype), v.astype(jnp.bfloat16).astype(v.dtype)
        monkeypatch.setattr(M, "_window_qkv", narrow)
    return TOY


_BLOCKED = M.blocked_attention


@pytest.mark.parametrize("name", [
    "no lower bound", "rotary on the global layers", "shared experts summed", "rms norm",
    "two norms", "renormalised over the held", "gate in bfloat16", "kv in bfloat16",
])
def test_each_control_fails_the_tolerance(monkeypatch, name):
    """The same comparison with one thing wrong: 10 to 10,000 times the
    tolerance, so the tolerance tells each of them."""
    config = _control(monkeypatch, name)
    params = FAMILY.seeded(key=1)
    tokens, lens = _tokens(), np.asarray([64, 41], np.int32)
    logits = FAMILY.forward(params, config, tokens, lens)[0]
    assert _worst(logits, ARCH.forward_logits(params, TOY, tokens, lens), lens) > 10 * LOGIT_TOL


def test_blocked_attention_is_the_one_pass_attention_without_a_window():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 16, 8, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 2, 64, 8)), jnp.float32) for _ in range(2))
    pos = 40 + jnp.broadcast_to(jnp.arange(16), (2, 16))
    lens = jnp.asarray([56, 56])
    want = M.attention_xla(q, k, v, pos, lens)
    got = M.blocked_attention(q, k, v, pos, lens, block=16)
    assert float(jnp.abs(got - want).max()) < 1e-5
    windowed = M.blocked_attention(q, k, v, pos, lens, window=W, block=16)
    assert float(jnp.abs(windowed - want).max()) > 1e-3  # 40-55 > 24: the bound cuts


# ------------------------------------------------ the share test
def _shared_leaves(lp):
    return {n: w for n, w in lp.items() if n.startswith("s_")}


@pytest.mark.parametrize("family", [FAMILY, MELLUM_MOE], ids=["command-a-plus", "mellum"])
def test_the_shares_parts_add_up_to_the_uncut_layer(family):
    """What the 2 shares of the experts give (each the routed sum over ITS
    held experts, weights not renormalised), the shared experts (where the
    model has any) and the attention counted ONCE, add up to the uncut layer:
    in the program (``moe_ffn``) and in the reference (a one-layer model's
    logits are linear in what the FFN adds, so its parts are compared before
    the head).  Both window stacks: command-a-plus's sigmoid gate with its
    shared experts averaged, and Mellum 2's softmax gate with none, whose cell
    holds every expert and whose toy is held by halves here."""
    arch, toy = family.arch, family.toy
    whole = replace(toy, n_layers=1, layer_types=(WINDOW,), n_routed_experts=8,
                    n_experts_total=0, expert_first=0)
    params = family.seeded(whole, key=5)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 32)), jnp.float32)
    n_shared = toy.n_shared_experts
    # the reference's expert block: (k, renormalise, first held[, shared experts])
    block = (lambda first: arch._expert_ffn(3, True, first, n_shared)) if n_shared else (
        lambda first: arch._expert_ffn(3, True, first))
    with jax.default_matmul_precision("highest"):
        full, _ = moe.moe_ffn(h, lp, whole)
        shared = full - moe.moe_ffn(
            h, {n: w for n, w in lp.items() if n not in _shared_leaves(lp)},
            replace(whole, n_shared_experts=0, shared_expert_combine="sum"))[0]
        assert bool(n_shared) == bool(float(jnp.abs(shared).max()) > 1e-3)
        parts = []
        for first in (0, 4):
            share = replace(whole, n_routed_experts=4, n_experts_total=8, expert_first=first)
            mine = {n: (w[first:first + 4] if n in ("w_gate", "w_up", "w_down") else w)
                    for n, w in lp.items()}
            parts.append(moe.moe_ffn(h, mine, share)[0] - shared)
    assert float(jnp.abs(sum(parts) + shared - full).max()) < 1e-5
    assert float(jnp.abs(parts[0]).max()) > 1e-2 < float(jnp.abs(parts[1]).max())
    # the reference, through its own expert block
    ref = {first: block(first)(
        h, {n: (w[:, first:first + 4] if n in ("w_gate", "w_up", "w_down") else w)
            for n, w in params["layers"]["moe"].items()}, jnp.int32(0)) for first in (0, 4)}
    ref_whole = block(0)(h, params["layers"]["moe"], jnp.int32(0))
    ref_shared = ref[0] - parts[0]  # its shared part, by the program's routed part
    assert float(jnp.abs(ref[0] + ref[4] - ref_shared - ref_whole).max()) < 1e-4
    assert float(jnp.abs(ref_whole - full).max()) < 1e-4


# ------------------------------------------------ the kernel's window form
_KERNEL = dict(L=3, N=40, K=2, G=2, page=8, hd=128, ring=5)


def _kernel_case(lens, seed=0):
    rng = np.random.default_rng(seed)
    c = _KERNEL
    pool = [jnp.asarray(rng.normal(size=(c["L"], c["N"], c["K"], c["page"], c["hd"])), jnp.float32)
            for _ in range(2)]
    B = len(lens)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, c["N"]))[: B * c["ring"]].reshape(B, c["ring"]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, c["K"], c["G"], c["hd"])), jnp.float32)
    return pool, tables, q, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("t", [0, 3])
@pytest.mark.parametrize("length", [
    W - 1, W, W + 1,  # the window's edge
    16, 32,  # a page edge (pages of 8), short of the window and past it
    40, 41, 47,  # the ring's first wrap (5 pages of 8)
    81, 97,  # its second wrap and beyond
    0,  # a row that is not active reads nothing
])
def test_the_decode_kernel_s_window_form_agrees_with_the_xla_read(length, t):
    """Interpret mode on the CPU against ``_window_ring_valid`` over the
    gathered ring: the page that holds ``len - W + 1`` first, its head
    masked, around the ring."""
    (pool_k, pool_v), tables, q, lens = _kernel_case([length, 29])
    q_pos = lens + t
    o, m, z = paged_decode_attention_pallas(
        q, pool_k, pool_v, jnp.int32(1), tables, lens, wpages=_KERNEL["ring"], interpret=True,
        window_starts=jnp.maximum(q_pos - W + 1, 0))
    k_ring, v_ring = (M.gather_window_paged(side[1], tables, _KERNEL["ring"], _KERNEL["hd"])
                      for side in (pool_k, pool_v))
    valid = M._window_ring_valid(k_ring.shape[2], lens, q_pos, W)
    assert int(valid[0].sum()) == max(0, min(length, W - 1 - t))
    o2, m2, z2 = M.masked_attention_source(q, k_ring, v_ring, valid)
    assert float(jnp.abs(z - z2[..., 0]).max()) < 1e-4 * max(1.0, float(z2.max()))
    assert float(jnp.abs(m - m2[..., 0]).max()) < 1e-5
    assert float(jnp.abs(o - o2).max()) < 1e-4


def test_without_a_window_the_kernel_is_the_program_it_was():
    """No ``window_starts``: no fourth scalar array and no ring arithmetic
    are traced (the four older cells' decode read: their programs' jaxpr
    hashes at the published widths equal the parent's, PERF.md section 6)."""
    (pool_k, pool_v), tables, q, lens = _kernel_case([29, 17])
    args = (q, pool_k, pool_v, jnp.int32(1), tables, lens)
    plain = str(jax.make_jaxpr(lambda *a: paged_decode_attention_pallas(
        *a, wpages=5, interpret=True))(*args))
    none = str(jax.make_jaxpr(lambda *a: paged_decode_attention_pallas(
        *a, wpages=5, interpret=True, window_starts=None))(*args))
    ring = str(jax.make_jaxpr(lambda *a: paged_decode_attention_pallas(
        *a[:-1], wpages=5, interpret=True, window_starts=a[-1]))(*args, lens))
    assert plain == none != ring and len(ring) > len(plain)


def test_the_kernel_refuses_a_slab_that_is_not_whole_tiles():
    (pool_k, pool_v), tables, q, lens = _kernel_case([29, 17])
    with pytest.raises(PallasShapeError):
        paged_decode_attention_pallas(
            q[..., :96], pool_k[..., :96], pool_v[..., :96], jnp.int32(0), tables, lens,
            wpages=5, interpret=True, window_starts=lens)


def test_ring_validity_names_the_newest_position_of_every_entry():
    """40 ring positions, 97 keys written: entry r holds position 80 + r (r
    < 17) or 40 + r; a query at 97 under a window of 24 sees 74 .. 96."""
    valid = np.asarray(M._window_ring_valid(40, jnp.asarray([97]), jnp.asarray([97]), 24))[0]
    newest = np.asarray([80 + r if r < 17 else 40 + r for r in range(40)])
    assert (valid == (newest > 97 - 24)).all() and valid.sum() == 23
