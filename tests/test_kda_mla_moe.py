"""Kimi Delta Attention beside latent attention (Ling-3.0-flash-VL's kind): the
delta rule with a decay a key channel in its two forms, the group-limited
gate, the share of an expert layer, the description, and the controls that
each have to FAIL the tolerance.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import gdn, moe
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import (
    ATTENTION,
    CACHE_KINDS,
    KDA,
    ModelConfig,
    preset,
)
from calfkit_tpu.inference.mamba import make_recurrent_state
from tests.arch_harness import KDA_MLA_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------ (a) the delta rule by channel, two forms
def _plain(q, k, v, beta, g, S):
    """The recurrence, position by position: S <- Diag(exp(g)) S; u = S^T k;
    S <- S + k (x) beta (v - u); o = S^T q."""
    outs = []
    for t in range(q.shape[1]):
        S = S * jnp.exp(g[:, t])[..., None]
        u = jnp.einsum("bhkv,bhk->bhv", S, k[:, t], precision=HI)
        S = S + k[:, t][..., None] * ((v[:, t] - u) * beta[:, t][..., None])[..., None, :]
        outs.append(jnp.einsum("bhkv,bhk->bhv", S, q[:, t], precision=HI))
    return jnp.stack(outs, 1), S


def _inputs(T: int, dk: int = 16, pinned: bool = False, B: int = 2, H: int = 3):
    keys = jax.random.split(jax.random.key(T), 6)
    q = jax.random.normal(keys[0], (B, T, H, dk))
    k = jax.random.normal(keys[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, T, H, dk))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (B, T, H)))
    g = (jnp.full((B, T, H, dk), -5.0) if pinned
         else -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(keys[4], (B, T, H, dk))))
    return q, k, v, beta, g, jax.random.normal(keys[5], (B, H, dk, dk))


def test_the_step_with_a_channel_decay_is_the_plain_recurrence():
    """``delta_step_xla`` over 9 positions on a stacked state of two layers:
    each output and the final state against the recurrence; the other
    layer's slice and a row that is not active keep theirs bit for bit."""
    q, k, v, beta, g, S0 = _inputs(9)
    want_o, want_S = _plain(q, k, v, beta, g, S0)
    all_S = jnp.stack([jnp.ones_like(S0), S0])
    active = jnp.asarray([True, False])
    for t in range(9):
        o, all_S = gdn.delta_step_xla(
            all_S, jnp.int32(1), q[:, t], k[:, t], v[:, t], beta[:, t], g[:, t], active)
        assert float(jnp.abs(o[0] - want_o[0, t]).max()) < 1e-5
    assert float(jnp.abs(all_S[1, 0] - want_S[0]).max()) < 1e-5
    assert bool((all_S[1, 1] == S0[1]).all()) and bool((all_S[0] == 1.0).all())


@pytest.mark.parametrize("T,block,sub,pinned", [
    (128, 64, 16, False),  # two blocks of four sub-blocks
    (64, 64, 16, True),  # g = -5 in EVERY channel for 64 positions: e^320 one-level
    (128, 64, 16, True),
    (100, 64, 16, False),  # padded to two whole blocks
    (24, 8, 4, False),  # the toy's sizes
    (5, 8, 4, False),  # under one block: padded to whole sub-blocks
], ids=["two-blocks", "pinned-64", "pinned-128", "padded-to-blocks", "toy", "under-a-block"])
def test_the_two_level_chunk_form_is_the_step_form(T, block, sub, pinned):
    q, k, v, beta, g, S0 = _inputs(T, pinned=pinned)
    want_o, want_S = _plain(q, k, v, beta, g, S0)
    o, S = gdn.delta_chunks_by_channel(q, k, v, beta, g, S0, block, sub)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    assert float(jnp.abs(o - want_o).max()) < 2e-5
    assert float(jnp.abs(S - want_S).max()) < 2e-5


def test_one_level_overflows_where_the_two_level_form_does_not():
    """The reason for the sub-blocks: with ONE reference a block of 64 at
    g = -5, ``exp(R - G_j)`` is e^315 and the products are not finite."""
    q, k, v, beta, g, S0 = _inputs(64, pinned=True)
    o, _ = gdn.delta_chunks_by_channel(q, k, v, beta, g, S0, 64, 64)
    assert not bool(jnp.isfinite(o).all())
    o, _ = gdn.delta_chunks_by_channel(q, k, v, beta, g, S0, 64, 16)
    assert bool(jnp.isfinite(o).all())


def test_padding_rows_move_neither_state():
    """Positions past a row's ``n_valid`` (g = 0, beta = 0 there): the state
    is the one its own positions left, and the conv tail its last inputs."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=2)["layers"]["gdn"])
    h = jax.random.normal(jax.random.key(0), (2, 24, c.d_model))
    state = make_recurrent_state(c, 2)
    n = jnp.asarray([24, 13])
    _, (S, conv) = gdn.gdn_chunk(h, lp, state, jnp.int32(0), n, c)
    _, (S13, conv13) = gdn.gdn_chunk(h[1:, :13], lp, make_recurrent_state(c, 1), jnp.int32(0),
                                     jnp.asarray([13]), c)
    assert float(jnp.abs(S[0, 1] - S13[0, 0]).max()) < 1e-5
    assert float(jnp.abs(conv[0, :, 1] - conv13[0, :, 0]).max()) == 0.0


def test_a_chunk_then_steps_is_all_steps():
    """The mixer's two forms on the SAME carried pair: a chunk of 16, then 5
    steps, against 21 steps from zero state (the conv tail, the state, every
    output)."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=4)["layers"]["gdn"])
    h = jax.random.normal(jax.random.key(1), (2, 21, c.d_model))
    im = jnp.int32(0)
    steps, state = [], make_recurrent_state(c, 2)
    for t in range(21):
        y, state = gdn.gdn_step(h[:, t:t + 1], lp, state, im, None, c)
        steps.append(y[:, 0])
    y16, mixed = gdn.gdn_chunk(h[:, :16], lp, make_recurrent_state(c, 2), im,
                               jnp.asarray([16, 16]), c)
    assert float(jnp.abs(y16 - jnp.stack(steps[:16], 1)).max()) < 1e-5
    for t in range(16, 21):
        y, mixed = gdn.gdn_step(h[:, t:t + 1], lp, mixed, im, None, c)
        assert float(jnp.abs(y[:, 0] - steps[t]).max()) < 1e-5
    assert float(jnp.abs(mixed[0] - state[0]).max()) < 1e-5
    assert float(jnp.abs(mixed[1] - state[1]).max()) == 0.0


def test_the_gate_is_bounded_and_spans_its_range_by_channel():
    """``g`` lies in [kda_lower_bound, 0) and, as the architecture file seeds
    it, differs WITHIN a head by more than it differs between heads' means."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=3)["layers"]["gdn"])
    h = jax.random.normal(jax.random.key(2), (4, 32, c.d_model))
    g = np.asarray(gdn._decay(h, lp, c))
    assert g.shape == (4, 32, c.gdn_n_v_heads, c.gdn_d_k)
    assert g.min() >= c.kda_lower_bound and g.max() < 0
    per_channel = g.mean((0, 1))  # [H, dk]
    assert per_channel.std(axis=1).min() > 0.3 and g.min() < -4 and g.max() > -0.05


# ------------------------------------------------ (b) the program against the reference
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_full_forward_agrees_with_the_reference(monkeypatch, form):
    """The whole forward (one chunk: the two-level delta rule, expanded
    latent attention, both forms of the expert products) against the
    reference at every own position of two ragged rows; the counters count
    the own positions alone."""
    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
    params = FAMILY.seeded(key=1)
    tokens = np.random.default_rng(2).integers(3, TOY.vocab_size, (2, 40)).astype(np.int32)
    lens = np.asarray([40, 27], np.int32)
    logits, (c_side, r_side), (S, conv), (counts, _, absent, reach) = FAMILY.forward(
        params, TOY, tokens, lens, moe=moe.moe_stats_init(TOY))
    assert moe.dense_form(2 * 40, TOY) == (form == "dense")
    # ONE latent a token in the 2 latent layers alone; the state pair of the 4 others
    assert c_side.shape == (2, 2, 1, 40, 16) and r_side.shape == (2, 2, 1, 40, 4)
    assert S.shape == (4, 2, 4, 8, 8) and conv.shape == (4, 3, 2, 96)
    want = ARCH.forward_logits(params, TOY, tokens, lens)
    for r in range(2):
        assert np.abs(np.asarray(logits[r, : lens[r]]) - want[r, : lens[r]]).max() < LOGIT_TOL
    assert counts.shape == (5, 4)  # 5 expert layers: the first of the 6 is dense
    assert int(counts.sum()) + int(absent) == (40 + 27) * 3 * 5
    # a quarter of the experts is held; a row reaches this device if one of its
    # 2 kept groups of 4 is group 1: half the rows, by symmetry
    assert 0.1 < int(counts.sum()) / ((40 + 27) * 3 * 5) < 0.4
    assert 0.3 < int(reach) / ((40 + 27) * 5) < 0.7
    states, sent = ARCH.left_behind(params, TOY, tokens, lens)
    assert np.abs(np.asarray(S) - states[:, :, 1]).max() < 1e-4
    assert (np.asarray(counts) == sent[:, :, 1].sum(1)).all()


def test_the_stack_is_a_head_and_a_scan_of_periods():
    """Leading dense layers are unrolled with as many layers as leave the
    fewest to trace; without them the plan is the period as it was."""
    assert TOY.stack_plan == (1, (KDA, ATTENTION, KDA, KDA, ATTENTION))  # 1 + 5: the first fewest
    assert replace(TOY, n_layers=9, layer_types=TOY.layer_types[:3] * 3).stack_plan == (
        3, (KDA, KDA, ATTENTION))
    full = preset("ling-3.0-flash-vl")
    assert full.stack_plan == (6, (KDA,) * 5 + (ATTENTION,)) and full.first_k_dense == 2
    cut = replace(full, n_layers=7, layer_types=(KDA,) * 6 + (ATTENTION,), first_k_dense=1)
    assert cut.stack_plan == (1, (KDA,) * 5 + (ATTENTION,))
    assert preset("qwen3-next-80b-a3b-instruct").stack_plan == (0, ("gdn", "gdn", "gdn", ATTENTION))
    assert (full.n_recurrent_layers, full.n_kv_layers, full.n_moe_layers) == (35, 7, 40)
    assert 120e9 < full.param_count < 130e9
    assert CACHE_KINDS[KDA] == "state" and full.recurrent_kind == "Kimi Delta Attention"
    # per slot a KDA layer: S 32 x 128 x 128 float32 and a conv tail 3 x 12,288 bf16
    assert cut.recurrent_state_bytes(1) == 6 * (2_097_152 + 73_728)
    assert cut.kv_bytes_per_token() == 1152  # 576 numbers, once: ONE latent layer


# ------------------------------------------------ (c) the gate by groups, the share
def test_route_with_groups_is_the_reference_s_choice():
    """``moe.route``: the kept groups, the chosen experts and their weights
    against the architecture file's ``_chosen`` and its weights, on 200
    tokens of the seeded gate (ties apart: there are none in float32)."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=5)["layers"]["moe"])
    h = jax.random.normal(jax.random.key(3), (200, c.d_model))
    chosen, weights = moe.route(h, lp, c)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ lp["router"])
    want = np.asarray(ARCH._chosen(s, lp["router_bias"], c.n_experts_per_tok, c.n_group,
                                   c.topk_group))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(chosen), 1.0, axis=-1)
    assert (got == want).all() and (want.sum(-1) == c.n_experts_per_tok).all()
    w = np.asarray(s) * want
    w = w / w.sum(-1, keepdims=True) * c.routed_scaling_factor
    assert np.abs(np.take_along_axis(w, np.asarray(chosen), -1) - np.asarray(weights)).max() < 1e-6
    # every chosen expert lies in a kept group, and topk_group groups are kept
    per = c.experts_scored // c.n_group
    kept = moe.kept_groups(s + lp["router_bias"], c)
    assert bool(jnp.take_along_axis(kept, chosen // per, axis=-1).all())
    assert (np.asarray(kept).sum(-1) == c.topk_group).all()
    # this device holds group 1: a row reaches it iff group 1 is kept
    assert (np.asarray(moe.rows_in_held_groups(h, lp, c)) == np.asarray(kept[:, 1])).all()
    # the choice is NOT the plain top k: some token's plain top 3 leaves the kept groups
    plain = np.asarray(jax.lax.top_k(s + lp["router_bias"], c.n_experts_per_tok)[1])
    assert (np.sort(plain, -1) != np.sort(np.asarray(chosen), -1)).any()


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The four shares of ONE expert layer (a group a device): each device's
    routed part with the weights normalised over the token's WHOLE top k,
    plus the shared expert counted once, is the uncut layer's output."""
    whole = replace(TOY, n_routed_experts=16, n_experts_total=0, expert_first=0)
    lp = jax.tree.map(lambda a: a[0], M.init_params(whole, jax.random.key(6))["layers"]["moe"])
    lp["router_bias"] = jax.random.uniform(jax.random.key(7), (16,), jnp.float32, -0.05, 0.05)
    h = jax.random.normal(jax.random.key(8), (2, 12, whole.d_model))
    uncut, _ = moe.moe_ffn(h, lp, whole)
    flat = h.reshape(-1, whole.d_model)
    shared = moe._swiglu(flat, lp["s_gate"], lp["s_up"], lp["s_down"]).reshape(h.shape)
    routed = jnp.zeros_like(h)
    for rank in range(4):
        share = replace(TOY, expert_first=4 * rank)
        held = {n: (lp[n][4 * rank:4 * rank + 4] if n in ("w_gate", "w_up", "w_down") else lp[n])
                for n in lp}
        part, stats = moe.moe_ffn(h, held, share, moe.moe_stats_init(share))
        routed = routed + (part - shared)
        assert int(stats[0].sum()) + int(stats[2]) == 24 * 3
    assert float(jnp.abs(routed + shared - uncut).max()) < 1e-5


# ------------------------------------------------ (d) the controls, each of which has to FAIL
def _forward_error(config=TOY, params=None):
    params = FAMILY.seeded(key=1) if params is None else params
    tokens = np.random.default_rng(3).integers(3, TOY.vocab_size, (1, 40)).astype(np.int32)
    want = ARCH.forward_logits(params, TOY, tokens, np.asarray([40], np.int32))
    return float(np.abs(np.asarray(FAMILY.forward(params, config, tokens)[0]) - want).max())


def _decay_by_head(monkeypatch):
    right = gdn._decay

    def mean(h, lp, c):
        g = right(h, lp, c)
        return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    monkeypatch.setattr(gdn, "_decay", mean)


def _no_groups(monkeypatch):
    monkeypatch.setattr(moe, "kept_groups", lambda pick, c: jnp.ones(
        (pick.shape[0], c.n_group), bool))


def _bias_in_the_weights(monkeypatch):
    right = moe.route

    def biased(h, lp, c):
        chosen, _ = right(h, lp, c)
        s = jax.nn.sigmoid(jnp.einsum("td,de->te", h, lp["router"], precision=HI))
        w = jnp.take_along_axis(s + lp["router_bias"], chosen, axis=-1)
        return chosen, w / w.sum(-1, keepdims=True) * c.routed_scaling_factor

    monkeypatch.setattr(moe, "route", biased)


def _bfloat16_gate(monkeypatch):
    right = moe.route

    def rounded(h, lp, c):
        b = jnp.bfloat16
        return right(h.astype(b), {**lp, "router": lp["router"].astype(b)}, c)

    monkeypatch.setattr(moe, "route", rounded)


def _unbounded_gate(monkeypatch):
    def softplus(h, lp, c):
        a = jnp.einsum("...d,ed->...e", h, lp["w_alpha"], precision=HI)
        a = a.reshape(*a.shape[:-1], c.gdn_n_v_heads, c.gdn_d_k)
        return -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(a + lp["dt_bias"])

    monkeypatch.setattr(gdn, "_decay", softplus)


def _no_output_gate_kda(monkeypatch):
    right = gdn._gate_out
    monkeypatch.setattr(gdn, "_gate_out", lambda o, z, lp, c, dt: right(
        o, jnp.full_like(z, 30.0), lp, c, dt))  # sigmoid(30) = 1


def _no_output_gate_mla(monkeypatch):
    monkeypatch.setattr(M, "mla_head_gate", lambda attn, x, lp, c: attn)


WRONG = {
    "decay_taken_by_head": _decay_by_head,
    "top_8_without_groups": _no_groups,
    "bias_added_to_the_weights": _bias_in_the_weights,
    "gate_product_in_bfloat16": _bfloat16_gate,
    "unbounded_gate": _unbounded_gate,
    "output_gate_left_out_of_the_delta_rule": _no_output_gate_kda,
    "output_gate_left_out_of_the_latent_layers": _no_output_gate_mla,
}


def test_the_stated_program_passes_the_tolerance_the_controls_must_fail():
    assert _forward_error() < LOGIT_TOL


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_a_lower_precision_or_wrong_mathematics_fails_the_reference(monkeypatch, fault):
    WRONG[fault](monkeypatch)
    assert _forward_error() > 10 * LOGIT_TOL


# ------------------------------------------------ (e) the description: what it takes and refuses
def test_the_four_refusals_now_describe():
    """Latent attention in a hybrid stack, groups in the gate, leading dense
    layers in a hybrid and a decay a key channel each raised at one line of
    the parent's ``ModelConfig``: the preset holds all four."""
    full = preset("ling-3.0-flash-vl")
    assert full.latent and full.recurrent and full.kda and full.gdn
    assert (full.n_group, full.topk_group, full.first_k_dense) == (8, 4, 2)
    assert full.recurrent_state_shapes(2) == ((35, 2, 32, 128, 128), (35, 3, 2, 12288))
    assert full.cache_heads == 1 and full.cache_dims == (512, 64)
    assert full.gdn_d_in_proj == 12288 + 64  # q | k | v | one z a head | one b a head


@pytest.mark.parametrize("change,reason", [
    (dict(expert_swiglu_limits=(0.0,) * 4 + (4.0,)), "nonzero swiglu limit"),
    (dict(shared_expert_swiglu_limits=(5.0,) * 5), "shared_expert_swiglu_limits"),
    (dict(expert_swiglu_limits=(0.0,) * 3), "names 3 layers"),
    (dict(kv_lora_rank=0), "latent attention"),
    (dict(topk_method="greedy"), "noaux_tc"),
    (dict(n_group=3), "do not fit"),
    (dict(topk_group=1, n_experts_per_tok=5), "do not fit"),
    (dict(gdn_n_k_heads=2), "as many key heads"),
    (dict(kda_lower_bound=1.0), "kda_lower_bound"),
    (dict(kda_sub_block=3), "kda_sub_block"),
    (dict(layer_types=(KDA, "gdn", ATTENTION) * 2), "one recurrent kind"),
    (dict(layer_types=(KDA, "window", ATTENTION) * 2, sliding_window=8), "not described"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_stays_outside_the_description_is_refused_by_name(change, reason):
    with pytest.raises(ValueError, match=reason):
        replace(TOY, **change)


def test_what_other_stacks_do_not_describe_stays_refused():
    """The doors opened for this stack stay shut for the others."""
    gdn_toy, kimi = preset("debug-gdn-moe"), preset("kimi-vl-a3b-instruct")
    with pytest.raises(ValueError, match="leading dense"):
        replace(gdn_toy, first_k_dense=1)
    with pytest.raises(ValueError, match="latent attention in a hybrid"):
        replace(gdn_toy, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    with pytest.raises(ValueError, match="attn_output_gate"):
        replace(kimi, attn_output_gate=True)
    with pytest.raises(ValueError, match="swiglu"):
        ModelConfig(expert_swiglu_limits=(0.0,))
    assert replace(kimi, n_group=8, topk_group=4).n_group == 8  # DeepSeek-V3's own gate
