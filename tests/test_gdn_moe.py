"""Gated DeltaNet hybrid (Qwen3-Next's kind): the delta rule's two forms, the share of a
layer's experts, the description and its refusals, the benchmark's two new readers.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import hashlib
import json
import warnings
from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import gdn, moe
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import (
    ModelConfig,
    SpecConfig,
    UnsupportedWithRecurrentLayers,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import GDN_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - both_forms_at_toy_size is an autouse fixture
    STACK_LAYERS, STACK_ROUTINGS, Spy, both_forms_at_toy_size, experts_dense_rows_first,
    stack_check,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


# ------------------------------------------------ (a) the delta rule's two forms
def _recurrence(q, k, v, beta, g, S0):
    def position(S, t):
        q, k, v, b, g = t
        S = S * jnp.exp(g)[..., None, None]
        u = jnp.einsum("bhkv,bhk->bhv", S, k)
        S = S + k[..., None] * ((v - u) * b[..., None])[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    S, o = jax.lax.scan(position, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, beta, g)))
    return jnp.moveaxis(o, 0, 1), S


def _delta_inputs(T, B=2, H=4, dk=8, dv=8, key=0):
    ks = jax.random.split(jax.random.key(key), 6)
    q = gdn._l2(jax.random.normal(ks[0], (B, T, H, dk))) / np.sqrt(dk)
    k = gdn._l2(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    # log decays from forgetting within a few tokens to remembering for thousands
    g = -jnp.exp(jax.random.uniform(ks[4], (B, T, H), minval=-7.0, maxval=0.5))
    return q, k, v, beta, g, jax.random.normal(ks[5], (B, H, dk, dv))


@pytest.mark.parametrize("block", [8, 16, 64, 48])
def test_the_chunkwise_delta_rule_equals_the_recurrence(block):
    """Blocks of 8, 16 and the whole chunk, and a block that does not
    divide it (one block then), entered with a state that is not zero."""
    q, k, v, beta, g, S0 = _delta_inputs(64)
    want_o, want_S = _recurrence(q, k, v, beta, g, S0)
    o, S = gdn.delta_chunks(q, k, v, beta, g, S0, block)
    # float32 sums in two orders, values of order 1: measured 6e-7 at most
    assert np.abs(np.asarray(o) - np.asarray(want_o)).max() < 1e-5
    assert np.abs(np.asarray(S) - np.asarray(want_S)).max() < 1e-5


def test_a_padded_tail_moves_neither_the_state_nor_the_earlier_outputs():
    q, k, v, beta, g, S0 = _delta_inputs(32, key=1)
    n = np.asarray([32, 19])
    own = (np.arange(32)[None, :] < n[:, None])[..., None]
    o, S = gdn.delta_chunks(q, k, v, jnp.where(own, beta, 0.0), jnp.where(own, g, 0.0), S0, 8)
    for b in range(2):
        cut = slice(b, b + 1), slice(0, int(n[b]))
        want_o, want_S = _recurrence(*(a[cut] for a in (q, k, v, beta, g)), S0[b:b + 1])
        assert np.abs(np.asarray(o[cut]) - np.asarray(want_o)).max() < 1e-5
        assert np.abs(np.asarray(S[b:b + 1]) - np.asarray(want_S)).max() < 1e-5


def test_the_one_pass_step_equals_the_recurrence_and_keeps_inactive_rows():
    q, k, v, beta, g, S0 = _delta_inputs(1, B=3, key=2)
    all_S = jnp.stack([jnp.zeros_like(S0), S0])
    active = jnp.asarray([True, False, True])
    o, new = gdn.delta_step_xla(all_S, jnp.int32(1), q[:, 0], k[:, 0], v[:, 0], beta[:, 0],
                                g[:, 0], active)
    want_o, want_S = _recurrence(q, k, v, beta, g, S0)
    assert np.abs(np.asarray(o) - np.asarray(want_o[:, 0])).max() < 1e-6
    assert np.abs(np.asarray(new[1])[[0, 2]] - np.asarray(want_S)[[0, 2]]).max() < 1e-6
    assert np.array_equal(np.asarray(new[1][1]), np.asarray(S0[1]))  # bit for bit
    assert not np.asarray(new[0]).any()  # the other layer's slice untouched


def test_the_mixer_carries_its_states_from_chunk_to_chunk_and_into_the_steps():
    """One DeltaNet layer: 40 positions as chunks of 16, 16 and 8 of 16
    (a padded tail), blocks of 8, then 5 single steps on the state the last
    chunk left, against the reference's token-by-token layer over all 45;
    and the state they leave against the one the reference keeps for the
    row's 45 tokens (the one for 44 is another: the agreement check holds a
    served row's slot to the nearer of the two)."""
    c = TOY
    params = FAMILY.seeded()
    lp = jax.tree.map(lambda a: a[1], params["layers"]["gdn"])
    x = jax.random.normal(jax.random.key(4), (1, 45, c.d_model))
    layer, _ = ARCH._gdn(c.gdn_n_k_heads, c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v, c.gdn_d_conv,
                         float(c.norm_eps))
    out, kept = layer(x, params["layers"]["gdn"], jnp.int32(1), jnp.asarray([45]))
    want = np.asarray(out - x)[0]
    h = M.rms_norm(x, lp["mixer_norm"], c.norm_eps, True)
    one = replace(c, n_layers=1, layer_types=("gdn",))
    state = make_recurrent_state(one, 1)
    got = []
    for start, n in ((0, 16), (16, 16), (32, 8)):
        chunk = jnp.zeros((1, 16, c.d_model)).at[:, :n].set(h[:, start:start + n])
        out, state = gdn.gdn_chunk(chunk, lp, state, jnp.int32(0), jnp.asarray([n]), one)
        got.append(np.asarray(out[0, :n]))
    for t in range(40, 45):
        out, state = gdn.gdn_step(h[:, t:t + 1], lp, state, jnp.int32(0), None, one)
        got.append(np.asarray(out[0]))
    assert np.abs(np.concatenate(got) - want).max() < 1e-5
    left = np.asarray(state[0][0, 0])
    assert np.abs(left - np.asarray(kept[0, 1])).max() < 1e-5 * np.abs(left).max()
    assert np.abs(left - np.asarray(kept[0, 0])).max() > 1e-2 * np.abs(left).max()


# ------------------------------------------------ (c) the share
def _whole_layer(key: int = 6):
    """One expert layer's leaves with ALL 8 experts, and its description."""
    whole = replace(TOY, n_routed_experts=8, n_experts_total=0, expert_first=0)
    lp = jax.tree.map(lambda a: a[0], M.init_params(whole, jax.random.key(key))["layers"]["moe"])
    lp["router"] = lp["router"] * 2.0
    return whole, lp


@pytest.mark.parametrize("form", ["dense", "grouped"])
def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer(monkeypatch, form):
    """Four devices hold experts 0-1, 2-3, 4-5, 6-7 of 8; each routes over
    all 8 and computes its own experts' part plus the shared expert.  The
    four parts, the shared expert counted ONCE, are the uncut layer; every
    assignment is counted by exactly one share and absent from three."""
    monkeypatch.setattr(moe, "dense_form", lambda tokens, config: form == "dense")
    whole, lp = _whole_layer()
    h = jax.random.normal(jax.random.key(7), (3, 32, TOY.d_model))
    stats = moe.moe_stats_init(replace(whole, n_layers=1, layer_types=("gdn",)))
    want, (all_counts, _) = moe.moe_ffn(h, lp, whole, stats, None, 0)
    shared_only = moe.moe_ffn(
        h, {**lp, "w_down": jnp.zeros_like(lp["w_down"])}, whole, None, None, 0)[0]
    total, held, absent = jnp.zeros_like(want), 0, 0
    for rank in range(4):
        share = replace(TOY, n_layers=1, layer_types=("gdn",), n_routed_experts=2,
                        n_experts_total=8, expert_first=2 * rank)
        mine = {**lp, **{n: lp[n][2 * rank: 2 * rank + 2] for n in ("w_gate", "w_up", "w_down")}}
        part, (counts, _, away) = moe.moe_ffn(h, mine, share, moe.moe_stats_init(share), None, 0)
        assert np.array_equal(np.asarray(counts[0]), np.asarray(all_counts[0, 2 * rank: 2 * rank + 2]))
        total, held, absent = total + (part - shared_only), held + int(counts.sum()), absent + int(away)
    # float32 sums in two orders, outputs of order 1: measured 5e-7 at most
    assert np.abs(np.asarray(total + shared_only) - np.asarray(want)).max() < 1e-5
    assert held == 96 * 3 and absent == 3 * 96 * 3
    # and a share is NOT the uncut layer (the absent experts' part is left out)
    assert np.abs(np.asarray(part) - np.asarray(want)).max() > 1e-2


def test_a_share_s_two_forms_agree_with_each_other_and_the_reference(monkeypatch):
    c = replace(TOY, n_layers=1, layer_types=("gdn",))
    params = FAMILY.seeded(c, key=8)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    h = jax.random.normal(jax.random.key(9), (2, 24, c.d_model))
    dense = moe.moe_ffn(h, lp, c, None, None, 0)[0]
    monkeypatch.setattr(moe, "dense_form", lambda tokens, config: False)
    grouped = moe.moe_ffn(h, lp, c, None, None, 0)[0]
    assert np.abs(np.asarray(dense) - np.asarray(grouped)).max() < 1e-5
    # the reference's block takes the un-normed stream and adds it back
    experts = ARCH._expert_ffn(c.n_experts_per_tok, True, c.expert_first, float(c.norm_eps))
    x = jax.random.normal(jax.random.key(10), (2, 24, c.d_model))
    normed = M.rms_norm(x, lp["mlp_norm"], c.norm_eps, True)
    want = experts(x, params["layers"]["moe"], jnp.int32(0)) - x
    assert np.abs(np.asarray(moe.moe_ffn(normed, lp, c, None, None, 0)[0]) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("m", range(STACK_LAYERS))
@pytest.mark.parametrize("case", [*STACK_ROUTINGS, "every_pair_absent"])
def test_a_share_s_grouped_experts_read_their_layer_out_of_the_stack(case, m):
    """The same over experts held by SHARE (4 of 8, from the fifth): the
    pairs whose expert is held elsewhere sort behind the LAST of the stack's
    groups, not behind this layer's, and no group's product reaches them;
    with every pair absent the routed part is exactly zero."""
    stack_check(TOY, case, m)


def test_the_gate_is_a_softmax_over_all_the_experts_normalised_over_the_chosen():
    _, lp = _whole_layer()
    h = jax.random.normal(jax.random.key(11), (64, TOY.d_model))
    chosen, weights = moe.route(h, lp, TOY)
    probs = jax.nn.softmax(h @ lp["router"], axis=-1)
    assert chosen.shape == (64, 3) and int(chosen.max()) > 3  # experts held elsewhere are chosen too
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    assert np.abs(np.asarray(weights) - np.asarray(picked / picked.sum(-1, keepdims=True))).max() < 1e-6
    assert np.abs(np.asarray(weights.sum(-1)) - 1.0).max() < 1e-6
    _, raw = moe.route(h, lp, replace(TOY, norm_topk_prob=False))
    assert np.abs(np.asarray(raw) - np.asarray(picked)).max() < 1e-6


def test_the_dense_form_s_limit_follows_the_shape_and_kimi_s_has_not_moved(monkeypatch):
    monkeypatch.undo()  # the measured limits, not the toy one
    kimi = preset("kimi-vl-a3b-instruct")
    # ONE row TIMED on the chip: Kimi's crossing.  LFM2's shape has NO row since PR 45,
    # Ling's none since PR 53 (its row of 0 sent its decode steps to the grouped form,
    # which read the experts a step hits alone, ~27 of 64 in its cell; the step kernel
    # reads them now, and an engine without it runs the dense form, the reference)
    assert moe._DENSE_TO_THE_CROSSING == {(64, 2048, 1408): 1536}
    lfm2 = preset("lfm2-8b-a1b")
    assert moe.dense_form(1, lfm2) and moe.dense_form(128, lfm2) and moe.dense_form(512, lfm2)
    assert not moe.dense_form(1024, lfm2)
    ling = replace(preset("ling-3.0-flash-vl"), n_routed_experts=64, n_experts_total=512)
    assert moe.dense_form(1, ling) and moe.dense_form(128, ling) and moe.dense_form(512, ling)
    assert not moe.dense_form(1024, ling)
    assert moe.dense_form(1536, kimi) and not moe.dense_form(1537, kimi)
    # any other shape, this model's share or another, keeps the dense form to
    # the decode steps' rows and narrow chunks: no chunk of 1,024 beside them
    for held in (64, 128, 256):
        share = replace(preset("qwen3-next-80b-a3b-instruct"), n_routed_experts=held,
                        n_experts_total=512)
        assert moe.dense_form(64, share) and moe.dense_form(moe._DENSE_MAX_TOKENS, share)
        assert not moe.dense_form(1024, share)
    assert moe._DENSE_MAX_TOKENS == 512
    assert not moe.dense_form(1024, replace(kimi, moe_d_ff=1024))


# toy widths (1/64) of the five shapes a cell holds, each with its gate: Kimi's (the
# biased sigmoid, two shared experts summed), Qwen3-Next's (a softmax over 512, 128 held
# from the 129th, a gated shared expert), command-a-plus's (the greedy sigmoid, 16 of 128
# held, four shared experts averaged), Ling's (the choice by group, 64 of 512 held: one
# group), LFM2's (the biased sigmoid with its 1e-6, NO shared expert)
_HELD_SHAPES = {
    "kimi": dict(n_routed_experts=64, d_model=32, moe_d_ff=22, n_experts_per_tok=6,
                 n_shared_experts=2, routed_scaling_factor=2.446, topk_method="noaux_tc"),
    "qwen3-next": dict(n_routed_experts=128, experts_scored=512, expert_first=128, d_model=32,
                       moe_d_ff=8, n_experts_per_tok=10, n_shared_experts=1,
                       shared_expert_gate=True, scoring_func="softmax"),
    "command-a-plus": dict(n_routed_experts=16, experts_scored=128, expert_first=32, d_model=64,
                           moe_d_ff=64, n_experts_per_tok=8, n_shared_experts=4,
                           shared_expert_combine="average"),
    "ling": dict(n_routed_experts=64, experts_scored=512, expert_first=192, d_model=40,
                 moe_d_ff=12, n_experts_per_tok=8, n_shared_experts=1, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, topk_method="noaux_tc"),
    "lfm2": dict(n_routed_experts=32, d_model=32, moe_d_ff=28, n_experts_per_tok=4,
                 topk_method="noaux_tc", topk_norm_eps=1e-6),
}


def _held_layer(fields: dict, key: int):
    """(what ``moe`` reads of a configuration, one expert layer's float32 leaves)."""
    c = SimpleNamespace(**{
        "experts_scored": fields["n_routed_experts"], "expert_first": 0, "n_shared_experts": 0,
        "shared_expert_gate": False, "shared_expert_combine": "sum", "scoring_func": "sigmoid",
        "topk_method": "greedy", "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1.0, "topk_norm_eps": 1e-20, "n_moe_layers": 1, "norm_plus_one": False,
        **fields})
    c.expert_share = c.experts_scored != c.n_routed_experts
    lp = jax.tree.map(lambda a: a[0], moe.init_moe_params(c, jax.random.key(key), jnp.float32))
    lp["router"] = lp["router"] * 4.0  # scores that spread: the choice is no near-tie
    if "router_bias" in lp:
        lp["router_bias"] = jax.random.uniform(
            jax.random.key(key + 1), lp["router_bias"].shape, jnp.float32, -0.1, 0.1)
    return c, lp


@pytest.mark.parametrize("shape", sorted(_HELD_SHAPES))
def test_the_respelled_dense_form_is_the_old_product_and_the_grouped_one(monkeypatch, shape):
    """PR 45 changed the dense form's SPELLING (the weights first) and nothing
    of its mathematics: at toy widths of each held shape, under its own gate
    (a share, a choice by group, a shared expert or none), the products equal
    the old einsum's and the grouped form's on the same routing, and the
    whole layer through either form is one layer."""
    c, lp = _held_layer(_HELD_SHAPES[shape], key=45)
    E = c.n_routed_experts
    h = jax.random.normal(jax.random.key(46), (48, c.d_model), jnp.float32)
    chosen, weights = moe.route(h, lp, c)
    onehot = chosen[..., None] == jnp.arange(E, dtype=jnp.int32) + c.expert_first
    held = float(jnp.mean(jnp.any(onehot, axis=-1)))
    # a share holds SOME of the pairs (Ling's one group: a row's kept groups or not)
    assert (0.05 < held < 0.9) if c.expert_share else held == 1.0
    want = experts_dense_rows_first(h, onehot, weights, lp)
    assert float(jnp.abs(want).max()) > 0.05
    dense = moe.experts_dense(h, onehot, weights, lp)
    # float32 sums in two orders, outputs of order 0.1-1
    assert np.abs(np.asarray(dense) - np.asarray(want)).max() < 1e-4
    stack = {n: lp[n][None] for n in ("w_gate", "w_up", "w_down")}
    grouped = moe.experts_grouped(h, chosen, onehot, weights, stack, 0, c.expert_share)
    assert np.abs(np.asarray(dense) - np.asarray(grouped)).max() < 1e-4
    # and the layer (the shared expert, its gate, its mean) through either form
    layer = {}
    for form in (True, False):
        monkeypatch.setattr(moe, "dense_form", lambda tokens, config, form=form: form)
        layer[form] = moe.moe_ffn(h.reshape(2, 24, -1), lp, c, None, None, 0)[0]
    assert np.abs(np.asarray(layer[True]) - np.asarray(layer[False])).max() < 1e-4
    routed_only = np.abs(np.asarray(layer[True]).reshape(48, -1) - np.asarray(dense)).max()
    assert (routed_only > 1e-2) if c.n_shared_experts else (routed_only < 1e-6)


# ------------------------------------------------ (e) the description and its refusals
def test_the_description_counts_what_the_published_model_has():
    c = preset("qwen3-next-80b-a3b-instruct")
    assert c.param_count == 79_674_391_296 and c.head_dim == 256 and c.rotary_dim == 64
    assert c.layer_period == ("gdn", "gdn", "gdn", "attention")
    assert (c.n_recurrent_layers, c.n_kv_layers, c.n_moe_layers) == (36, 12, 48)
    assert (c.gdn_conv_dim, c.gdn_d_in_proj) == (8192, 12352)
    cut = replace(c, n_layers=8, layer_types=c.layer_types[:8], n_routed_experts=128,
                  n_experts_total=512, vocab_size=37984)
    assert cut.param_count == 3_667_251_328 and cut.expert_share
    assert cut.recurrent_state_shapes(64) == ((6, 64, 32, 128, 128), (6, 3, 64, 8192))
    assert cut.recurrent_state_bytes(1) == 12_877_824 and cut.kv_bytes_per_token() == 4096
    assert not c.expert_share and c.experts_scored == 512 and c.recurrent_kind == "Gated DeltaNet"
    g = preset("granite-4.0-h-micro")
    assert g.recurrent_kind == "Mamba-2" and not g.gdn and g.n_recurrent_layers == 36
    assert g.recurrent_state_bytes(64) == 4_892_000_256  # as it was


@pytest.mark.parametrize("fields, why", [
    (dict(layer_types=("gdn", "mamba") * 4), "one stack"),
    (dict(gdn_n_k_heads=3), "divide"),
    (dict(expert_first=6), "not among"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "router"),
    (dict(first_k_dense=1), "every layer"),
    (dict(partial_rotary_factor=0.2), "even"),
])
def test_a_description_that_is_not_described_is_refused(fields, why):
    with pytest.raises(ValueError, match=why):
        replace(TOY, **fields)


def test_the_new_fields_belong_to_their_stack():
    with pytest.raises(ValueError, match="Gated DeltaNet hybrid"):
        replace(preset("debug"), qk_norm=True)
    with pytest.raises(ValueError, match="routed experts"):
        replace(preset("debug"), n_experts_total=8)
    with pytest.raises(ValueError, match="latent-attention stack"):
        replace(preset("granite-4.0-h-micro"), n_routed_experts=8, n_experts_per_tok=2, moe_d_ff=64)


@pytest.mark.parametrize("option, kw, why", [
    ("speculative", dict(speculative=SpecConfig()), "no state snapshot"),
    ("tp > 1", dict(tp=2), "no sharding"),
    ("dp > 1", dict(dp=2), "no sharding"),
    ("quantization", dict(quantization="int8"), "no scales"),
    ("long_context", dict(long_context=True), "carries no recurrent state"),
    ("kv_layout='dense'", dict(kv_layout="dense", chunked_prefill=False), "served from pages"),
])
def test_what_has_no_code_is_refused_at_construction_with_its_reason(option, kw, why):
    with pytest.raises(UnsupportedWithRecurrentLayers, match=why) as refused:
        InferenceEngine(TOY, FAMILY.runtime(**kw))
    assert option in str(refused.value) and "Gated DeltaNet" in str(refused.value)


def test_an_explicit_kernel_outside_its_rule_and_a_quantized_tree_are_refused():
    from calfkit_tpu.inference.pallas_attention import PallasShapeError
    from calfkit_tpu.inference.quant import quantize_params

    with pytest.raises(PallasShapeError, match="head_dim=16"):
        InferenceEngine(TOY, FAMILY.runtime(attention_impl="pallas"))
    with pytest.raises(ValueError, match="no scales"):
        quantize_params(M.init_params(TOY, jax.random.key(0)))


def test_the_new_counter_reaches_metrics_and_the_state_reaches_capacity():
    from calfkit_tpu.observability.capacity import recurrent_bytes_per_token
    from calfkit_tpu.observability.metrics import metrics_text

    FAMILY.serve((TOY, FAMILY.runtime()), [(FAMILY.prompt_of(20), 3)])
    text = metrics_text()
    for name in ("calfkit_engine_moe_assignments_total",
                 "calfkit_engine_moe_assignments_absent_total",
                 "calfkit_engine_recurrent_state_bytes"):
        assert name in text
    assert recurrent_bytes_per_token(TOY) == 2.0 * TOY.recurrent_state_bytes(1)


# ------------------------------------------------ (h) the benchmark's two new readers
def test_the_two_new_readers_read_what_a_hand_reckons_and_nothing_elsewhere():
    pct, roofline = manifest.load_reader("gdn_device_pct"), manifest.load_reader("gdn_state_roofline")
    parent = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {"decode_loop/mamba/ssm": 1.5, "(unscoped)": 0.5}},
        trace_counters={"decode_tokens": 100, "decode_dispatches": 5, "short_dispatches": 0},
        arch=manifest.load_architecture("granite-hybrid"), config={}, chips=1,
        runtime=SimpleNamespace(decode_steps_per_dispatch=8), peaks={})
    assert pct(parent) is None and roofline(parent) is None
    assert pct(SimpleNamespace(trace_reduced=None)) is None
    assert roofline(SimpleNamespace(trace_reduced=None, trace_counters=None, arch=None)) is None
    with open(manifest.ROOT + "/benchmarks/configs/qwen3-next-80b-a3b-instruct.json") as f:
        config = json.load(f)
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {
            "decode_loop/gdn/state": 0.5, "decode_loop/gdn/conv": 0.1,
            "decode_loop/gdn/in_proj": 0.1, "chunk_loop/gdn/state": 0.2,
            "decode_loop/mlp/moe/experts": 0.9}},
        trace_counters={"decode_tokens": 56 * 40, "decode_dispatches": 5, "short_dispatches": 0},
        arch=ARCH, config=config, chips=1, runtime=SimpleNamespace(decode_steps_per_dispatch=8),
        peaks=manifest.load_peaks("TPU v5 lite"))
    assert pct(run) == pytest.approx(45.0)
    # 40 steps x 2 x 56 rows x 12,877,824 B at 819 GB/s over 0.6 s measured
    assert roofline(run) == pytest.approx(100 * 40 * 2 * 56 * 12_877_824 / 819e9 / 0.6)
    assert roofline(run) < 100

