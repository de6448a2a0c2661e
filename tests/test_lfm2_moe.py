"""A gated short convolution beside rotary GQA attention with normed heads
(LFM2-8B-A1B's kind): the mixer in its two forms, the tail it leaves behind,
the gate with its bias in the choice only and its ``+ 1e-6``, the cut in depth,
the description, and the controls that each have to FAIL the tolerance.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe, shortconv
from calfkit_tpu.inference.config import (
    ATTENTION,
    CACHE_KINDS,
    CONV,
    ModelConfig,
    preset,
)
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.observability import capacity
from tests.arch_harness import LFM2_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

HI = jax.lax.Precision.HIGHEST


def _conv_leaves(key: int = 2):
    return jax.tree.map(lambda a: a[0], FAMILY.seeded(key=key)["layers"]["conv"])


def _plain(h, lp):
    """The mixer as written: ``u = B * x`` zero before the sequence, three
    taps, ``(C * v) W_out`` -> (out [B, T, D], u [B, T, D])."""
    with jax.default_matmul_precision("highest"):
        bcx = jnp.einsum("btd,ed->bte", h, lp["w_in"])
        D = h.shape[-1]
        u, gate_c = bcx[..., :D] * bcx[..., 2 * D:], bcx[..., D:2 * D]
        K = lp["conv_w"].shape[0]
        before = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        v = sum(before[:, j:j + h.shape[1]] * lp["conv_w"][j] for j in range(K))
        return (gate_c * v) @ lp["w_out"], u


# ------------------------------------------------ (a) the mixer's two forms and its tail
def test_the_step_form_is_the_plain_convolution():
    """21 steps from a zero tail on a stacked pair of 9 layers: every output
    against the padded sum; the tail is ``u`` at the last two positions; the
    other layers' slices and a row that is not active keep theirs bit for bit."""
    c, lp = TOY, _conv_leaves()
    h = jax.random.normal(jax.random.key(1), (2, 21, c.d_model))
    want, u = _plain(h, lp)
    state = make_recurrent_state(c, 2)
    assert state[0].shape == (9, 2, 0) and state[1].shape == (9, 2, 2, c.d_model)
    state = (state[0], state[1] + 1.0)
    state = (state[0], state[1].at[4].set(0.0))
    active = jnp.asarray([True, False])
    for t in range(21):
        y, state = shortconv.shortconv_step(h[:, t:t + 1], lp, state, jnp.int32(4), active)
        assert float(jnp.abs(y[0, 0] - want[0, t]).max()) < 1e-5
    assert float(jnp.abs(state[1][4, :, 0] - u[0, 19:21]).max()) < 1e-6
    assert bool((state[1][4, :, 1] == 0.0).all())  # the row that is not active
    assert bool((state[1][:4] == 1.0).all()) and bool((state[1][5:] == 1.0).all())
    assert state[0].size == 0  # no matrix state: nothing to read or write


@pytest.mark.parametrize("first,then", [(16, 5), (1, 20), (20, 1), (2, 19)],
                         ids=["across-a-chunk-edge", "a-chunk-of-one-token", "one-step-after",
                              "a-chunk-of-two"])
def test_a_chunk_then_steps_is_all_steps(first, then):
    """The two forms on the SAME carried tail: a chunk of ``first`` positions
    (a chunk of ONE token leaves the zero before the sequence and its own
    ``u``), then ``then`` steps, against the plain convolution of all 21."""
    c, lp = TOY, _conv_leaves(4)
    h = jax.random.normal(jax.random.key(1), (2, first + then, c.d_model))
    want, u = _plain(h, lp)
    im = jnp.int32(0)
    y, state = shortconv.shortconv_chunk(
        h[:, :first], lp, make_recurrent_state(c, 2), im, jnp.asarray([first, first]), c)
    assert float(jnp.abs(y - want[:, :first]).max()) < 1e-5
    before = jnp.pad(u, ((0, 0), (2, 0), (0, 0)))
    assert float(jnp.abs(state[1][0] - jnp.swapaxes(before[:, first:first + 2], 0, 1)).max()) < 1e-6
    for t in range(first, first + then):
        y, state = shortconv.shortconv_step(h[:, t:t + 1], lp, state, im, None)
        assert float(jnp.abs(y[:, 0] - want[:, t]).max()) < 1e-5
    assert float(jnp.abs(state[1][0] - jnp.swapaxes(u[:, -2:], 0, 1)).max()) < 1e-6


def test_a_chunk_that_starts_mid_sequence_takes_the_tail_it_is_handed():
    """Two chunks of a wave of rows of UNEQUAL length (24 | 13 of 24, then 8 |
    0 of 8): the second chunk's first outputs read the first chunk's tail; a
    row's padding moves no tail (its tail is its last two REAL inputs), and a
    row that is ALL padding in a chunk keeps the tail it came with."""
    c, lp = TOY, _conv_leaves(5)
    h = jax.random.normal(jax.random.key(3), (2, 32, c.d_model))
    want, u = _plain(h, lp)
    short, _ = _plain(h[1:, :13], lp)
    im = jnp.int32(0)
    y1, state = shortconv.shortconv_chunk(
        h[:, :24], lp, make_recurrent_state(c, 2), im, jnp.asarray([24, 13]), c)
    assert float(jnp.abs(y1[0] - want[0, :24]).max()) < 1e-5
    assert float(jnp.abs(y1[1, :13] - short[0]).max()) < 1e-5
    assert float(jnp.abs(state[1][0, :, 1] - u[1, 11:13]).max()) < 1e-6  # not positions 22, 23
    y2, state = shortconv.shortconv_chunk(h[:, 24:], lp, state, im, jnp.asarray([8, 0]), c)
    assert float(jnp.abs(y2[0] - want[0, 24:]).max()) < 1e-5
    assert float(jnp.abs(state[1][0, :, 0] - u[0, 30:32]).max()) < 1e-6
    assert float(jnp.abs(state[1][0, :, 1] - u[1, 11:13]).max()) < 1e-6  # all padding: kept


# ------------------------------------------------ (b) the program against the reference
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_full_forward_agrees_with_the_reference(monkeypatch, form):
    """The whole forward (one chunk: the conv's chunk form, GQA with normed
    heads, both forms of the expert products) against the reference at every
    own position of two ragged rows; the counters count the own positions
    alone; the tails are the reference's ``u`` at each row's last two."""
    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
    params = FAMILY.seeded(key=1)
    tokens = np.random.default_rng(2).integers(3, TOY.vocab_size, (2, 40)).astype(np.int32)
    lens = np.asarray([40, 27], np.int32)
    logits, (k_side, v_side), (empty, tail), (counts, hit) = FAMILY.forward(
        params, TOY, tokens, lens, moe=moe.moe_stats_init(TOY))
    assert moe.dense_form(2 * 40, TOY) == (form == "dense")
    # K and V in the 3 attention layers alone; a tail in the 9 others; no matrix state
    assert k_side.shape == v_side.shape == (3, 2, 2, 40, 8)
    assert empty.shape == (9, 2, 0) and tail.shape == (9, 2, 2, 32)
    want = ARCH.forward_logits(params, TOY, tokens, lens)
    for r in range(2):
        assert np.abs(np.asarray(logits[r, : lens[r]]) - want[r, : lens[r]]).max() < LOGIT_TOL
    assert counts.shape == (10, 8)  # 10 expert layers: the first 2 of the 12 are dense
    assert int(counts.sum()) == (40 + 27) * 3 * 10  # every expert is held: none absent
    # the seeded gate spreads its choices: no expert takes over a third of a layer's
    assert int(counts.max(axis=1).max()) < (40 + 27) * 3 / 3
    left = ARCH.left_behind(params, TOY, tokens, lens)  # [Lc, B, 2, taps - 1, D]
    assert np.abs(np.asarray(tail) - np.moveaxis(left[:, :, 1], 1, 2)).max() < 1e-5


def test_the_cut_is_layers_0_to_11_of_the_24_layer_model():
    """The test that ties the cut to the model: the stream the 12-layer cut
    leaves BEFORE its final norm is what the first 12 layers of the 24-layer
    reference leave, on the same leaves (the whole model's tree, its first 12
    layers sliced out group by group)."""
    kinds = tuple(ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))
    whole = replace(TOY, n_layers=24, layer_types=kinds)
    assert kinds[:12] == TOY.layer_types and whole.stack_plan[1] != TOY.stack_plan[1]
    params = FAMILY.seeded(whole, key=6)
    cut_tree = {**params, "layers": {
        "attn": jax.tree.map(lambda a: a[:3], params["layers"]["attn"]),
        "conv": jax.tree.map(lambda a: a[:9], params["layers"]["conv"]),
        "dense": params["layers"]["dense"],
        "moe": jax.tree.map(lambda a: a[:10], params["layers"]["moe"]),
    }}
    tokens = np.random.default_rng(5).integers(3, TOY.vocab_size, (1, 33)).astype(np.int32)
    lens = np.asarray([33], np.int32)
    want = ARCH.hidden_after(params, whole, tokens, lens, layers=12)
    assert np.abs(ARCH.hidden_after(cut_tree, TOY, tokens, lens, layers=12) - want).max() == 0.0
    # ... and the program's cut serves that stream: its logits are the final norm
    # and the tied head of it
    with jax.default_matmul_precision("highest"):
        h = ARCH._rms(jnp.asarray(want), params["final_norm"], TOY.norm_eps)
        logits = np.asarray(jnp.einsum("bsd,vd->bsv", h, params["embed"]))
    got = np.asarray(FAMILY.forward(cut_tree, TOY, tokens)[0])
    assert np.abs(got - logits).max() < LOGIT_TOL


def test_the_stack_is_a_head_of_four_and_two_periods():
    """``stack_plan`` as it stands gives the cell's cut a head of 4 layers
    (two dense, then an attention and a conv layer with experts) and two
    periods of ``c c A c``; the whole model a head of 18."""
    full = preset("lfm2-8b-a1b")
    cut = replace(full, n_layers=12, layer_types=full.layer_types[:12])
    assert cut.layer_types == (CONV, CONV, ATTENTION, CONV) * 3
    assert cut.stack_plan == (4, (CONV, CONV, ATTENTION, CONV)) == TOY.stack_plan
    assert full.stack_plan == (18, (ATTENTION, CONV, CONV))
    assert (full.n_recurrent_layers, full.n_kv_layers, full.n_moe_layers) == (18, 6, 22)
    assert (cut.n_recurrent_layers, cut.n_kv_layers, cut.n_moe_layers) == (9, 3, 10)
    assert 8.33e9 < full.param_count < 8.35e9 and 3.92e9 < cut.param_count < 3.94e9
    assert CACHE_KINDS[CONV] == "state" and full.recurrent_kind == "gated short convolution"
    assert full.shortconv and full.recurrent and full.expert_hybrid and not full.gdn
    # per slot: 9 layers x 2 positions x 2,048 channels of bfloat16 and NOTHING else
    assert cut.recurrent_state_shapes(128) == ((9, 128, 0), (9, 2, 128, 2048))
    assert cut.recurrent_state_bytes(1) == 73_728
    assert cut.kv_bytes_per_token() == 6144  # 3 attention layers x 2 x 8 heads of 64, bfloat16
    # what the capacity observatory charges a decoded token: the tail in and out
    assert capacity.recurrent_bytes_per_token(cut) == 2 * 73_728
    assert capacity.hbm_constants(cut)[1] == 6144.0


# ------------------------------------------------ (c) the gate
def test_route_is_the_reference_s_choice_bias_in_the_choice_only():
    """``moe.route``: the 3 largest of ``s + bias`` and the UNBIASED scores
    over ``sum + 1e-6`` against the architecture file's own, on 200 tokens of
    the seeded gate; some token's choice differs from the plain top 3 of ``s``."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=5)["layers"]["moe"])
    h = jax.random.normal(jax.random.key(3), (200, c.d_model))
    chosen, weights = moe.route(h, lp, c)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    want = np.argsort(-(s + np.asarray(lp["router_bias"])), axis=-1)[:, :c.n_experts_per_tok]
    assert (np.sort(np.asarray(chosen), -1) == np.sort(want, -1)).all()
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    assert np.abs(w - np.asarray(weights)).max() < 1e-6
    plain = np.argsort(-s, axis=-1)[:, :c.n_experts_per_tok]
    assert (np.sort(plain, -1) != np.sort(want, -1)).any()
    assert c.topk_norm_eps == 1e-6 and preset("kimi-vl-a3b-instruct").topk_norm_eps == 1e-20


def test_the_published_epsilon_shows_where_the_scores_are_small():
    """``+ 1e-6`` against ``+ 1e-20``: where a token's three scores are 3.4e-6
    each (logits of -12.6) the weights sum to 10 / 11 and not to 1: the
    program's are the published ones."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=5)["layers"]["moe"])
    cold = {**lp, "router": jnp.full_like(lp["router"], -12.6 / c.d_model)}
    _, weights = moe.route(jnp.ones((8, c.d_model)), cold, c)
    s = float(jax.nn.sigmoid(-12.6))
    total = np.asarray(weights).sum(-1)
    assert np.allclose(total, 3 * s / (3 * s + 1e-6), rtol=1e-4) and total[0] < 0.92


def test_a_tie_at_the_gate_is_broken_the_same_way_in_both():
    """Two experts with the SAME ``s + bias`` at the k-th place: the program's
    ``top_k`` and the reference's both take the lower index (ties happen in
    float32 only by construction; in bfloat16 streams ``routing_tie`` follows
    both choices)."""
    c = TOY
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=5)["layers"]["moe"])
    router = jnp.zeros_like(lp["router"])
    bias = jnp.asarray([0.3, 0.2, 0.1, 0.1, 0.1, 0.0, 0.0, 0.0], jnp.float32)
    chosen, _ = moe.route(jnp.ones((4, c.d_model)), {**lp, "router": router, "router_bias": bias}, c)
    assert (np.sort(np.asarray(chosen), -1) == np.asarray([0, 1, 2])).all()
    from benchmarks.routing_tie import routings

    parent, picked, first, crowded = routings(np.asarray(0.5 + bias)[None], 3, 0.008)
    assert len(parent) == 3 and first.tolist() == [True, False, False] and not crowded.any()
    assert picked[0].tolist() == [1, 1, 1, 0, 0, 0, 0, 0]


# ------------------------------------------------ (d) the controls, each of which has to FAIL
def _forward_error(config=TOY, params=None, lens=None):
    params = FAMILY.seeded(key=1) if params is None else params
    tokens = np.random.default_rng(3).integers(3, TOY.vocab_size, (1, 40)).astype(np.int32)
    n = 40 if lens is None else int(lens[0])
    want = ARCH.forward_logits(params, TOY, tokens, np.asarray([n], np.int32))
    got = np.asarray(FAMILY.forward(params, config, tokens, lens)[0])
    return float(np.abs(got[:, :n] - want[:, :n]).max())


def _taps_summed_in_bfloat16(monkeypatch):
    monkeypatch.setattr(shortconv, "_SUM_DTYPE", jnp.bfloat16)


def _bias_in_the_weights(monkeypatch):
    right = moe.route

    def biased(h, lp, c):
        chosen, _ = right(h, lp, c)
        s = jax.nn.sigmoid(jnp.einsum("td,de->te", h, lp["router"], precision=HI))
        w = jnp.take_along_axis(s + lp["router_bias"], chosen, axis=-1)
        return chosen, w / (w.sum(-1, keepdims=True) + 1e-6) * c.routed_scaling_factor

    monkeypatch.setattr(moe, "route", biased)


def _heads_norm_left_out(monkeypatch):
    right = M.gated_attn_qkv
    monkeypatch.setattr(M, "gated_attn_qkv", lambda x, lp, cos, sin, c: right(
        x, lp, cos, sin, replace(c, qk_norm=False)))


def _activation_after_the_conv(monkeypatch):
    right = shortconv._gate_out
    monkeypatch.setattr(shortconv, "_gate_out", lambda v, gate_c, lp, dt: right(
        jax.nn.silu(v), gate_c, lp, dt))


def _thirds_in_another_order(monkeypatch):
    right = shortconv._in_proj

    def swapped(h, lp):
        gate_b, gate_c, x = right(h, lp)
        return gate_c, gate_b, x

    monkeypatch.setattr(shortconv, "_in_proj", swapped)


WRONG = {
    "taps_summed_in_bfloat16": _taps_summed_in_bfloat16,
    "bias_added_to_the_weights": _bias_in_the_weights,
    "heads_norm_left_out": _heads_norm_left_out,
    "activation_after_the_conv": _activation_after_the_conv,
    "thirds_in_another_order": _thirds_in_another_order,
}


def test_the_stated_program_passes_the_tolerance_the_controls_must_fail():
    assert _forward_error() < LOGIT_TOL


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_a_lower_precision_or_wrong_mathematics_fails_the_reference(monkeypatch, fault):
    WRONG[fault](monkeypatch)
    assert _forward_error() > 10 * LOGIT_TOL


def test_a_tail_written_from_a_padded_position_fails_the_next_chunk(monkeypatch):
    """The control the chunk form's ``n_valid`` exists for: a tail read at
    the chunk's END (its padding) and not at the row's last two real
    positions leaves the stated first chunk right and every later token of
    the row wrong."""
    c, lp = TOY, _conv_leaves(5)
    h = jax.random.normal(jax.random.key(3), (1, 24, c.d_model))
    want, _ = _plain(h[:, :14], lp)
    im, n = jnp.int32(0), jnp.asarray([13])

    def next_token_error():
        _, state = shortconv.shortconv_chunk(
            h[:, :16], lp, make_recurrent_state(c, 1), im, n, c)  # 13 real, 3 of padding
        y, _ = shortconv.shortconv_step(h[:, 13:14], lp, state, im, None)
        return float(jnp.abs(y[:, 0] - want[:, 13]).max())

    assert next_token_error() < 1e-5
    import types

    from jax import lax

    read_at_the_end = types.SimpleNamespace(**{**vars(lax), "dynamic_slice_in_dim": (
        lambda row, start, size, axis=0: lax.dynamic_slice_in_dim(
            row, row.shape[axis] - size, size, axis=axis))})
    monkeypatch.setattr(shortconv, "lax", read_at_the_end)  # the mixer's own lax alone
    assert next_token_error() > 1e-2


# ------------------------------------------------ (e) the description: what it takes and refuses
def test_the_four_refusals_now_describe():
    """Routed experts outside the three older stacks, leading dense layers in
    a hybrid without ``kda``, a recurrent kind that is none of mamba / gdn /
    kda, and a hybrid without ``gdn_*`` sizes each raised at one line of the
    parent's ``ModelConfig``: the preset holds all four."""
    full = preset("lfm2-8b-a1b")
    assert full.moe and full.first_k_dense == 2 and CONV in full.layer_types
    assert (full.gdn_n_k_heads, full.gdn_d_k, full.mamba_n_heads) == (0, 0, 0)
    assert full.qk_norm and full.tie_embeddings and full.n_shared_experts == 0
    assert full.head_dim == 64 == full.rotary_dim and full.conv_L_cache == 3


@pytest.mark.parametrize("change,reason", [
    (dict(conv_L_cache=0), "conv_L_cache >= 2"),
    (dict(conv_L_cache=1), "conv_L_cache >= 2"),
    (dict(conv_bias=True), "conv_bias"),
    (dict(n_routed_experts=0, n_experts_per_tok=0, moe_d_ff=0, first_k_dense=0),
     "short-convolution hybrid's FFN"),
    (dict(layer_types=(CONV, "mamba", ATTENTION, CONV) * 3), "one recurrent kind"),
    (dict(layer_types=(CONV, "window", ATTENTION, CONV) * 3, sliding_window=8), "not described"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8),
     "latent attention in a hybrid"),
    (dict(attn_output_gate=True), "attn_output_gate"),
    (dict(norm_plus_one=True), "norm_plus_one"),
    (dict(partial_rotary_factor=0.5), "partial_rotary_factor"),
    (dict(first_k_dense=12), "at least one expert layer"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_stays_outside_the_description_is_refused_by_name(change, reason):
    with pytest.raises(ValueError, match=reason):
        replace(TOY, **change)


def test_what_other_stacks_do_not_describe_stays_refused():
    """The doors opened for this stack stay shut for the others."""
    with pytest.raises(ValueError, match="conv_L_cache and conv_bias belong"):
        replace(preset("debug-gdn-moe"), conv_L_cache=3)
    with pytest.raises(ValueError, match="conv_L_cache and conv_bias belong"):
        ModelConfig(conv_bias=True)
    with pytest.raises(ValueError, match="leading dense"):
        replace(preset("debug-gdn-moe"), first_k_dense=1)
    with pytest.raises(ValueError, match="routed experts are described"):
        ModelConfig(n_routed_experts=8, n_experts_per_tok=2, moe_d_ff=16)
    with pytest.raises(ValueError, match="qk_norm"):
        ModelConfig(qk_norm=True)
