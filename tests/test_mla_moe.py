"""Latent attention and routed experts in one stack (Kimi-VL-A3B's decoder's kind).

Toy widths on the CPU that keep the RATIOS of the real model: 8 experts
with 2 a token and a shared one, a leading dense layer, rope on a part of
the head (8 of 24), a latent (32) narrower than the heads' keys (4 x 24).
Seeded random weights, LOGITS compared and never sampled tokens.  The other
side of every comparison is the benchmark's plain reference,
``benchmarks/architectures/deepseek-mla-moe.py``: float32, attention in the
expanded form over the whole sequence, every expert on every token times a
weight that is zero outside the chosen.

Each tolerance is written with its reason where it is set.  The weights and
activations here are float32, so that the tolerances can be tight enough
for the controls (f): the same run with the latent pool in bfloat16, and
with the router's product in bfloat16, each has to FAIL the tolerance that
the stated precision passes.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import (
    ModelConfig,
    RuntimeConfig,
    SpecConfig,
    UnsupportedWithLatentAttention,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import MLA_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - both_forms_at_toy_size is an autouse fixture
    STACK_LAYERS, STACK_ROUTINGS, Spy, both_forms_at_toy_size,
    check_the_step_kernel_is_not_taken, stack_check, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


@pytest.fixture(scope="module")
def first_served(standing):
    """The prompt of 37 with 21 new tokens, the first thing the standing engine serves
    (its prefix cache is ON: a prompt served twice is a hit the second time)."""
    return standing.serve([(FAMILY.prompt_of(37), 21)])


def generated(want: np.ndarray, prompt: list[int], out: list[int]) -> np.ndarray:
    return want[len(prompt) - 1: len(prompt) - 1 + len(out)]


# ----------------------------------------------------------------- (a)
def test_full_forward_agrees_with_the_reference():
    """The program's whole forward (one chunk: the expanded algebra, the
    grouped expert products) against the reference, at every own position
    of two ragged rows; the counters count the own positions alone."""
    params = FAMILY.seeded(key=1)
    tokens = np.random.default_rng(2).integers(3, TOY.vocab_size, (2, 40)).astype(np.int32)
    lens = np.asarray([40, 27], np.int32)
    logits, (c, k_rope), (counts, _) = FAMILY.forward(
        params, TOY, tokens, moe=moe.moe_stats_init(TOY), n_valid=jnp.asarray(lens))
    # 32 + 8 numbers a token a layer and nothing else: no K or V per head
    assert c.shape == (3, 2, 1, 40, 32) and k_rope.shape == (3, 2, 1, 40, 8)
    assert not moe.dense_form(2 * 40, TOY)
    want = ARCH.forward_logits(params, TOY, tokens, lens)
    for r in range(2):
        assert np.abs(np.asarray(logits[r, : lens[r]]) - want[r, : lens[r]]).max() < LOGIT_TOL
    assert counts.shape == (2, 8) and int(counts.sum()) == (40 + 27) * 2 * 2


# ----------------------------------------------------------------- (b)
def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(first_served):
    """Paged latent pool, chunked with a chunk (16) smaller than the prompt
    (37), pages of 8; 21 generated tokens cross five dispatches of four
    steps and two windows.  Every generated position's logits (the absorbed
    algebra over latent pages, the dense expert form) against the
    reference's expanded full forward of prompt + output."""
    prompt = FAMILY.prompt_of(37)
    (out,), params, spy = first_served.outs, first_served.params, first_served.spy
    counters = {**first_served.added, "latent_cache_bytes": first_served.counters["latent_cache_bytes"]}
    got = spy.of_request(prompt, out, 16)
    want = generated(FAMILY.reference_logits(params, TOY, prompt + out), prompt, out)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # and all 37 prompt positions, from the three chunks that attended the
    # latents the chunks before them left in the scratch
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    assert np.abs(chunks - FAMILY.reference_logits(params, TOY, prompt + out)[: len(prompt)]).max() < LOGIT_TOL
    # 2 expert layers x 2 experts a token x (37 prompt tokens + 20 decode
    # steps run: five dispatches of four, the row active in all of them)
    assert counters["moe_assignments"] == 2 * 2 * (37 + 20)
    assert 0 < counters["moe_experts_hit"] <= 2 * 2 * 20
    assert counters["latent_cache_bytes"] == 3 * 33 * 8 * 40 * 4  # L x pages x page x 40 x f32
    assert counters["moe_expert_tokens_max"] >= counters["moe_expert_tokens_mean"] > 0


def test_single_shot_prefill_serves_the_same_logits(monkeypatch):
    """(Single-shot prefill without the prefix cache is another lane: a build of its own.)"""
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(23, seed=7)
    (out,), params, counters = FAMILY.serve(
        (TOY, FAMILY.runtime(chunked_prefill=False, prefix_cache=False)), [(prompt, 7)])
    steps = [s for s in spy.seen if s.shape[1] == 1]
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    slot = next(b for b in range(2) if int(np.argmax(steps[0][b, 0])) == out[1])
    for i in range(len(out) - 1):
        assert np.abs(steps[i][slot, 0] - want[len(prompt) + i]).max() < LOGIT_TOL
    assert counters["moe_assignments"] == 2 * 2 * (23 + 8)


# ----------------------------------------------------------------- (c)
def _layer(skewed: bool):
    lp = jax.tree.map(lambda a: a[0], FAMILY.seeded(key=6)["layers"]["moe"])
    if skewed:  # a gate that sends most tokens to experts 2 and 5
        lp["router_bias"] = lp["router_bias"].at[jnp.asarray([2, 5])].add(3.0)
    return lp


@pytest.mark.parametrize("skewed", [False, True], ids=["even", "skewed"])
def test_grouped_experts_equal_every_expert_masked(monkeypatch, skewed):
    """The two forms of the expert products on the same 96 tokens, under
    even routing and under a gate that sends most tokens to two experts
    (groups of 90 and of 0): equal outputs, every pair counted, nothing
    dropped; and both equal the plain sum over a token's experts."""
    lp = _layer(skewed)
    h = jax.random.normal(jax.random.key(7), (3, 32, TOY.d_model))
    stats = (jnp.zeros((1, 8), jnp.int32), jnp.zeros((), jnp.int32))
    dense, (counts_d, _) = moe.moe_ffn(h, lp, TOY, stats, None, 0)
    monkeypatch.setattr(moe, "dense_form", lambda tokens, config: False)
    grouped, (counts, hit) = moe.moe_ffn(h, lp, TOY, stats, None, 0)
    # float32 sums in two orders, outputs of order 1: measured 4.8e-7 at most
    assert np.abs(np.asarray(grouped) - np.asarray(dense)).max() < 1e-5
    assert int(counts.sum()) == 96 * 2 and np.array_equal(counts, counts_d)
    assert int(hit) == int((np.asarray(counts) > 0).sum())
    if skewed:
        assert int(counts[0, 2]) + int(counts[0, 5]) > 0.8 * 96 * 2
        assert int(counts[0].max()) > 80  # far past any "capacity" of 96 x 2 / 8 = 24
    chosen, weights = moe.route(h.reshape(96, -1), lp, TOY)
    flat = h.reshape(96, -1)
    plain = sum(
        weights[:, j, None] * jax.vmap(
            lambda x, e: (jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])) @ lp["w_down"][e]
        )(flat, chosen[:, j])
        for j in range(2)
    ) + (jax.nn.silu(flat @ lp["s_gate"]) * (flat @ lp["s_up"])) @ lp["s_down"]
    assert np.abs(np.asarray(grouped).reshape(96, -1) - np.asarray(plain)).max() < 1e-5


@pytest.mark.parametrize("m", range(STACK_LAYERS))
@pytest.mark.parametrize("case", STACK_ROUTINGS)
def test_grouped_experts_read_their_layer_out_of_the_stack(case, m):
    """The grouped products take the STACKED leaves and the layer's index
    (every expert held): each layer of three gives what the parent's form
    gave on the sliced layer, bit for bit, and what the dense form gives;
    with a group of 0, and a first and a last group of every token."""
    stack_check(replace(TOY, n_layers=1 + STACK_LAYERS), case, m)


def test_padding_and_inactive_rows_are_computed_and_not_counted():
    lp = _layer(False)
    h = jax.random.normal(jax.random.key(8), (2, 8, TOY.d_model))
    stats = (jnp.zeros((1, 8), jnp.int32), jnp.zeros((), jnp.int32))
    valid = jnp.arange(8)[None, :] < jnp.asarray([8, 3])[:, None]
    _, (counts, _) = moe.moe_ffn(h, lp, TOY, stats, valid, 0)
    assert int(counts.sum()) == (8 + 3) * 2


# ----------------------------------------------------------------- (d)
def test_the_bias_moves_the_choice_and_not_the_weights():
    """``e_score_correction_bias`` decides WHICH experts; the weights are
    the unbiased scores of the chosen, normalised over them and scaled by
    routed_scaling_factor ONCE.  Checked against the reference too, which a
    program that put the bias into the weights, or left it out of the
    choice, would fail."""
    lp = _layer(False)
    h = jax.random.normal(jax.random.key(9), (64, TOY.d_model))
    scores = jax.nn.sigmoid(h @ lp["router"])
    chosen, weights = moe.route(h, lp, TOY)
    unbiased = replace_bias(lp, 0.0)
    chosen0, _ = moe.route(h, unbiased, TOY)
    assert not np.array_equal(np.sort(chosen, -1), np.sort(chosen0, -1))  # the bias moved a choice
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * TOY.routed_scaling_factor
    assert np.abs(np.asarray(weights) - np.asarray(want)).max() < 1e-6
    assert np.abs(np.asarray(weights.sum(-1)) - TOY.routed_scaling_factor).max() < 1e-5
    _, raw = moe.route(h, lp, replace(TOY, norm_topk_prob=False, routed_scaling_factor=1.0))
    assert np.abs(np.asarray(raw) - np.asarray(picked)).max() < 1e-6


def replace_bias(lp, value):
    return {**lp, "router_bias": jnp.full_like(lp["router_bias"], value)}


@pytest.mark.parametrize("fault", ["bias_in_the_weights", "bias_left_out_of_the_choice"])
def test_a_program_that_gets_the_bias_wrong_fails_the_reference(monkeypatch, fault):
    params = FAMILY.seeded(key=1)
    tokens = np.random.default_rng(3).integers(3, TOY.vocab_size, (1, 40)).astype(np.int32)
    want = ARCH.forward_logits(params, TOY, tokens, np.asarray([40], np.int32))
    right = moe.route

    def wrong(h, lp, config):
        if fault == "bias_left_out_of_the_choice":
            return right(h, replace_bias(lp, 0.0), config)
        chosen, _ = right(h, lp, config)
        biased = jnp.take_along_axis(
            jax.nn.sigmoid(h @ lp["router"]) + lp["router_bias"], chosen, axis=-1)
        return chosen, biased / biased.sum(-1, keepdims=True) * config.routed_scaling_factor

    logits, _ = FAMILY.forward(params, TOY, tokens)
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL
    monkeypatch.setattr(moe, "route", wrong)
    logits, _ = FAMILY.forward(params, TOY, tokens)
    assert np.abs(np.asarray(logits) - want).max() > LOGIT_TOL


# ----------------------------------------------------------------- (e)
def test_a_reused_latent_prefix_gives_the_logits_of_a_cold_engine(monkeypatch, standing):
    """The second request shares 32 tokens (four latent pages) with the
    first: it seeds its scratch from the cached pages and starts at the
    reused offset.  Its logits are those of an engine that prefilled the
    whole prompt itself (the cold engine is a build of its own)."""
    shared = FAMILY.prompt_of(32, seed=1)
    first, second = shared + FAMILY.prompt_of(9, seed=2), shared + FAMILY.prompt_of(13, seed=3)
    served = standing.serve([(first, 4), (second, 9)])
    (_, out), params, counters = served.outs, served.params, served.added
    assert counters["prefix_hits"] == 1 and counters["prefix_reused_tokens"] == 32
    warm = served.spy.of_request(second, out, 16)
    spy = Spy(monkeypatch)
    (cold_out,), _, cold_counters = FAMILY.serve((TOY, FAMILY.runtime()), [(second, 9)])
    assert cold_counters["prefix_hits"] == 0 and out == cold_out
    cold = spy.of_request(second, cold_out, 16)
    # the same programs on the same numbers but for the chunks skipped
    assert np.abs(warm - cold).max() < 1e-6
    want = generated(FAMILY.reference_logits(params, TOY, second + out), second, out)
    assert np.abs(warm - want).max() < LOGIT_TOL


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(monkeypatch):
    """(One slot is another runtime, and the fresh engine is what the reused one is held
    to: two builds of its own.)"""
    first, second = FAMILY.prompt_of(29, seed=1), FAMILY.prompt_of(21, seed=2)
    spy = Spy(monkeypatch)
    (_, out), _, _ = FAMILY.serve((TOY, FAMILY.runtime(max_batch_size=1)), [(first, 9), (second, 9)])
    reused = list(spy.seen)[-8:]
    spy.seen.clear()
    (fresh_out,), _, _ = FAMILY.serve((TOY, FAMILY.runtime(max_batch_size=1)), [(second, 9)])
    assert out == fresh_out
    for a, b in zip(reused, spy.seen[-8:]):
        assert np.array_equal(a, b)  # the same program on the same numbers


# ----------------------------------------------------------------- (f)
@pytest.mark.parametrize("lowered", ["latent_pool", "router_product"])
def test_control_a_lower_precision_fails_the_tolerance(monkeypatch, lowered):
    """The tolerance of (b) would catch a lower precision than the file
    states: float32 weights and activations as before, and the latent pool
    held in bfloat16, or the router's product taken in bfloat16, each FAILS
    (b)'s own run (the same prompt, 21 generated positions), which the
    stated precision passes there.  (Each control is another program: a
    build of its own.)"""
    prompt = FAMILY.prompt_of(37)
    rt = FAMILY.runtime()
    if lowered == "latent_pool":
        made = M.make_page_pool
        monkeypatch.setattr(
            M, "make_page_pool", lambda c, n, page, dtype=None: made(c, n, page, jnp.bfloat16))
    else:
        right = moe.route

        def lowered_route(h, lp, c):  # the product's two sides rounded to bfloat16
            low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
            return right(low(h), {**lp, "router": low(lp["router"])}, c)

        monkeypatch.setattr(moe, "route", lowered_route)
    spy = Spy(monkeypatch)
    (out,), params, _ = FAMILY.serve((TOY, rt), [(prompt, 21)])
    got = spy.of_request(prompt, out, 16)
    want = generated(FAMILY.reference_logits(params, TOY, prompt + out), prompt, out)
    assert np.abs(got - want).max() > LOGIT_TOL


# ----------------------------------------------------------------- (g)
def hf_tensors(params, c: ModelConfig, prefix: str) -> dict[str, np.ndarray]:
    """The toy tree in HF DeepseekV3's names and layouts, its rope columns
    in HF's order (adjacent pairs), under ``prefix``."""
    def f(a):
        return np.ascontiguousarray(np.asarray(a, np.float32))

    D, H, r, dn, dr, dv = (c.d_model, c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    adjacent = np.argsort(halves)  # undoes the loader's permutation

    def hf_rope(w, start):
        return np.concatenate([w[..., :start], w[..., start:][..., adjacent]], axis=-1)

    L = params["layers"]
    out = {"model.embed_tokens.weight": f(params["embed"]),
           "model.norm.weight": f(params["final_norm"]),
           "lm_head.weight": f(np.asarray(params["lm_head"]).T)}
    for i in range(c.n_layers):
        pre = f"model.layers.{i}."
        a = jax.tree.map(lambda x: np.asarray(x[i]), L["attn"])
        out[pre + "self_attn.q_proj.weight"] = f(hf_rope(a["wq"], dn).reshape(D, H * (dn + dr)).T)
        out[pre + "self_attn.kv_a_proj_with_mqa.weight"] = f(hf_rope(a["w_kva"], r).T)
        out[pre + "self_attn.kv_a_layernorm.weight"] = f(a["kv_norm"])
        out[pre + "self_attn.kv_b_proj.weight"] = f(
            np.concatenate([a["w_uk"], a["w_uv"]], axis=-1).reshape(r, H * (dn + dv)).T)
        out[pre + "self_attn.o_proj.weight"] = f(a["wo"].reshape(H * dv, D).T)
        out[pre + "input_layernorm.weight"] = f(a["attn_norm"])
        if i < c.n_dense_layers:
            d = jax.tree.map(lambda x: np.asarray(x[i]), L["dense"])
            for ours, hf in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                out[pre + f"mlp.{hf}.weight"] = f(d[ours].T)
            out[pre + "post_attention_layernorm.weight"] = f(d["mlp_norm"])
            continue
        m = jax.tree.map(lambda x: np.asarray(x[i - c.n_dense_layers]), L["moe"])
        out[pre + "mlp.gate.weight"] = f(m["router"].T)
        out[pre + "mlp.gate.e_score_correction_bias"] = f(m["router_bias"])
        for ours, hf in (("gate", "gate_proj"), ("up", "up_proj"), ("down", "down_proj")):
            for e in range(c.n_routed_experts):
                out[pre + f"mlp.experts.{e}.{hf}.weight"] = f(m["w_" + ours][e].T)
            out[pre + f"mlp.shared_experts.{hf}.weight"] = f(m["s_" + ours].T)
        out[pre + "post_attention_layernorm.weight"] = f(m["mlp_norm"])
    return {prefix + name: tensor for name, tensor in out.items()}


HF_TEXT_CONFIG = {
    "model_type": "deepseek_v3", "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 800000, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
}


@pytest.mark.parametrize("model_type", ["kimi_vl", "deepseek_v3"])
def test_a_checkpoint_in_hf_names_loads_to_the_tree_the_reference_agrees_with(tmp_path, model_type):
    """A synthetic safetensors in HF's names: for ``kimi_vl`` under the
    ``language_model.`` prefix with tower tensors beside it (skipped,
    counted, one typed notice).  The tree comes back leaf for leaf, its
    rope columns permuted from HF's adjacent pairs to halves."""
    from safetensors.numpy import save_file

    from calfkit_tpu.inference.loader import VisionTowerSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import make_mesh, param_shardings

    params = FAMILY.seeded(key=4)
    vl = model_type == "kimi_vl"
    tensors = hf_tensors(params, TOY, "language_model." if vl else "")
    if vl:
        tensors["vision_tower.patch_embed.proj.weight"] = np.zeros((4, 4), np.float32)
        tensors["multi_modal_projector.linear_1.weight"] = np.zeros((4, 4), np.float32)
    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "kimi_vl", "text_config": HF_TEXT_CONFIG, "vision_config": {}} if vl
        else HF_TEXT_CONFIG))
    save_file(tensors, str(tmp_path / "model.safetensors"))
    config = replace(config_from_hf(tmp_path), name=TOY.name, dtype="float32")
    assert config == TOY
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, make_mesh()))
    notices = [w for w in caught if issubclass(w.category, VisionTowerSkipped)]
    assert len(notices) == (1 if vl else 0)
    assert not vl or "2 tensors" in str(notices[0].message)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert got.dtype == want.dtype and np.array_equal(np.asarray(got), np.asarray(want))
    # HF's q_proj really is in another column order than the tree's
    hf_q = tensors[("language_model." if vl else "") + "model.layers.0.self_attn.q_proj.weight"]
    assert not np.array_equal(hf_q.T.reshape(32, 4, 24), np.asarray(params["layers"]["attn"]["wq"][0]))
    tokens = np.asarray([FAMILY.prompt_of(24, seed=9)], np.int32)
    logits, _ = FAMILY.forward(loaded, config, tokens)
    want = ARCH.forward_logits(loaded, config, tokens, np.asarray([24], np.int32))
    assert np.abs(np.asarray(logits) - want).max() < LOGIT_TOL


def test_rotating_adjacent_pairs_equals_rotating_the_halves_of_permuted_columns():
    """What the loader's permutation rests on: HF's interleaved rotation of
    a vector equals, up to that same permutation, the half rotation of the
    permuted vector; scores are dot products, which no permutation moves."""
    dr = 8
    x = np.random.default_rng(0).normal(size=(1, 5, 1, dr)).astype(np.float32)
    cos, sin = M.rope_tables(jnp.arange(5)[None], *M.rope_frequencies(dr, 800000.0))
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    ours = np.asarray(M.apply_rope(jnp.asarray(x[..., halves]), cos, sin))
    c, s = np.asarray(cos)[0, :, None, :], np.asarray(sin)[0, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]  # HF: pair (2i, 2i + 1) turns by angle i
    hf = np.empty_like(x)
    hf[..., 0::2], hf[..., 1::2] = even * c - odd * s, odd * c + even * s
    assert np.abs(ours - hf[..., halves]).max() < 1e-6


# ----------------------------------------------------------------- (h)
@pytest.mark.parametrize("option, kwargs", [
    ("speculative", {"speculative": SpecConfig()}),
    ("tp > 1", {"tp": 2}),
    ("dp > 1", {"dp": 2}),
    ("quantization", {"quantization": "int8"}),
    ("long_context", {"long_context": True}),
    ("kv_layout='dense'", {"kv_layout": "dense", "prefix_cache": False}),
])
def test_what_has_no_latent_or_expert_path_is_refused_at_construction(option, kwargs):
    with pytest.raises(UnsupportedWithLatentAttention, match=option):
        InferenceEngine(TOY, FAMILY.runtime(**kwargs))


@pytest.mark.parametrize("latent, page, built", [
    ((128, 64), 16, True),  # whole lane tiles | half of one, pages of two parts of a tile
    ((32, 8), 8, False),  # this file's toy latent
], ids=["inside-the-rule", "outside-the-rule"])
def test_the_paged_decode_kernel_is_not_for_a_latent_pool(latent, page, built):
    """The kernel of K and V pairs never reads a latent pool.  An explicit
    request builds the LATENT decode kernel where the shapes are in its
    rule (``pallas_attention.latent_decode_in_place_ok``) and is refused
    outside it; "auto" on this CPU reads through XLA either way."""
    from calfkit_tpu.inference import pallas_attention as PA

    config = replace(TOY, kv_lora_rank=latent[0], qk_rope_head_dim=latent[1])
    engine = InferenceEngine(config, FAMILY.runtime(page_size=page))
    assert (engine._attn_impl, engine._ssm_impl) == ("xla", "xla")
    if not built:
        with pytest.raises(PA.PallasShapeError, match="latent_decode_in_place_ok"):
            InferenceEngine(config, FAMILY.runtime(page_size=page, attention_impl="pallas_interpret"))
        return
    # what it then builds and serves: tests/test_latent_decode_attention.py
    engine = InferenceEngine(config, FAMILY.runtime(page_size=page, attention_impl="pallas_interpret"))
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "xla")


@pytest.mark.parametrize("fields, message", [
    ({"kv_lora_rank": 0}, "latent-attention stack"),
    ({"scoring_func": "softmax"}, "sigmoid"),
    # groups are described since PR 40 (tests/test_kda_mla_moe.py); ones that do not fit are not
    ({"n_group": 3, "topk_group": 2}, "group-limited"),
    ({"layer_types": ("attention", "mamba", "mamba")}, "hybrid"),
    ({"qk_rope_head_dim": 0}, "qk_nope_head_dim/qk_rope_head_dim"),
    ({"first_k_dense": 3}, "at least one expert layer"),
])
def test_a_description_the_stack_does_not_run_is_refused(fields, message):
    with pytest.raises(ValueError, match=message):
        replace(TOY, **fields)


def test_quantize_params_refuses_expert_leaves():
    from calfkit_tpu.inference.quant import quantize_params

    with pytest.raises(ValueError, match="no scales"):
        quantize_params(M.init_params(TOY, jax.random.key(0)))


def test_routed_experts_of_a_granite_checkpoint_are_still_refused(tmp_path):
    from calfkit_tpu.inference.loader import RoutedExpertsUnsupported, config_from_hf

    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "granitemoehybrid", "num_local_experts": 8}))
    with pytest.raises(RoutedExpertsUnsupported, match="num_local_experts"):
        config_from_hf(tmp_path)


# ----------------------------------------------------------------- (i)
HYBRID = ModelConfig(
    name="toy-hybrid", vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
    d_ff=64, layer_types=("mamba", "mamba", "attention"), mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, dtype="float32",
    position_embedding="none", attention_multiplier=0.25, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, tie_embeddings=True, max_seq_len=1024,
)
# sha256 of str(jaxpr) of the paged decode dispatch and of a ragged program
# carrying one chunk of a two-row wave, as the commit BEFORE latent attention
# and experts traced them (245ab58; recorded there with this file's
# ``_programs``).  A PR that changes these programs on purpose records anew.
# since PR 46 the programs end in the paged write's loop of window updates: with PR 45's scatter
# (tests/test_kv_write.py's reference) put back, each traced to the hash pinned before, letter for letter
TRACED_BEFORE = {
    "dense": {"decode": "6f3974d73e2b667aace9dd64f5c9d415cf02293d44858013c4406ec21fe28920",
              "ragged": "fca501f0667c6ddc88f13b2129a11e002cbd23600be1ff7a6eaf9196b326dada"},
    "hybrid": {"decode": "2a78532baaf81aaf37e67fef237b26b3576565d404af13faf57d59edc2aeea83",
               "ragged": "0e20bb88bc992ca6414abc39ffc0fddf52541c2dede1f317e1378c0afb16920a"},
}


def _programs(engine) -> dict:
    from calfkit_tpu.inference.mamba import make_recurrent_state

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    rows, chunk = 2, rt.prefill_chunk
    scratch = jnp.zeros(
        (cfg.n_kv_layers, rows, cfg.n_kv_heads, 2 * chunk, cfg.d_model // cfg.n_heads),
        engine._k.dtype)
    wave = [scratch, scratch, jnp.zeros((rows, chunk), jnp.int32), jnp.int32(0)]
    state = wave_state = ()
    if cfg.layer_types:
        state = (engine._state,)
        wave_state = (engine._state, make_recurrent_state(cfg, rows), jnp.zeros((rows,), jnp.int32))
    return {
        "decode": jax.make_jaxpr(
            engine._decode_fn_paged(window // rt.page_size, steps, sampled))(*args, *state),
        "ragged": jax.make_jaxpr(
            engine._ragged_jit(window, steps, sampled, chunk, rows))(*args, *wave, *wave_state),
    }


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_a_dense_and_a_hybrid_description_trace_what_they_traced_before(kind):
    """A description without the new fields builds the programs it built
    before latent attention and experts existed, letter for letter."""
    config = preset("debug") if kind == "dense" else HYBRID
    engine = InferenceEngine(config, FAMILY.runtime(prefix_cache=False, attention_impl="xla"))
    for name, jaxpr in _programs(engine).items():
        text = str(jaxpr)
        assert "mla" not in text and "moe" not in text
        assert hashlib.sha256(text.encode()).hexdigest() == TRACED_BEFORE[kind][name], name


def test_the_dense_and_hybrid_descriptions_are_what_they_were():
    c = preset("debug")
    assert not c.latent and not c.moe and (c.cache_heads, c.cache_dims) == (2, (16, 16))
    assert preset("llama-3-8b").param_count == 8030261248
    assert preset("llama-3-8b").kv_bytes_per_token() == 2 * 32 * 8 * 128 * 2
    g = preset("granite-4.0-h-micro")
    assert g.kv_bytes_per_token() == 8192 and g.head_dim == 64
    k = preset("kimi-vl-a3b-instruct")
    assert (k.head_dim, k.cache_heads, k.cache_dims) == (192, 1, (512, 64))
    assert k.param_count == 15960110208
    held = replace(k, n_layers=7)
    assert held.param_count == 4263151488 and held.kv_bytes_per_token() == 8064
    assert (held.n_dense_layers, held.n_moe_layers) == (1, 6)


@pytest.mark.parametrize("rows, grouped", [(1, False), (2, True), (4, True)])
def test_a_chunk_dispatch_is_counted_by_the_form_its_shape_gives_it(rows, grouped):
    """``moe_grouped_chunks`` / ``moe_dense_chunks``: one count for every
    chunk the engine enqueues (riding a decode dispatch, or alone), from the
    wave's rows x the chunk's length alone; a decode dispatch counts nothing.
    At toy size the limit is 16 tokens: a one-row chunk of 16 is dense, as
    Kimi's one-row chunk of 1,024 is under its limit of 1,536."""
    engine = InferenceEngine(TOY, FAMILY.runtime(max_batch_size=4, max_prefill_wave=4), seed=3, params=FAMILY.seeded())
    inf = {"wave": [None] * rows, "chunk": 16, "wmoe": engine._moe_zero,
           "arrays": {"true_lens": np.full(rows, 16, np.int32)}}
    assert moe.dense_form(rows * 16, TOY) != grouped
    assert set(engine._moe_kw(inf)) == {"moe", "wmoe", "true_lens"}  # riding a decode dispatch
    assert set(engine._moe_kw(inf, decode=False)) == {"wmoe", "true_lens"}  # alone
    assert set(engine._moe_kw()) == {"moe"}  # decode steps alone: no chunk
    counters = engine.stats.counters()
    assert (counters["moe_grouped_chunks"], counters["moe_dense_chunks"]) == (
        (2, 0) if grouped else (0, 2))


def test_a_served_prompt_s_one_row_chunks_count_as_dense(first_served):
    counters, text = first_served.added, first_served.metrics
    assert (counters["moe_grouped_chunks"], counters["moe_dense_chunks"]) == (0, 3)  # 37 in chunks of 16
    assert "calfkit_engine_moe_grouped_chunks_total" in text
    assert "calfkit_engine_moe_dense_chunks_total" in text


def test_the_new_counters_reach_metrics_and_capacity(standing):
    from calfkit_tpu.observability.capacity import hbm_constants

    text = standing.serve([(FAMILY.prompt_of(20), 3)]).metrics
    for name in ("calfkit_engine_moe_assignments_total",
                 "calfkit_engine_moe_expert_tokens_max_total",
                 "calfkit_engine_moe_expert_tokens_mean_total",
                 "calfkit_engine_moe_experts_hit_total",
                 "calfkit_engine_latent_cache_bytes"):
        assert name in text
    # the page accounting reads the description, not n_kv_heads x head_dim
    assert hbm_constants(replace(preset("kimi-vl-a3b-instruct"), n_layers=7))[1] == 8064.0


def test_experts_held_whole_keep_the_dense_form_under_any_value(monkeypatch, standing):
    """The step kernel (PR 53) is for experts held by SHARE: these are held
    whole and hit whole, so on a TPU the engine takes it under no value of
    ``attention_impl``, and the module's engine ran none of its steps."""
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(
        standing.engine, monkeypatch, "tpu", ("auto", "pallas", "pallas_interpret", "xla"))
