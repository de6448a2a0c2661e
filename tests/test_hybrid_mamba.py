"""Mamba-2 layers beside attention in one stack (granite-4.0-h-micro's kind).

Toy widths on the CPU, seeded random weights, LOGITS compared and never
sampled tokens (with random weights the largest logit changes on rounding).
The other side of every comparison is the benchmark's plain reference,
``benchmarks/architectures/granite-hybrid.py``: float32, the recurrence as
a ``lax.scan`` over positions, no chunked form, no cache.

Each tolerance is written with its reason where it is set.  The weights and
activations here are float32, so that the tolerances can be tight enough
for the control (e): the same run with the SSM state held in bfloat16 has
to FAIL the tolerance that the float32 state passes.
"""

from __future__ import annotations

import asyncio
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import mamba as mm
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import (
    ModelConfig,
    RuntimeConfig,
    SpecConfig,
    UnsupportedWithRecurrentLayers,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import HYBRID_MAMBA, Spy, standing  # noqa: F401 - a fixture

FAMILY = HYBRID_MAMBA
ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy
# the toy inside BOTH kernels' rules, for the ``pallas_interpret`` cases: heads
# of 64 on pages of 16 for the paged decode read, and a state of whole tiles
# (32 heads of 8 in two groups, 128 lines a group, d_state 128) for the SSM step
TOYK = replace(
    TOY, name="toy-hybrid-tiles", d_model=256, mamba_n_heads=32, mamba_d_head=8,
    mamba_d_state=128,
)
# impl -> (the configuration, its runtime overrides)
KERNEL_CASES = {
    "xla": (TOY, {}),
    "pallas_interpret": (TOYK, {"attention_impl": "pallas_interpret", "page_size": 16}),
}


def ssm_kernel_traces(fresh: bool = False) -> int:
    """Times the SSM step kernel was traced (interpreted).  Its entry point
    is a jit of its own, traced once a process a shape: ``fresh`` clears
    that, so that the next engine on the same shapes counts again."""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES
    from calfkit_tpu.inference.pallas_ssm import ssm_step_pallas

    if fresh:
        ssm_step_pallas.clear_cache()
    return KERNEL_TRACES["ssm_step", "interpreted"]


# ----------------------------------------------------------------- (a)
def test_full_forward_agrees_with_the_reference():
    """The program's whole forward (one chunk, zero state) against the
    reference, at every position of two ragged rows."""
    params = M.init_params(TOY, jax.random.key(1))
    rng = np.random.default_rng(2)
    tokens = rng.integers(3, TOY.vocab_size, (2, 40)).astype(np.int32)
    lens = np.asarray([40, 27], np.int32)
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    logits, _, _ = M.forward(
        params, TOY, jnp.asarray(tokens), pos, M.make_empty_cache(TOY, 2, 40),
        jnp.full((2,), 40, jnp.int32), state=mm.make_recurrent_state(TOY, 2),
        n_valid=jnp.asarray(lens),
    )
    want = ARCH.forward_logits(params, TOY, tokens, lens)
    for r in range(2):
        got = np.asarray(logits[r, : lens[r]])
        assert np.abs(got - want[r, : lens[r]]).max() < LOGIT_TOL


# ----------------------------------------------------------------- (b)
@pytest.mark.parametrize("impl", sorted(KERNEL_CASES))
def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(
        monkeypatch, request, impl):
    """Paged, chunked with a chunk (16) smaller than the prompt (37) and an
    SSD block (8) smaller than the chunk; 21 generated tokens cross five
    dispatches of four steps.  Every generated position's logits against
    the reference's full forward of prompt + output.  Under
    ``pallas_interpret`` the decode steps' pass over the SSM state is the
    kernel's (and the paged decode read the other kernel's: the toy inside
    both kernels' rules is another configuration, a build of its own)."""
    config, over = KERNEL_CASES[impl]
    prompt = FAMILY.prompt_of(37)
    before = ssm_kernel_traces(fresh=True)
    if impl == "xla":
        served = request.getfixturevalue("standing").serve([(prompt, 21)])
        (out,), params, spy = served.outs, served.params, served.spy
        counters = {**served.counters, **{n: served.added[n] for n in (
            "pipeline_drains_wave", "wave_landings_deferred")}}
    else:
        spy = Spy(monkeypatch)
        (out,), params, counters = FAMILY.serve(
            (config, FAMILY.runtime(**over)), [(prompt, 21)])
    got = spy.of_request(prompt, out, 16)
    want = FAMILY.reference_logits(params, config, prompt + out)[len(prompt) - 1: len(prompt) - 1 + len(out)]
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # one wave, onto an engine with no active rows: nothing to ride, its landing is a sync
    assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (1, 0)
    assert counters["recurrent_state_bytes"] == config.recurrent_state_bytes(2)
    assert (ssm_kernel_traces() > before) == (impl == "pallas_interpret")


def test_the_prompt_s_logits_agree_chunk_by_chunk(standing):
    """All 37 prompt positions, from the three chunks that carried the
    state between them."""
    prompt = FAMILY.prompt_of(37, seed=5)
    served = standing.serve([(prompt, 2)])
    (out,), params, spy = served.outs, served.params, served.spy
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    want = FAMILY.reference_logits(params, TOY, prompt + out)[: len(prompt)]
    assert np.abs(chunks - want).max() < LOGIT_TOL


# ----------------------------------------------------------------- (c)
def test_chunked_scan_agrees_with_the_recurrence_on_ragged_rows():
    """Rows of 32, 20 and 0 own positions in one chunk of 32 (blocks of 8):
    outputs at the own positions and both states equal the one-token
    recurrence's, and padding moves neither state."""
    lp = jax.tree.map(lambda a: a[0], mm.init_mamba_params(TOY, jax.random.key(0), jnp.float32))
    lp["conv_b"] = 0.1 * jax.random.normal(jax.random.key(5), lp["conv_b"].shape)
    h = jax.random.normal(jax.random.key(1), (3, 32, TOY.d_model))
    # one layer's slice of a stacked state that is not zero, so that
    # "untouched" says something
    state = tuple(s[:1] + jax.random.normal(jax.random.key(2 + i), s[:1].shape)
                  for i, s in enumerate(mm.make_recurrent_state(TOY, 3)))
    (ssm, conv), im = (s[0] for s in state), jnp.int32(0)
    n_valid = jnp.asarray([32, 20, 0])
    out, (ssm_c, conv_c) = mm.mamba_chunk(h, lp, state, im, n_valid, TOY)
    ssm_c, conv_c = ssm_c[0], conv_c[0]
    st, outs = state, []
    for t in range(32):
        o, st = mm.mamba_step(h[:, t:t + 1], lp, st, im, t < n_valid, TOY)
        outs.append(o)
    (s, cv), outs = (a[0] for a in st), jnp.concatenate(outs, axis=1)
    for b, n in enumerate([32, 20, 0]):
        # float32 sums in two orders, states of order 1: measured 1.4e-6 at most
        assert np.abs(out[b, :n] - outs[b, :n]).max(initial=0.0) < 1e-5
        assert np.abs(ssm_c[b] - s[b]).max() < 1e-5
        assert np.abs(conv_c[:, b] - cv[:, b]).max() < 1e-5
    assert np.array_equal(ssm_c[2], ssm[2]) and np.array_equal(conv_c[:, 2], conv[:, 2])
    # two chunks of 16 carry the state to where one of 32 arrives
    _, half = mm.mamba_chunk(h[:, :16], lp, state, im, jnp.clip(n_valid, 0, 16), TOY)
    _, (s2, c2) = mm.mamba_chunk(h[:, 16:], lp, half, im, jnp.clip(n_valid - 16, 0, 16), TOY)
    assert np.abs(s2[0] - ssm_c).max() < 1e-5 and np.abs(c2[0] - conv_c).max() < 1e-5


# ----------------------------------------------------------------- (d)
def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(monkeypatch):
    """One slot: the second request lands where the first one's state
    still lies.  Its logits are those of an engine that never served the
    first: the landing overwrites the whole of a slot's state.  (One slot is
    another runtime, and the fresh engine is what the reused one is held to:
    two builds of its own.)"""
    first, second = FAMILY.prompt_of(29, seed=1), FAMILY.prompt_of(21, seed=2)
    spy = Spy(monkeypatch)
    (_, out), _, counters = FAMILY.serve((TOY, FAMILY.runtime(max_batch_size=1)), [(first, 9), (second, 9)])
    reused = [s for s in spy.seen][-8:]
    assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (2, 0)
    spy.seen.clear()
    (fresh_out,), _, _ = FAMILY.serve((TOY, FAMILY.runtime(max_batch_size=1)), [(second, 9)])
    fresh = spy.seen[-8:]
    assert out == fresh_out
    for a, b in zip(reused, fresh):
        assert np.array_equal(a, b)  # the same program on the same numbers


@pytest.mark.parametrize("impl", sorted(KERNEL_CASES))
def test_a_frozen_row_s_state_is_bit_equal_across_a_dispatch(impl):
    """Row 1 is not active: a decode dispatch leaves its SSM and conv
    state bit for bit, while row 0's moves.  (The kernel neither reads nor
    writes such a row.)  (It overwrites the engine's state by hand: an
    engine of its own.)"""
    config, over = KERNEL_CASES[impl]
    engine = InferenceEngine(config, FAMILY.runtime(**over), seed=3)
    assert engine._ssm_impl == impl
    ssm, conv = engine._state
    engine._state = (
        ssm + jax.random.normal(jax.random.key(1), ssm.shape),
        conv + jax.random.normal(jax.random.key(2), conv.shape),
    )
    before = jax.tree.map(np.asarray, engine._state)
    args, window, steps, sampled = engine._decode_args()
    args[6] = jnp.asarray([True, False])  # the active mask of the paged decode program
    *_, (ssm2, conv2) = engine._decode_jit(window, steps, sampled)(*args, engine._state)
    assert np.array_equal(np.asarray(ssm2)[:, 1], before[0][:, 1])
    assert np.array_equal(np.asarray(conv2)[:, :, 1], before[1][:, :, 1])
    assert not np.array_equal(np.asarray(ssm2)[:, 0], before[0][:, 0])


# ----------------------------------------------------------------- (e)
@pytest.mark.parametrize("impl", sorted(KERNEL_CASES))
def test_control_a_bfloat16_state_fails_the_tolerance(monkeypatch, impl):
    """The tolerance of (b) would catch a lower precision than the file
    states: with the SSM state held in bfloat16 (float32 weights and
    activations as before) the same comparison fails within 512 steps.
    Under ``pallas_interpret`` the float32 state takes the kernel and
    passes; the bfloat16 state is outside the kernel's rule (a float32 pass
    or none), reads through XLA, and fails as it does there.  (A control and
    512 steps in a longer window: builds of its own.)"""
    float32, over = KERNEL_CASES[impl]
    config = replace(float32, state_dtype="bfloat16")
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(37)
    rt = FAMILY.runtime(max_seq_len=1024, window_buckets=(128, 1024), **over)
    assert InferenceEngine(config, rt)._ssm_impl == "xla"
    (out,), params, _ = FAMILY.serve((config, rt), [(prompt, 512)])
    got = spy.of_request(prompt, out, 16)
    want = FAMILY.reference_logits(params, float32, prompt + out)[len(prompt) - 1: len(prompt) - 1 + len(out)]
    assert np.abs(got - want).max() > LOGIT_TOL
    spy.seen.clear()
    before = ssm_kernel_traces(fresh=True)
    (out32,), params, _ = FAMILY.serve((float32, rt), [(prompt, 512)])  # and the stated precision passes
    got = spy.of_request(prompt, out32, 16)
    want = FAMILY.reference_logits(params, float32, prompt + out32)[len(prompt) - 1:][: len(out32)]
    assert np.abs(got - want).max() < LOGIT_TOL
    assert (ssm_kernel_traces() > before) == (impl == "pallas_interpret")


# ----------------------------------------------------------------- (f)
def hf_tensors(params, c: ModelConfig) -> dict[str, np.ndarray]:
    """The toy tree in HF GraniteMoeHybrid's names and fused layouts."""
    def f(a):
        return np.ascontiguousarray(np.asarray(a, np.float32))

    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    L = params["layers"]
    out = {"model.embed_tokens.weight": f(params["embed"]),
           "model.norm.weight": f(params["final_norm"])}
    ia = im = 0
    for i, kind in enumerate(c.layer_types):
        pre = f"model.layers.{i}."
        out[pre + "shared_mlp.input_linear.weight"] = f(np.concatenate(
            [np.asarray(L["mlp"]["w_gate"][i]).T, np.asarray(L["mlp"]["w_up"][i]).T]))
        out[pre + "shared_mlp.output_linear.weight"] = f(np.asarray(L["mlp"]["w_down"][i]).T)
        out[pre + "post_attention_layernorm.weight"] = f(L["mlp"]["mlp_norm"][i])
        if kind == "attention":
            a = jax.tree.map(lambda x: np.asarray(x[ia]), L["attn"])
            out[pre + "self_attn.q_proj.weight"] = f(a["wq"].reshape(D, H * hd).T)
            out[pre + "self_attn.k_proj.weight"] = f(a["wk"].reshape(D, K * hd).T)
            out[pre + "self_attn.v_proj.weight"] = f(a["wv"].reshape(D, K * hd).T)
            out[pre + "self_attn.o_proj.weight"] = f(a["wo"].reshape(H * hd, D).T)
            out[pre + "input_layernorm.weight"] = f(a["attn_norm"])
            ia += 1
        else:
            m = jax.tree.map(lambda x: np.asarray(x[im]), L["mamba"])
            out[pre + "mamba.in_proj.weight"] = f(m["w_in"])
            out[pre + "mamba.conv1d.weight"] = f(m["conv_w"].T[:, None, :])
            out[pre + "mamba.conv1d.bias"] = f(m["conv_b"])
            for name in ("A_log", "D", "dt_bias"):
                out[pre + "mamba." + name] = f(m[name])
            out[pre + "mamba.norm.weight"] = f(m["norm"])
            out[pre + "mamba.out_proj.weight"] = f(m["w_out"].T)
            out[pre + "input_layernorm.weight"] = f(m["mixer_norm"])
            im += 1
    return out


HF_CONFIG = {
    "model_type": "granitemoehybrid", "vocab_size": 128, "hidden_size": 32,
    "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "shared_intermediate_size": 64, "intermediate_size": 64, "num_local_experts": 0,
    "layer_types": ["mamba", "mamba", "attention"] * 2, "mamba_n_heads": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2, "position_embedding_type": "nope",
    "attention_multiplier": 0.25, "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 1024,
}


def test_a_checkpoint_in_hf_names_loads_to_the_tree_the_reference_agrees_with(tmp_path):
    from safetensors.numpy import save_file

    from calfkit_tpu.inference.loader import config_from_hf, load_params
    from calfkit_tpu.inference.sharding import make_mesh, param_shardings

    params = M.init_params(TOY, jax.random.key(4))
    (tmp_path / "config.json").write_text(json.dumps(HF_CONFIG))
    save_file(hf_tensors(params, TOY), str(tmp_path / "model.safetensors"))
    config = replace(config_from_hf(tmp_path), name=TOY.name, dtype="float32")
    assert config == TOY
    loaded = load_params(tmp_path, config, param_shardings(config, make_mesh()))
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert got.dtype == want.dtype and np.array_equal(np.asarray(got), np.asarray(want))
    # and the tree that came through HF's fused layouts serves what the reference computes
    tokens = np.asarray([FAMILY.prompt_of(24, seed=9)], np.int32)
    lens = np.asarray([24], np.int32)
    logits, _, _ = M.forward(
        loaded, config, jnp.asarray(tokens), jnp.arange(24, dtype=jnp.int32)[None],
        M.make_empty_cache(config, 1, 24), jnp.asarray(lens),
        state=mm.make_recurrent_state(config, 1),
    )
    assert np.abs(np.asarray(logits) - ARCH.forward_logits(loaded, config, tokens, lens)).max() \
        < LOGIT_TOL


# ----------------------------------------------------------------- (g)
@pytest.mark.parametrize("option, kwargs", [
    ("speculative", {"speculative": SpecConfig()}),
    ("tp > 1", {"tp": 2}),
    ("quantization", {"quantization": "int8"}),
    ("long_context", {"long_context": True}),
])
def test_what_cannot_keep_the_state_is_refused_at_construction(option, kwargs):
    with pytest.raises(UnsupportedWithRecurrentLayers, match=option):
        InferenceEngine(TOY, FAMILY.runtime(**kwargs))


def test_routed_experts_are_refused_by_the_loader(tmp_path):
    from calfkit_tpu.inference.loader import RoutedExpertsUnsupported, config_from_hf

    (tmp_path / "config.json").write_text(json.dumps({**HF_CONFIG, "num_local_experts": 8}))
    with pytest.raises(RoutedExpertsUnsupported, match="num_local_experts"):
        config_from_hf(tmp_path)


# ------------------------------------------------- the rest of the contract
def test_a_cached_prefix_is_declined_and_counted():
    """With the prefix cache on, a model with recurrent layers plans no
    reuse: the second, identical prompt is prefilled whole, counted once,
    and answers what the first did.  (The prefix cache on is another
    runtime: a build of its own.)"""
    prompt = FAMILY.prompt_of(40, seed=3)
    (a, b), _, counters = FAMILY.serve((TOY, FAMILY.runtime(prefix_cache=True)), [(prompt, 6), (prompt, 6)])
    assert a == b
    assert counters["prefix_reuse_declined_recurrent"] == 1
    assert counters["prefix_hits"] == 0 and counters["prefix_reused_tokens"] == 0
    assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (2, 0)


def test_the_new_counters_reach_metrics(standing):
    text = standing.serve([(FAMILY.prompt_of(20), 3)]).metrics
    for name in ("calfkit_engine_pipeline_drains_wave_total",
                 "calfkit_engine_wave_landings_deferred_total",
                 "calfkit_engine_prefix_reuse_declined_recurrent_total",
                 "calfkit_engine_recurrent_state_bytes"):
        assert name in text


@pytest.mark.parametrize("layout, chunked", [("dense", False), ("dense", True), ("paged", False)])
def test_the_other_layouts_serve_the_same_logits(monkeypatch, layout, chunked):
    """The dense KV layout and single-shot prefill thread the state too
    (each another lane: a build of its own)."""
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(23, seed=7)
    rt = FAMILY.runtime(kv_layout=layout, chunked_prefill=chunked)
    (out,), params, _ = FAMILY.serve((TOY, rt), [(prompt, 7)])
    steps = [s for s in spy.seen if s.shape[1] == 1]
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    slot = next(b for b in range(2) if int(np.argmax(steps[0][b, 0])) == out[1])
    for i in range(len(out) - 1):
        assert np.abs(steps[i][slot, 0] - want[len(prompt) + i]).max() < LOGIT_TOL


def test_concurrent_rows_do_not_touch_each_other_s_state(standing):
    """Two requests of different lengths decode side by side (one wave of
    two ragged rows, then one dispatch for both): each one's tokens are
    what it gets alone."""
    a, b = FAMILY.prompt_of(37, seed=11), FAMILY.prompt_of(18, seed=12)
    (alone_a,) = standing.serve([(a, 10)]).outs
    (alone_b,) = standing.serve([(b, 14)]).outs
    together = standing.serve([(a, 10), (b, 14)], sequential=False).outs
    assert together == [alone_a, alone_b]


def test_the_dense_description_is_what_it_was():
    """No ``layer_types``: the tree, the KV layers and the parameter count
    of the Llama-family decoder."""
    c = preset("debug")
    assert not c.recurrent and c.n_kv_layers == c.n_layers and c.layer_types == ()
    assert set(M.init_params(c, jax.random.key(0))["layers"]) == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm", "mlp_norm"}
    assert preset("llama-3-8b").param_count == 8030261248
    g = preset("granite-4.0-h-micro")
    assert g.layer_period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (g.n_mamba_layers, g.n_kv_layers) == (36, 4)
    assert g.recurrent_state_bytes(64) == 4831838208 + 60162048


# ------------------------------------- heads of 64: the paged decode read in place
# the toy at granite's head width: two positions of a kv head a lane row in
# the kernel's view of the pool, a float32 page of 16 a view of 8 rows
TOY64 = replace(TOY, name="toy-hybrid-64", d_model=256)
assert TOY64.head_dim == 64


def test_heads_of_64_serve_the_same_tokens_through_the_kernel():
    """``pallas_interpret`` against ``xla`` on one engine configuration: more
    requests than slots, so chunks ride live dispatches (the ragged
    program's decode loop) and rows decode on alone (the decode program);
    the tokens are equal and the kernel that reads live pages in place was
    traced.  (Heads of 64 under two implementations: two builds of its own.)"""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

    rt = dict(page_size=16, window_buckets=(64, 128))
    requests = [(FAMILY.prompt_of(9 + 13 * i, seed=20 + i), 6 + 5 * i) for i in range(4)]
    want, _, _ = FAMILY.serve((TOY64, FAMILY.runtime(**rt)), requests, sequential=False)
    before = KERNEL_TRACES["paged_decode", "interpreted"]
    got, _, counters = FAMILY.serve(
        (TOY64, FAMILY.runtime(attention_impl="pallas_interpret", **rt)), requests, sequential=False)
    assert got == want
    assert [len(out) for out in got] == [n for _, n in requests]
    assert KERNEL_TRACES["paged_decode", "interpreted"] > before
    assert 0 < counters["unified_dispatches"] < counters["decode_dispatches"]
    assert 0 < counters["decode_pages_live"] < counters["decode_pages_window"]


@pytest.mark.parametrize(
    "platform,devices,config,page,want",
    [
        pytest.param("tpu", 1, TOY64, 16, "pallas", id="tpu-float32-page16"),
        pytest.param("tpu", 1, replace(TOY64, dtype="bfloat16"), 32, "pallas", id="tpu-bf16-page32"),
        # a view of 8 rows of bf16, of 4 rows of float32: under a sublane tile
        pytest.param("tpu", 1, replace(TOY64, dtype="bfloat16"), 16, "xla", id="tpu-bf16-page16"),
        pytest.param("tpu", 1, TOY64, 8, "xla", id="tpu-float32-page8"),
        # heads of 8: sixteen positions a lane row want pages of 128
        pytest.param("tpu", 1, TOY, 8, "xla", id="tpu-heads-of-8"),
        pytest.param("tpu", 2, TOY64, 16, "xla", id="tpu-two-devices"),
        pytest.param("cpu", 1, TOY64, 16, "xla", id="cpu"),
    ],
)
def test_the_paged_decode_read_is_selected_by_platform_and_shape(
    monkeypatch, platform, devices, config, page, want
):
    """``_resolved_attn_impl()`` under "auto" answers from the
    platform, the mesh's size, the head, the page and the cache's dtype:
    a dense model of the same head and page gets the hybrid's answer."""
    from types import SimpleNamespace

    real = jax.devices()
    dense = ModelConfig(
        name="toy-dense", vocab_size=128, d_model=config.d_model, n_layers=2,
        n_heads=config.n_heads, n_kv_heads=config.n_kv_heads, d_ff=64, dtype=config.dtype,
        max_seq_len=1024,
    )
    answers = []
    for c in (config, dense):
        engine = InferenceEngine(c, FAMILY.runtime(page_size=page, prefill_chunk=32, window_buckets=(128,)))
        monkeypatch.setattr(engine, "mesh", SimpleNamespace(size=devices))
        monkeypatch.setattr(
            jax, "devices", lambda *a: [SimpleNamespace(platform=platform)] if not a else real)
        answers.append(engine._resolved_attn_impl())
        monkeypatch.setattr(jax, "devices", lambda *a: real)
    assert answers == [want, want]
