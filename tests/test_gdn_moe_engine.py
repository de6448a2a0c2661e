"""Gated DeltaNet hybrid (Qwen3-Next's kind): the program's logits against the plain reference
through the engine, and the controls that each have to FAIL the tolerance.

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
from tests.arch_harness import (  # noqa: F401 - fixtures
    Spy, both_forms_at_toy_size, check_the_step_kernel_is_not_taken,
    check_the_step_kernel_serves_what_xla_serves, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


@pytest.fixture(scope="module")
def one_engine(standing):
    """What three suites read of the module's ONE engine."""
    return FAMILY.served_in_three_phases(standing)


# ------------------------------------------------ (b) the program against the reference
@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_full_forward_agrees_with_the_reference(monkeypatch, form):
    """The whole forward (one chunk: the chunkwise delta rule, both forms of
    the expert products) against the reference at every own position of two
    ragged rows; the counters count the own positions alone, the held
    experts' assignments and the absent ones' apart."""
    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
    params = FAMILY.seeded(key=1)
    tokens = np.random.default_rng(2).integers(3, TOY.vocab_size, (2, 40)).astype(np.int32)
    lens = np.asarray([40, 27], np.int32)
    logits, (k, v), (S, conv), (counts, _, absent) = FAMILY.forward(
        params, TOY, tokens, lens, moe=moe.moe_stats_init(TOY))
    assert moe.dense_form(2 * 40, TOY) == (form == "dense")
    # K and V of the 2 attention layers alone; the state pair of the 6 others
    assert k.shape == v.shape == (2, 2, 2, 40, 16)
    assert S.shape == (6, 2, 4, 8, 8) and conv.shape == (6, 3, 2, 64)
    want = ARCH.forward_logits(params, TOY, tokens, lens)
    for r in range(2):
        assert np.abs(np.asarray(logits[r, : lens[r]]) - want[r, : lens[r]]).max() < LOGIT_TOL
    assert counts.shape == (8, 4)
    assert int(counts.sum()) + int(absent) == (40 + 27) * 3 * 8
    assert 0.3 < int(counts.sum()) / ((40 + 27) * 3 * 8) < 0.7  # about half are held here


def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(one_engine):
    """Pages of 8, chunks of 16 under a prompt of 37 (a padded tail), blocks
    of 8; 21 generated tokens cross five dispatches of four steps and two
    windows.  Every generated position's logits (the one-pass step on the
    carried state, the paged read, the dense expert form) against the
    reference's full forward of prompt + output."""
    spy, prompt = one_engine.seen[0], FAMILY.prompt_of(37)
    out, params, counters = one_engine.first, one_engine.params, one_engine.counters[0]
    got = Spy.of_request(spy, prompt, out, 16)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    assert np.abs(chunks - want[: len(prompt)]).max() < LOGIT_TOL
    # 8 layers x 3 experts a token x (37 prompt tokens + 20 decode steps run)
    assert counters["moe_assignments"] + counters["moe_assignments_absent"] == 8 * 3 * (37 + 20)
    assert counters["moe_assignments"] > 0 < counters["moe_assignments_absent"]
    assert 0 < counters["moe_experts_hit"] <= 8 * 3 * 20
    assert counters["recurrent_state_bytes"] == 2 * TOY.recurrent_state_bytes(1)
    assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (1, 0)
    assert counters["latent_cache_bytes"] == 0


@pytest.mark.parametrize("form", ["grouped", "dense"])
def test_every_chunk_of_a_served_prompt_is_counted_by_its_form(monkeypatch, request, form):
    """Three chunks of 16 under a prompt of 37: past the limit (8 tokens at
    toy size) each counts as grouped, as every chunk of the Qwen3-Next cell
    does; under a limit of 4,096 as dense.  Counted at enqueue from shapes."""
    from calfkit_tpu.observability.metrics import metrics_text

    if form == "dense":
        monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 4096)
        _, _, counters = FAMILY.serve((TOY, FAMILY.runtime()), [(FAMILY.prompt_of(37), 3)])
        text = metrics_text()
    else:  # the shared engine's first request is that prompt
        shared = request.getfixturevalue("one_engine")
        counters, text = shared.counters[0], shared.metrics
    assert (counters["moe_grouped_chunks"], counters["moe_dense_chunks"]) == (
        (3, 0) if form == "grouped" else (0, 3))
    assert "calfkit_engine_moe_grouped_chunks_total" in text
    assert "calfkit_engine_moe_dense_chunks_total" in text


def test_a_reused_slot_starts_from_zero_state_and_two_rows_do_not_mix(one_engine):
    """Three requests one after another through two slots (every one lands
    in a slot another request left), then two at once: each served as alone."""
    spy, params = one_engine.seen[1], one_engine.params
    for prompt, out in zip((p for p, _ in one_engine.requests), one_engine.alone):
        got = Spy.of_request(spy, prompt, out, 16)
        want = FAMILY.reference_logits(params, TOY, prompt + out)
        assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
    # one after another: each wave lands on an engine with no active rows, by a sync of its own
    before, after = one_engine.counters
    assert (after["pipeline_drains_wave"] - before["pipeline_drains_wave"],
            after["wave_landings_deferred"]) == (3, 0)
    assert one_engine.together == one_engine.alone[:2]


def test_single_shot_prefill_serves_the_same_logits(monkeypatch):
    """(Single-shot prefill is another lane: a build of its own.)"""
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(23, seed=7)
    (out,), params, _ = FAMILY.serve((TOY, FAMILY.runtime(chunked_prefill=False)), [(prompt, 7)])
    steps = [s for s in spy.seen if s.shape[1] == 1]
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    slot = next(b for b in range(2) if int(np.argmax(steps[0][b, 0])) == out[1])
    for i in range(len(out) - 1):
        assert np.abs(steps[i][slot, 0] - want[len(prompt) + i]).max() < LOGIT_TOL


@pytest.mark.parametrize("fault", ["none", "state_in_bfloat16", "gate_in_bfloat16"])
def test_what_the_served_rows_leave_in_the_engine_is_held_to_its_limits(monkeypatch, capsys, fault):
    """The architecture file's second check, at toy size in float32: it finds
    the engine that serves the tree it is handed, reads back the delta-rule
    state the finished rows left in their slots (``recurrent_state()``) and
    the tokens each held expert was sent (``moe_expert_counts()``), and holds
    the first layer's of each to the reference's.  As stated both read
    (nearly) nothing; a state STORED in bfloat16, and a gate TAKEN in
    bfloat16, each passes the margin rule's tokens or not, and FAILS its own
    limit, through the harness's own comparison.  (The configuration file's
    rehearsal sizes, and each fault another program: builds of its own.)"""
    import asyncio
    import dataclasses

    from benchmarks.reference import agreement

    monkeypatch.undo()  # the file's own rehearsal sizes: a chunk of 64 takes the dense form
    with open(manifest.os.path.join(
            manifest.os.path.dirname(manifest.__file__), "configs",
            "qwen3-next-80b-a3b-instruct.json")) as f:
        config = json.load(f)
    toy, rt = ARCH.model(config, True)
    assert (toy.state_error_limit, toy.gate_mismatch_limit) == (0.0, 0.0)  # logged at toy widths
    stated, _ = ARCH.model(config, False)
    assert stated.state_error_limit == config["agreement"]["state_error_limit"] > 0
    assert stated.gate_mismatch_limit == config["agreement"]["gate_mismatch_limit"] > 0
    new = 16  # 15 decode steps are needed; dispatches of 4 or 8 run 16 and feed the last token
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=new, routing_tie=0.0,
                              state_error_limit=1e-4, gate_mismatch_limit=1e-6)
    served = toy
    if fault == "state_in_bfloat16":
        served = dataclasses.replace(toy, state_dtype="bfloat16")
    if fault == "gate_in_bfloat16":
        def rounded(h, lp, c):
            logits = h.astype(jnp.bfloat16) @ lp["router"].astype(jnp.bfloat16)
            w, chosen = jax.lax.top_k(jax.nn.softmax(logits.astype(jnp.float32), axis=-1),
                                      c.n_experts_per_tok)
            return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True)
        monkeypatch.setattr(moe, "route", rounded)
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (9, 40, 70, 100)]

    async def run():
        engine = InferenceEngine(served, replace(rt, compilation_cache=False), seed=3,
                                 params=FAMILY.seeded(served, key=5))
        await engine.start()
        try:
            async def one(p):
                return [t async for t in engine.generate(p, max_new_tokens=new)]
            return engine, list(await asyncio.gather(*[one(p) for p in prompts]))
        finally:
            await engine.stop()

    engine, outs = asyncio.run(run())  # the engine stays alive: the check finds it by its tree
    S, conv = engine.recurrent_state()
    assert S.shape[:2] == (6, rt.max_batch_size) and S.dtype == jnp.dtype(served.state_dtype)
    counts = engine.moe_expert_counts()
    assert counts.shape == (8, toy.n_routed_experts)
    assert int(counts.sum()) == engine.stats.counters()["moe_assignments"]
    capsys.readouterr()
    result = agreement(ARCH.forward_top2, engine.params, toy, prompts, outs, 0.25, 8)
    printed = capsys.readouterr()
    line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
    assert line["rows_fed_their_last_token"] == len(prompts)
    assert len(set(line["state_slots"])) == len(prompts)  # a slot each, none taken again
    over = {"none": [], "state_in_bfloat16": ["state_error"],
            "gate_in_bfloat16": ["gate_mismatch"]}[fault]
    assert line["over_their_limit"] == over, line
    assert result["ok"] == (not over) and result["compared"] >= 8, result
    assert printed.err.count("FAIL") == len(over) and printed.err.count("(limit <= ") == 2
    if fault == "none":
        assert line["state_error"] < 1e-5 and line["gate_mismatch"] == 0.0, line
    elif fault == "state_in_bfloat16":
        assert line["state_error"] > 1e-3 and line["gate_mismatch"] <= 0.02, line
    else:
        assert line["gate_mismatch"] > 0 and line["state_error"] < 1e-5, line
    del engine


def test_prefix_reuse_is_declined_and_counted():
    """(The prefix cache on is another runtime: a build of its own.)"""
    prompt = FAMILY.prompt_of(40, seed=5)
    outs, _, counters = FAMILY.serve((TOY, FAMILY.runtime(prefix_cache=True)), [(prompt, 3), (prompt, 3)])
    assert outs[0] == outs[1]
    assert counters["prefix_reuse_declined_recurrent"] >= 1 and counters["prefix_hits"] == 0


def test_the_paged_decode_kernel_reads_a_head_of_256_with_8_query_heads_a_kv_head(monkeypatch):
    """The published attention shape (16 query heads over 2 KV heads of 256)
    is inside the decode read's rule and a value head of 128 inside the
    delta step's: in interpret mode BOTH kernels serve what XLA serves.
    (Another configuration under two implementations: builds of its own.)"""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

    wide = replace(TOY, attn_head_dim=256, n_heads=16, n_kv_heads=2, n_layers=4,
                   layer_types=TOY.layer_types[:4], gdn_d_v=128)
    params = FAMILY.seeded(wide)
    prompt = FAMILY.prompt_of(29, seed=9)
    (xla,), _, _ = FAMILY.serve((wide, FAMILY.runtime(attention_impl="xla")), [(prompt, 9)], params=params)
    before = dict(KERNEL_TRACES)
    spy = Spy(monkeypatch)
    engine = InferenceEngine(wide, FAMILY.runtime(attention_impl="pallas_interpret"), params=params)
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "pallas_interpret")
    (out,), _, _ = FAMILY.serve(
        (wide, FAMILY.runtime(attention_impl="pallas_interpret")), [(prompt, 9)], params=params)
    assert out == xla
    for kernel in ("paged_decode", "delta_step"):
        assert KERNEL_TRACES[(kernel, "interpreted")] > before.get((kernel, "interpreted"), 0)
    got = spy.of_request(prompt, out, 16)
    want = FAMILY.reference_logits(params, wide, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL


# ------------------------------------------------ (d) the controls, each of which has to FAIL
def _forward_error(config=TOY, params=None):
    params = FAMILY.seeded(key=1) if params is None else params
    tokens = np.random.default_rng(3).integers(3, TOY.vocab_size, (1, 40)).astype(np.int32)
    want = ARCH.forward_logits(params, TOY, tokens, np.asarray([40], np.int32))
    return float(np.abs(np.asarray(FAMILY.forward(params, config, tokens)[0]) - want).max())


def _bfloat16_gate(monkeypatch):
    right = moe.route

    def rounded(h, lp, config):
        b = jnp.bfloat16
        return right(h.astype(b), {**lp, "router": lp["router"].astype(b)}, config)

    monkeypatch.setattr(moe, "route", rounded)


def _w_for_one_plus_w(monkeypatch):
    right = M.rms_norm
    monkeypatch.setattr(M, "rms_norm", lambda x, w, eps, plus_one=False: right(x, w, eps))


def _no_output_gate(monkeypatch):
    monkeypatch.setattr(M, "attn_out_gate", lambda attn, gate: attn)


def _renormalised_over_the_held(monkeypatch):
    right = moe.route

    def over_held(h, lp, config):
        chosen, weights = right(h, lp, config)
        held = (chosen >= config.expert_first) & (
            chosen < config.expert_first + config.n_routed_experts)
        kept = jnp.where(held, weights, 0.0)
        return chosen, kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-20)

    monkeypatch.setattr(moe, "route", over_held)


def _no_shared_gate(monkeypatch):
    right = moe.moe_ffn

    def ungated(h, lp, *a, **kw):
        return right(h, {n: w for n, w in lp.items() if n != "shared_gate"}, *a, **kw)

    monkeypatch.setattr(M, "moe_ffn", ungated)


WRONG = {
    "bfloat16_gate": _bfloat16_gate,
    "w_for_1_plus_w": _w_for_one_plus_w,
    "no_output_gate": _no_output_gate,
    "weights_renormalised_over_the_held": _renormalised_over_the_held,
    "shared_expert_s_gate_left_out": _no_shared_gate,
}


def test_the_stated_program_passes_the_tolerance_the_controls_must_fail():
    assert _forward_error() < LOGIT_TOL


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_a_lower_precision_or_wrong_mathematics_fails_the_reference(monkeypatch, fault):
    WRONG[fault](monkeypatch)
    assert _forward_error() > 10 * LOGIT_TOL


def test_rotary_on_the_whole_head_fails_the_reference():
    assert _forward_error(replace(TOY, partial_rotary_factor=1.0)) > 10 * LOGIT_TOL


def test_a_bfloat16_state_fails_the_reference(monkeypatch):
    """``S`` rounded to bfloat16 where a chunk or a step leaves it: the
    decode steps' logits miss the tolerance that the float32 state passes
    (test_prefill_then_decode_through_the_engine...)."""
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(37)
    rounded = replace(TOY, state_dtype="bfloat16")
    (out,), params, _ = FAMILY.serve((rounded, FAMILY.runtime()), [(prompt, 9)])
    steps = [s for s in spy.seen if s.shape[1] == 1]
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    slot = next(b for b in range(2) if int(np.argmax(steps[0][b, 0])) == out[1])
    worst = max(float(np.abs(steps[i][slot, 0] - want[len(prompt) + i]).max())
                for i in range(len(out) - 1))
    assert worst > 10 * LOGIT_TOL


def test_a_decode_step_s_experts_through_the_step_kernel(monkeypatch, standing):
    """Experts held by share of one lane tile a side, beside an attention head
    of 256 and a value head of 128 (inside the decode read's and the delta
    step's rules too): the step kernel in interpret mode serves what XLA
    serves.  Under "auto" on this CPU the module's engine ran none of its
    steps.  (Another configuration under two implementations: builds of its own.)"""
    wide = replace(TOY, attn_head_dim=256, n_heads=16, n_kv_heads=2, n_layers=4,
                   layer_types=TOY.layer_types[:4], gdn_d_v=128, d_model=128, moe_d_ff=128)
    check_the_step_kernel_serves_what_xla_serves(FAMILY, wide, monkeypatch)
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(standing.engine, monkeypatch, "cpu", ("auto",))
