"""Kimi Delta Attention beside latent attention (Ling-3.0-flash-VL's kind)
through the engine: prefill then decode on the latent pool and the per-slot
state against the plain reference, what the served rows leave behind, what
the engine refuses for this model and why.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import asyncio
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import (
    SpecConfig,
    UnsupportedWithRecurrentLayers,
)
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import KDA_MLA_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - fixtures
    Spy, both_forms_at_toy_size, check_the_step_kernel_is_not_taken,
    check_the_step_kernel_serves_what_xla_serves, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


@pytest.fixture(scope="module")
def one_engine(standing):
    """What three suites read of the module's ONE engine."""
    return FAMILY.served_in_three_phases(standing)


def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(one_engine):
    """Pages of 8, chunks of 16 under a prompt of 37 (a padded tail), blocks
    of 8 in sub-blocks of 4; 21 generated tokens cross five dispatches of
    four steps and two windows.  Every generated position's logits (the
    one-pass step on the carried state, the absorbed read of the latent pool,
    the dense expert form) against the reference's full forward of prompt +
    output; one engine holds a latent pool AND a recurrent state."""
    spy, prompt = one_engine.seen[0], FAMILY.prompt_of(37)
    out, params, counters = one_engine.first, one_engine.params, one_engine.counters[0]
    got = Spy.of_request(spy, prompt, out, 16)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    assert np.abs(chunks - want[: len(prompt)]).max() < LOGIT_TOL
    # 5 expert layers x 3 experts a token x (37 prompt tokens + 20 decode steps run)
    assert counters["moe_assignments"] + counters["moe_assignments_absent"] == 5 * 3 * (37 + 20)
    assert counters["moe_assignments"] > 0 < counters["moe_assignments_absent"]
    assert 0 < counters["moe_rows_in_held_groups"] < 5 * (37 + 20)
    assert 0 < counters["moe_experts_hit"] <= 5 * 3 * 20
    assert counters["recurrent_state_bytes"] == 2 * TOY.recurrent_state_bytes(1) > 0
    # 2 latent layers x (2 slots x 16 pages + the trash page) x 8 tokens x (16 + 4) float32
    assert counters["latent_cache_bytes"] == 2 * 33 * 8 * 20 * 4 > 0


def test_a_reused_slot_starts_from_zero_state_and_two_rows_do_not_mix(one_engine):
    """Three requests one after another through two slots (every one lands
    in a slot another request left), then two at once: each served as alone."""
    spy, params = one_engine.seen[1], one_engine.params  # the logits of the three served alone
    for prompt, out in zip((p for p, _ in one_engine.requests), one_engine.alone):
        got = Spy.of_request(spy, prompt, out, 16)
        want = FAMILY.reference_logits(params, TOY, prompt + out)
        assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
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


def test_a_bfloat16_state_fails_the_reference(monkeypatch):
    """``S`` rounded to bfloat16 where a chunk or a step leaves it: the
    decode steps' logits miss the tolerance that the float32 state passes."""
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


@pytest.mark.parametrize("fault", ["none", "state_in_bfloat16", "gate_in_bfloat16"])
def test_what_the_served_rows_leave_in_the_engine_is_held_to_its_limits(monkeypatch, capsys, fault):
    """The architecture file's second check, at the configuration file's
    rehearsal sizes in float32: it finds the engine that serves the tree it
    is handed, reads back the delta-rule state the finished rows left in
    their slots and the tokens each held expert was sent, and holds the
    first layer's of each to the reference's.  As stated both read (nearly)
    nothing; a state STORED in bfloat16, and a gate TAKEN in bfloat16, each
    FAILS its own limit, through the harness's own comparison.  (The
    configuration file's rehearsal sizes, and each fault another program:
    builds of its own.)"""
    import dataclasses

    from benchmarks.reference import agreement

    monkeypatch.undo()  # the file's own rehearsal sizes
    with open(manifest.os.path.join(
            manifest.os.path.dirname(manifest.__file__), "configs",
            "ling-3.0-flash-vl.json")) as f:
        config = json.load(f)
    toy, rt = ARCH.model(config, True)
    assert (toy.state_error_limit, toy.gate_mismatch_limit) == (0.0, 0.0)  # logged at toy widths
    assert toy.layer_types == ("kda",) * 6 + ("attention",) and toy.first_k_dense == 1
    stated, _ = ARCH.model(config, False)
    assert stated.state_error_limit == config["agreement"]["state_error_limit"] > 0
    assert stated.gate_mismatch_limit == config["agreement"]["gate_mismatch_limit"] > 0
    new = 16  # 15 decode steps are needed; dispatches of 4 or 8 run 16 and feed the last token
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=new,
                              state_error_limit=1e-4, gate_mismatch_limit=1e-6)
    served = toy
    if fault == "state_in_bfloat16":
        served = dataclasses.replace(toy, state_dtype="bfloat16")
    if fault == "gate_in_bfloat16":
        right = moe.route

        def rounded(h, lp, c):
            b = jnp.bfloat16
            return right(h.astype(b), {**lp, "router": lp["router"].astype(b)}, c)

        monkeypatch.setattr(moe, "route", rounded)
    rng = np.random.default_rng(4)
    # 4 rows in two waves (no slot is taken again before the check reads it): some 450
    # tokens, so that a bfloat16 gate flips a HELD choice of the first expert layer
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (60, 100, 120, 110)]

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
    assert counts.shape == (6, toy.n_routed_experts)
    assert int(counts.sum()) == engine.stats.counters()["moe_assignments"]
    capsys.readouterr()
    result = agreement(ARCH.forward_top2, engine.params, toy, prompts, outs, 0.25, 8)
    printed = capsys.readouterr()
    line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
    assert line["rows_fed_their_last_token"] == len(prompts)
    assert len(set(line["state_slots"])) == len(prompts)  # a slot each, none taken again
    # a bfloat16 state moves the stream the first EXPERT layer's gate reads (it follows two
    # delta-rule layers), so it may trip the second limit too; a bfloat16 gate moves no
    # state of the FIRST delta-rule layer
    over = {"none": [], "state_in_bfloat16": ["state_error"],
            "gate_in_bfloat16": ["gate_mismatch"]}[fault]
    assert line["over_their_limit"][:1] == over[:1] and (
        fault == "state_in_bfloat16" or line["over_their_limit"] == over), line
    assert result["ok"] == (not over) and result["compared"] >= 8, result
    assert printed.err.count("FAIL") == len(line["over_their_limit"])
    assert printed.err.count("(limit <= ") == 2
    if fault == "none":
        assert line["state_error"] < 1e-5 and line["gate_mismatch"] == 0.0, line
    elif fault == "state_in_bfloat16":
        assert line["state_error"] > 1e-3, line
    else:
        assert line["gate_mismatch"] > 0 and line["state_error"] < 1e-5, line
    del engine


def test_prefix_reuse_is_declined_and_counted():
    """Pages hold no recurrent state at their edge: reuse is declined, the
    second request prefills whole and serves the same tokens.  (The prefix
    cache on is another runtime: a build of its own.)"""
    prompt = FAMILY.prompt_of(40, seed=5)
    outs, _, counters = FAMILY.serve((TOY, FAMILY.runtime(prefix_cache=True)), [(prompt, 3), (prompt, 3)])
    assert outs[0] == outs[1]
    assert counters["prefix_reuse_declined_recurrent"] >= 1 and counters["prefix_hits"] == 0


@pytest.mark.parametrize("option,reason", [
    (dict(speculative=SpecConfig(k=2)), "no state snapshot"),
    (dict(tp=2), "no sharding"),
    (dict(dp=2), "no sharding"),
    (dict(quantization="int8"), "no scales"),
    (dict(long_context=True), "no recurrent state"),
    (dict(kv_layout="dense"), "served from pages"),
], ids=["speculative", "tp", "dp", "quantization", "long_context", "dense_layout"])
def test_what_the_engine_cannot_keep_right_is_refused_with_its_reason(option, reason):
    with pytest.raises(UnsupportedWithRecurrentLayers, match=reason) as raised:
        InferenceEngine(TOY, FAMILY.runtime(**option))
    assert "Kimi Delta Attention" in str(raised.value)


def test_the_latent_decode_kernel_reads_the_hybrid_s_pool(monkeypatch):
    """A latent of 128 | 64 on pages of 16 is inside the latent read's rule
    and a value head of 128 inside the delta step's: in interpret mode the
    hybrid's ONE kind of attention layer and the state's pass by key channel
    serve what XLA serves.  (Another configuration under two
    implementations: builds of its own.)"""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

    wide = replace(TOY, kv_lora_rank=128, qk_rope_head_dim=64, n_layers=3,
                   layer_types=TOY.layer_types[:3], gdn_d_v=128)
    params = FAMILY.seeded(wide)
    prompt = FAMILY.prompt_of(29, seed=9)
    rt = dict(page_size=16, prefill_chunk=32)
    (xla,), _, _ = FAMILY.serve((wide, FAMILY.runtime(attention_impl="xla", **rt)), [(prompt, 9)],
                         params=params)
    before = dict(KERNEL_TRACES)
    engine = InferenceEngine(wide, FAMILY.runtime(attention_impl="pallas_interpret", **rt),
                             params=params)
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "pallas_interpret")
    (out,), _, _ = FAMILY.serve((wide, FAMILY.runtime(attention_impl="pallas_interpret", **rt)),
                         [(prompt, 9)], params=params)
    assert out == xla
    for kernel in ("latent_decode", "delta_step"):
        assert KERNEL_TRACES[(kernel, "interpreted")] > before.get((kernel, "interpreted"), 0)


def test_the_new_counter_and_both_gauges_are_in_the_metrics_and_the_catalog(one_engine):
    from calfkit_tpu.observability.devtrace import SCOPES

    text = one_engine.metrics  # as the shared engine's first request left it
    for name in ("calfkit_engine_moe_rows_in_held_groups_total",
                 "calfkit_engine_moe_assignments_absent_total",
                 "calfkit_engine_recurrent_state_bytes", "calfkit_engine_latent_cache_bytes"):
        assert name in text, name
    assert {"decay", "groups", "gdn", "mla", "state"} <= SCOPES
    with open(manifest.os.path.join(manifest.ROOT, "docs", "observability.md")) as f:
        catalog = f.read()
    for name in ("moe_rows_in_held_groups", "gdn/decay", "moe/router/groups"):
        assert name in catalog, name


def test_a_decode_step_s_experts_through_the_step_kernel(monkeypatch, standing):
    """Experts held by share behind a gate that chooses by group, of one lane
    tile a side, beside a latent of 128 | 64 on pages of 16 and a value head
    of 128 (inside the latent read's and the delta step's rules too): the
    step kernel in interpret mode serves what XLA serves.  Under "auto" on
    this CPU the module's engine ran none of its steps.  (Another
    configuration under two implementations: builds of its own.)"""
    wide = replace(TOY, kv_lora_rank=128, qk_rope_head_dim=64, n_layers=3,
                   layer_types=TOY.layer_types[:3], gdn_d_v=128, d_model=128, moe_d_ff=128)
    check_the_step_kernel_serves_what_xla_serves(
        FAMILY, wide, monkeypatch, chunk=32, page_size=16, prefill_chunk=32)
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(standing.engine, monkeypatch, "cpu", ("auto",))
