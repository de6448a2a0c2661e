"""A gated short convolution beside rotary GQA attention (LFM2-8B-A1B's kind)
through the engine: prefill then decode on the paged cache and the per-slot
conv tail against the plain reference, what the served rows leave behind, what
the engine refuses for this model and why.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import asyncio
import json
from dataclasses import replace

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import shortconv
from calfkit_tpu.inference.config import (
    SpecConfig,
    UnsupportedWithRecurrentLayers,
)
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import LFM2_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - fixtures
    Spy, both_forms_at_toy_size, check_the_step_kernel_is_not_taken, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


@pytest.fixture(scope="module")
def one_engine(standing):
    """What three suites read of the module's ONE engine."""
    return FAMILY.served_in_three_phases(standing)


def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(one_engine):
    """Pages of 8, chunks of 16 under a prompt of 37 (a padded tail: the conv
    tail is read at the row's last two REAL positions); 21 generated tokens
    cross five dispatches of four steps and two windows.  Every generated
    position's logits (the step form on the carried tail, the paged read, the
    dense expert form) against the reference's full forward of prompt +
    output, and the prompt's own against it too."""
    spy, prompt = one_engine.seen[0], FAMILY.prompt_of(37)
    out, params, counters = one_engine.first, one_engine.params, one_engine.counters[0]
    got = Spy.of_request(spy, prompt, out, 16)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    assert np.abs(chunks - want[: len(prompt)]).max() < LOGIT_TOL
    # 10 expert layers x 3 experts a token x (37 prompt tokens + 20 decode steps run):
    # every expert is held, so none is absent
    assert counters["moe_assignments"] == 10 * 3 * (37 + 20)
    assert counters["moe_assignments_absent"] == 0 == counters["moe_rows_in_held_groups"]
    assert 0 < counters["moe_experts_hit"] <= 10 * 3 * 20
    # the tail's bytes a slot (9 layers x 2 positions x 32 channels of float32) and the
    # three K and V layers are what the engine and the capacity observatory account
    assert counters["recurrent_state_bytes"] == 2 * TOY.recurrent_state_bytes(1) == 2 * 9 * 2 * 32 * 4
    assert TOY.kv_bytes_per_token(4) == 3 * 2 * 2 * 8 * 4
    assert counters["latent_cache_bytes"] == 0


def test_a_reused_slot_starts_from_a_zero_tail_and_two_rows_do_not_mix(one_engine):
    """Three requests one after another through two slots (every one lands
    in a slot another request left: its tail is overwritten whole at the
    landing), then two at once: each served as alone."""
    spy, params = one_engine.seen[1], one_engine.params  # the logits of the three served alone
    for prompt, out in zip((p for p, _ in one_engine.requests), one_engine.alone):
        got = Spy.of_request(spy, prompt, out, 16)
        want = FAMILY.reference_logits(params, TOY, prompt + out)
        assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
    assert one_engine.together == one_engine.alone[:2]


def test_the_scope_the_counters_and_the_gauge_are_in_the_metrics_and_the_catalog(one_engine):
    from calfkit_tpu.observability.devtrace import SCOPES

    text = one_engine.metrics  # as the shared engine's first request left it
    for name in ("calfkit_engine_moe_assignments_total", "calfkit_engine_moe_experts_hit_total",
                 "calfkit_engine_recurrent_state_bytes"):
        assert name in text, name
    assert {"shortconv", "in_proj", "conv", "out_proj", "state_land", "qk_norm"} <= SCOPES
    with open(manifest.os.path.join(manifest.ROOT, "docs", "observability.md")) as f:
        catalog = f.read()
    for name in ("shortconv", "decode_loop/shortconv", "shortconv_device_pct"):
        assert name in catalog, name


def test_a_wave_of_rows_of_unequal_length_lands_each_row_s_own_tail():
    """Two prompts of 37 and 9 tokens admitted in ONE wave (the short row is
    all padding in the wave's later chunks): each row's tail in its slot is
    the reference's ``u`` at ITS last two positions.  (Two slots served at
    once and read back afterwards: a build of its own.)"""
    requests = [(FAMILY.prompt_of(37, seed=1), 5), (FAMILY.prompt_of(9, seed=2), 5)]
    outs, engine, _ = FAMILY.serve((TOY, FAMILY.runtime()), requests, sequential=False, keep=True)
    seqs = [p + o for (p, _), o in zip(requests, outs)]
    width = max(len(s) for s in seqs)
    tokens = np.zeros((2, width), np.int32)
    for r, s in enumerate(seqs):
        tokens[r, : len(s)] = s
    tails = ARCH.left_behind(engine.params, TOY, tokens, np.asarray([len(s) for s in seqs]))
    read = ARCH.tail_errors(engine.recurrent_state()[1], tails)
    assert sorted(read["tail_slots"]) == [0, 1] and read["tail_error"] < 1e-5, read


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


@pytest.mark.parametrize("fault", ["none", "tail_in_bfloat16", "tail_from_a_padded_position"])
def test_what_the_served_rows_leave_in_the_engine_is_held_to_its_limit(monkeypatch, capsys, fault):
    """The architecture file's second check, at the configuration file's
    rehearsal sizes in float32: it finds the engine that serves the tree it
    is handed, reads back the conv tails the finished rows left in their
    slots and holds those of the conv layers AHEAD of the first expert layer
    to the reference's ``u`` at each row's last two positions (every layer's
    is logged).  As stated it reads (nearly) nothing; a tail KEPT in
    bfloat16, and a tail written from a chunk's END (its padding: read before
    any decode step writes over it), each FAILS the limit, through the
    harness's own comparison.  (The configuration
    file's rehearsal sizes, and each fault another program: builds of its own.)"""
    import dataclasses

    from benchmarks.reference import agreement
    from calfkit_tpu.inference import mamba

    monkeypatch.undo()  # the file's own rehearsal sizes
    with open(manifest.os.path.join(
            manifest.os.path.dirname(manifest.__file__), "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    toy, rt = ARCH.model(config, True)
    assert toy.tail_error_limit == 0.0  # logged at toy widths
    assert toy.layer_types == ("conv", "conv", "attention", "conv") * 3 and toy.first_k_dense == 2
    stated, _ = ARCH.model(config, False)
    assert stated.tail_error_limit == config["agreement"]["tail_error_limit"] > 0
    # 15 decode steps are needed; dispatches of 4 or 8 run 16 and feed the last token.  A
    # tail from a padded position is told by the FIRST layers' tails only until the decode
    # steps have written their own inputs over it: one served token, no step in between
    new = 1 if fault == "tail_from_a_padded_position" else 16
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=new,
                              tail_error_limit=1e-4)
    if fault == "tail_in_bfloat16":
        right = mamba.make_recurrent_state

        def rounded(c, rows):
            empty, tail = right(c, rows)
            return empty, tail.astype(jnp.bfloat16)

        monkeypatch.setattr(mamba, "make_recurrent_state", rounded)
        import calfkit_tpu.inference.engine as E

        monkeypatch.setattr(E, "make_recurrent_state", rounded)
    if fault == "tail_from_a_padded_position":
        import types

        from jax import lax

        # the mixer's OWN lax alone: the reference reads its tails through jax.lax too
        read_at_the_end = types.SimpleNamespace(**{**vars(lax), "dynamic_slice_in_dim": (
            lambda row, start, size, axis=0: lax.dynamic_slice_in_dim(
                row, row.shape[axis] - size, size, axis=axis))})
        monkeypatch.setattr(shortconv, "lax", read_at_the_end)
    rng = np.random.default_rng(4)
    # 4 rows in two waves (no slot is taken again before the check reads it)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (60, 100, 120, 110)]

    async def run():
        engine = InferenceEngine(toy, replace(rt, compilation_cache=False), seed=3,
                                 params=FAMILY.seeded(toy, key=5))
        await engine.start()
        try:
            async def one(p):
                return [t async for t in engine.generate(p, max_new_tokens=new)]
            return engine, list(await asyncio.gather(*[one(p) for p in prompts]))
        finally:
            await engine.stop()

    engine, outs = asyncio.run(run())  # the engine stays alive: the check finds it by its tree
    empty, tail = engine.recurrent_state()
    assert empty.shape == (9, rt.max_batch_size, 0)
    assert tail.shape == (9, 2, rt.max_batch_size, toy.d_model)
    capsys.readouterr()
    result = agreement(ARCH.forward_top2, engine.params, toy, prompts, outs, 0.25, min(8, new))
    printed = capsys.readouterr()
    line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
    if fault != "tail_from_a_padded_position":  # (whose tails are nobody's: no row is found)
        assert len(set(line["tail_slots"])) == len(prompts)  # a slot each, none taken again
    over = [] if fault == "none" else ["tail_error"]
    assert line["over_their_limit"] == over, line
    assert result["ok"] == (not over) and result["compared"] >= min(8, new), result
    assert printed.err.count("FAIL") == len(over) and printed.err.count("(limit <= ") == 1
    if fault == "none":
        assert line["rows_fed_their_last_token"] == len(prompts)
        assert line["tail_error"] <= line["tail_error_all_layers"] < 1e-5, line
        assert len(line["tail_error_by_layer"]) == 9
        # the two conv layers ahead of the first expert layer: c c | A c ...
        assert abs(line["tail_error"] - max(line["tail_error_by_layer"][:2])) < 1e-6
    elif fault == "tail_in_bfloat16":
        assert 1e-3 < line["tail_error"] < 1e-1, line
    else:  # prompts of 60, 100, 120, 110 in chunks of 64: every last chunk is padded
        assert line["tail_error"] > 0.5, line
    del engine


def test_prefix_reuse_is_declined_and_counted():
    """Pages hold no conv tail at their edge: reuse is declined, the second
    request prefills whole and serves the same tokens.  (The prefix cache on
    is another runtime: a build of its own.)"""
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
    """Every refusal is raised when the engine is BUILT, by name."""
    with pytest.raises(UnsupportedWithRecurrentLayers, match=reason) as raised:
        InferenceEngine(TOY, FAMILY.runtime(**option))
    assert "gated short convolution" in str(raised.value)


def test_the_quantizer_and_the_state_kernel_s_rule_say_no_by_name():
    """int8 leaves: ``quantize_params`` refuses the tree by name; the state's
    pass resolves to XLA whatever ``attention_impl`` asks (there is no matrix
    state for a kernel to pass over), and a head of 64 on pages of 16 takes
    the paged decode kernel in interpret mode."""
    from calfkit_tpu.inference.quant import quantize_params

    with pytest.raises(ValueError, match="short-convolution leaves have no scales"):
        quantize_params(FAMILY.seeded())
    wide = replace(TOY, d_model=256, n_heads=4, n_kv_heads=2, n_layers=4,
                   layer_types=TOY.layer_types[:4])
    engine = InferenceEngine(wide, FAMILY.runtime(
        attention_impl="pallas_interpret", page_size=16, prefill_chunk=32))
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "xla")


def test_experts_held_whole_keep_the_dense_form_under_any_value(monkeypatch, standing):
    """The step kernel (PR 53) is for experts held by SHARE: these are held
    whole and hit whole, so on a TPU the engine takes it under no value of
    ``attention_impl``, and the module's engine ran none of its steps."""
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(
        standing.engine, monkeypatch, "tpu", ("auto", "pallas", "pallas_interpret", "xla"))
