"""EvaByte's kind through the ENGINE: chunked prefill (a window a chunk) then
decode through BOTH pools (the aligned ring and the summary pages) over
contexts of three windows and more, prompts that end inside a chunk, on a
chunk's edge and on a window's edge, a dispatch that crosses a window's edge,
the counters and scopes, the refusals by name, what the served rows leave in
the engine, and the two controls of the decode read, each of which has to FAIL
the tolerance the stated program passes.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import eva
from calfkit_tpu.inference.config import (
    SpecConfig, UnsupportedWithEvaLayers, UnsupportedWithWindowLayers, replace,
)
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import EVABYTE as FAMILY
from tests.arch_harness import Spy, both_forms_at_toy_size, standing  # noqa: F401 - fixtures

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy
W, C, L = TOY.window_size, TOY.chunk_size, TOY.n_layers
CHUNK, STEPS = 32, 8  # the family's prefill chunk (ONE window) and a dispatch's steps


def _holds(spy, prompt, out, params) -> float:
    got = spy.of_request(prompt, out, CHUNK)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    return float(np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max())


@pytest.mark.parametrize("n, new, what", [
    (70, 60, "ends inside a chunk (70 = 17 x 4 + 2), in its third window; 60 tokens cross 96 and 128"),
    (72, 30, "ends on a chunk's edge (72 = 18 x 4); 30 tokens cross 96"),
    (96, 40, "ends on a window's edge (96 = 3 x 32): the first step's ring read is empty"),
    (93, 12, "a dispatch of 8 from 93 crosses 96 at its fourth step, a chunk of its own behind it"),
])
def test_prefill_then_decode_through_both_pools_across_the_edges(standing, n, new, what):
    """Every generated position's logits (the ring under the aligned bound, the
    summary pages, the fresh tokens and the chunks the dispatch itself
    completed, under one softmax) against the reference's whole forward of
    prompt + output; every chunk's too."""
    prompt = FAMILY.prompt_of(n, seed=n)
    served = standing.serve([(prompt, new)])
    (out,), spy = served.outs, served.spy
    assert len(out) == new and n >= 2 * W, what
    assert _holds(spy, prompt, out, served.params) < LOGIT_TOL, what
    want = FAMILY.reference_logits(served.params, TOY, prompt + out)
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == CHUNK])[:n]
    assert np.abs(chunks - want[:n]).max() < LOGIT_TOL, what
    added = served.added
    assert added["eva_windows_closed"] == (n + new - 1) // W  # the last token is never fed
    assert added["chunk_attn_pairs_eva_window"] > 0 < added["chunk_attn_pairs_eva_summary"]


def test_a_dispatch_that_crosses_an_edge_serves_its_later_steps_from_the_window_it_closed(standing):
    """From 93: the first decode dispatch's steps stand at 93 .. 100, so steps
    3 .. 7 are past the edge at 96 and must see chunk 23 (92 .. 95), which the
    dispatch's own first three tokens completed, pooled: not in the summary
    pages yet, not among the exact keys.  The counters say what the steps read."""
    prompt = FAMILY.prompt_of(93, seed=11)
    served = standing.serve([(prompt, 1 + STEPS)])
    (out,), added = served.outs, served.added
    # (the overlapped launch path has a second dispatch in flight when the first lands)
    n = added["decode_dispatches"]
    assert served.engine.runtime.decode_steps_per_dispatch == STEPS and n >= 1
    assert added["short_dispatches"] == 0
    assert _holds(served.spy, prompt, out, served.params) < LOGIT_TOL
    q = np.arange(93, 93 + STEPS * n)
    assert added["decode_eva_window_tokens_read"] == L * int((q % W + 1).sum())
    assert added["decode_eva_summaries_read"] == L * (W // C) * int((q // W).sum())
    # three chunks of 32 for 93 tokens pool 23 complete chunks; the first dispatch
    # completes 92..95 and 96..99, every later one two more
    assert added["eva_chunks_pooled"] == L * (93 // C + 2 * n)
    assert added["eva_windows_closed"] == 2 + (93 + STEPS * n) // W - 2
    assert (added["chunk_tokens"], added["chunk_tokens_padding"]) == (96, 3)
    assert added["chunk_attn_pairs_eva_window"] == L * (2 * (32 * 33 // 2) + 29 * 30 // 2)
    assert added["chunk_attn_pairs_eva_summary"] == L * (32 * 8 + 29 * 16)
    gauges = served.counters
    assert gauges["eva_summary_cache_bytes"] == 2 * L * 17 * 4 * 8 * 8 * 4  # 2 x 8 + 1 pages of 8
    assert (gauges["kv_pages_global_total"], gauges["kv_pages_window_total"]) == (16, 2 * 6)
    for name in ("decode_eva_summaries_read_total", "eva_chunks_pooled_total",
                 "eva_summary_cache_bytes"):
        assert f"calfkit_engine_{name}" in served.metrics, name


def test_short_and_long_rows_in_one_batch_are_served_as_if_alone(standing):
    requests = [(FAMILY.prompt_of(40, seed=1), 50), (FAMILY.prompt_of(130, seed=2), 30)]
    together = standing.serve(requests, sequential=False)
    for (prompt, _), out in zip(requests, together.outs):
        want = FAMILY.reference_logits(together.params, TOY, prompt + out)
        served = want[len(prompt) - 1: len(prompt) - 1 + len(out)]
        assert [int(t) for t in np.argmax(served, -1)] == out
    assert together.added["unified_dispatches"] >= 1
    alone = [standing.serve([request]).outs[0] for request in requests]
    assert together.outs == alone


def test_what_the_served_rows_leave_behind_is_the_reference_s(standing):
    """The summary pages hold the reference's pooled keys AND values of every
    complete chunk, the ring the rotated keys of the last window; and each
    fault the chip's limit has to catch moves the reference's own pooled keys
    by hundreds of times what the served rows read."""
    requests = [(FAMILY.prompt_of(70, seed=21), 40), (FAMILY.prompt_of(100, seed=22), 40)]
    served = standing.serve(requests, sequential=False)
    seqs = [p + o for (p, _), o in zip(requests, served.outs)]
    lens = np.asarray([len(s) for s in seqs])
    left = [[tuple(np.asarray(a) for a in layer)
             for layer in ARCH._walk(served.params, TOY, np.asarray(seq), len(seq))[1]]
            for seq in seqs]
    readings = ARCH._left_behind(served.engine, TOY, left, lens)
    assert sorted(readings["slots"]) == [0, 1]
    assert readings["ring_error"] < 1e-5 and max(readings["summary_error_by_layer"]) < 1e-5
    faults = ARCH._fault_sizes(served.params, TOY, np.asarray(seqs[0]), len(seqs[0]))
    assert set(faults) == {"summary_error_if_bfloat16_softmax", "summary_error_if_uniform_weights",
                           "summary_error_if_no_mu"}
    assert min(faults.values()) > 100 * max(readings["summary_error_by_layer"])


def _served_alone(monkeypatch, patch) -> float:
    """The worst logit of a row through an engine BUILT with ``patch`` (each
    control is another program)."""
    patch(monkeypatch)
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(93, seed=11)
    (out,), params, _ = FAMILY.serve((TOY, FAMILY.runtime()), [(prompt, 1 + STEPS)])
    return _holds(spy, prompt, out, params)


def _a_sliding_lower_bound(monkeypatch):
    monkeypatch.setattr(eva, "window_start", lambda q_pos, window: jnp.maximum(q_pos - window + 1, 0))


def _the_own_window_s_summaries_attended(monkeypatch):
    monkeypatch.setattr(eva, "summaries_in_pages", lambda start, base_lens, chunk: base_lens // chunk)


@pytest.mark.parametrize("control", [_a_sliding_lower_bound, _the_own_window_s_summaries_attended])
def test_a_wrong_decode_read_fails_the_tolerance(monkeypatch, control):
    """A sliding lower bound in place of the aligned one (the query sees the
    last 32 keys exactly, whatever its window); the summaries of the query's
    OWN window attended beside its exact keys."""
    assert _served_alone(monkeypatch, control) > LOGIT_TOL


def test_the_programs_name_the_eva_scopes():
    engine = InferenceEngine(TOY, FAMILY.runtime(attention_impl="xla"), seed=3)
    args, window, steps, sampled = engine._decode_args()
    text = jax.make_jaxpr(engine._decode_fn_paged(engine._wpages(window), steps, sampled))(
        *args).pretty_print(name_stack=True)
    for scope in ("decode_loop", "eva/qkv", "eva/attention/window", "eva/attention/summary",
                  "eva/merge", "eva/attn_out", "mlp", "kv_write", "kv_write/pool"):
        assert scope in text, scope
    sk, sv = eva.make_scratch(TOY, 1, 3 * CHUNK, jnp.float32)
    chunk = jax.make_jaxpr(engine._chunk_fn(CHUNK))(
        engine.params, sk, sv, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(CHUNK),
    ).pretty_print(name_stack=True)
    for scope in ("chunk_loop", "eva/qkv", "eva/attention", "eva/pool", "eva/attn_out", "mlp"):
        assert scope in chunk, scope


@pytest.mark.parametrize("option, kw", [
    ("speculative", dict(speculative=SpecConfig(k=2))),
    ("prefix_cache", dict(prefix_cache=True)),
    ("long_context", dict(long_context=True)),
    ("tp > 1", dict(tp=2)),
    ("dp > 1", dict(dp=2)),
    ("quantization", dict(quantization="int8")),
    ("kv_layout='dense'", dict(kv_layout="dense", chunked_prefill=False)),
    ("chunked_prefill=False", dict(chunked_prefill=False)),
    ("prefill_chunk=64", dict(prefill_chunk=64)),
    ("page_size=16", dict(page_size=16, prefill_chunk=32)),
])
def test_what_cannot_hold_with_this_cache_is_refused_by_name_when_the_engine_is_built(option, kw):
    with pytest.raises(UnsupportedWithEvaLayers, match=option.split(" ")[0]) as refused:
        InferenceEngine(TOY, FAMILY.runtime(**kw), seed=3)
    assert "EVA layers" in str(refused.value)
    assert isinstance(refused.value, UnsupportedWithWindowLayers)


def test_the_kernels_read_both_pools_in_interpret_mode(monkeypatch):
    """Heads of 128 on pages of 16 (inside both kernels' rules): the paged
    decode kernel's WINDOW form under the aligned lower bound over the ring and
    its global form over the summary pages, and the chunk kernel over the
    summaries with the chunk's keys behind them, serve the tokens XLA serves
    and the reference's logits (an engine of its own: another model)."""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

    wide = replace(TOY, d_model=256, n_heads=2, n_kv_heads=2, n_layers=2,
                   layer_types=TOY.layer_types[:2], window_size=128,
                   chunk_size=8, max_seq_len=512)
    rt = dict(max_seq_len=512, window_buckets=(512,), prefill_chunk=128, page_size=16)
    params = FAMILY.seeded(wide)
    prompt = FAMILY.prompt_of(250, seed=9)
    (xla,), _, _ = FAMILY.serve(
        (wide, FAMILY.runtime(attention_impl="xla", **rt)), [(prompt, 12)], params=params)
    before = dict(KERNEL_TRACES)
    spy = Spy(monkeypatch)
    (out,), engine, _ = FAMILY.serve(
        (wide, FAMILY.runtime(attention_impl="pallas_interpret", **rt)), [(prompt, 12)],
        params=params, keep=True)
    assert (engine._attn_impl, engine._chunk_attn_impl) == ("pallas_interpret",) * 2
    for kernel in ("paged_decode", "chunk_attention"):
        assert KERNEL_TRACES[kernel, "interpreted"] > before.get((kernel, "interpreted"), 0)
    assert out == xla
    got = spy.of_request(prompt, out, 128)
    want = FAMILY.reference_logits(params, wide, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < LOGIT_TOL
