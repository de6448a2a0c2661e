"""The engine's dispatch loop on the record (ISSUE 24).

- THE PHASE CLOCK is exclusive: between two snapshots of
  ``EngineStats.counters()`` the eight ``phase_*_s`` counters grow by the
  wall time between them, on every scheduler lane;
- ``starved_s`` is the sum of what ``dispatch_gap_ms`` observed;
- THE ADMISSION LEDGER names what held the head of the queue (no slot, no
  pages, a wave in flight), its four reasons add up to the time a request
  was queued, and ``empty_slot_queued_s`` integrates the free slots
  meanwhile;
- ``engine.queue`` is a span per traced request, from submit to the slot
  grant, under the caller's context;
- the new counters reach ``/metrics`` and ``counters()``, never the
  heartbeat advert's window.
"""

import asyncio
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, SpecConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import (  # noqa: E402
    BLOCKED,
    PHASES,
    EngineStats,
    InferenceEngine,
)
from calfkit_tpu.observability.trace import TRACER, TraceContext, current_context  # noqa: E402

CFG = preset("debug")
PROMPT = list(range(3, 23))


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _rt(**over):
    kw = dict(
        max_batch_size=4, max_seq_len=256, prefill_chunk=16,
        decode_steps_per_dispatch=4, page_size=16, chunked_prefill=True,
        kv_layout="paged",
    )
    kw.update(over)
    return RuntimeConfig(**kw)


async def _gen(engine, prompt, n, **kw):
    return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]


def _grew(before: dict, after: dict, fields) -> dict:
    return {f: after[f] - before[f] for f in fields}


async def _queue_behind(engine, n_first: int, n_second: int):
    """One request admitted and decoding, a second submitted behind it;
    returns the second's GenRequest once both have finished."""
    await _gen(engine, PROMPT, 8)  # compiles, and leaves the ledger closed
    first = asyncio.ensure_future(_gen(engine, PROMPT, n_first))
    while not engine._active:
        await asyncio.sleep(0.001)
    second = asyncio.ensure_future(_gen(engine, PROMPT, n_second))
    while not engine._pending and not engine._carry:
        await asyncio.sleep(0)
    waiting = (engine._carry or engine._pending)[0]
    await asyncio.gather(first, second)
    return waiting


LANES = {
    "ragged": dict(),
    "chunked": dict(ragged_waves=False),
    "single-shot": dict(chunked_prefill=False, kv_layout="dense"),
    "lockstep": dict(overlap_dispatch=False, kv_layout="dense"),
    "speculative": dict(speculative=SpecConfig(k=2), kv_layout="dense"),
}


class TestPhaseClock:
    @pytest.mark.parametrize("lane", sorted(LANES))
    async def test_phases_sum_to_wall_time(self, params, lane):
        engine = InferenceEngine(CFG, _rt(**LANES[lane]), params=params)
        await engine.start()
        try:
            await _gen(engine, PROMPT, 4)  # compiles: outside the interval
            before, t0 = engine.stats.counters(), time.perf_counter()
            await asyncio.gather(*[_gen(engine, PROMPT, 40) for _ in range(6)])
            await asyncio.sleep(0.05)  # and some idling
            after, t1 = engine.stats.counters(), time.perf_counter()
        finally:
            await engine.stop()
        grew = _grew(before, after, PHASES)
        assert all(v >= 0.0 for v in grew.values())
        assert sum(grew.values()) == pytest.approx(t1 - t0, rel=0.02)
        # the loop did every kind of work, and waited for some
        for phase in ("phase_admit_s", "phase_prep_s", "phase_enqueue_s",
                      "phase_sync_s", "phase_fanout_s", "phase_handoff_s",
                      "phase_idle_s"):
            assert grew[phase] > 0.0, phase

    async def test_stopped_loop_has_closed_its_phase(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        await _gen(engine, PROMPT, 4)
        await engine.stop()
        assert engine.stats._phase is None and engine.stats._blocked is None
        a = engine.stats.counters()
        await asyncio.sleep(0.02)
        assert engine.stats.counters() == a  # nothing left open to grow

    async def test_starved_is_the_sum_of_the_dispatch_gaps(self, params):
        engine = InferenceEngine(CFG, _rt(overlap_dispatch=False), params=params)
        await engine.start()
        try:
            await asyncio.gather(*[_gen(engine, PROMPT, 24) for _ in range(3)])
        finally:
            await engine.stop()
        gaps = engine.latency["dispatch_gap_ms"]
        assert gaps._count > 0 and engine.stats.starved_s > 0.0
        assert engine.stats.starved_s * 1e3 == pytest.approx(gaps._sum, rel=1e-6)

    def test_every_counter_is_a_plain_number(self):
        stats = EngineStats()
        stats.enter("phase_sync_s")
        stats.note_blocked("blocked_pages_s", 3, time.perf_counter())
        counters = stats.counters()
        assert set(PHASES) | set(BLOCKED) | {"starved_s", "empty_slot_queued_s"} <= set(counters)
        for key, value in counters.items():
            if key != "occupancy_hist":
                assert type(value) in (int, float), key
        stats.enter(None)

    def test_counters_include_the_open_intervals(self):
        stats = EngineStats()
        now = stats.enter("phase_sync_s")
        stats.note_blocked("blocked_slots_s", 2, now)
        time.sleep(0.02)
        seen = stats.counters()
        assert seen["phase_sync_s"] >= 0.02 and stats.phase_sync_s == 0.0
        assert seen["blocked_slots_s"] >= 0.02
        assert seen["empty_slot_queued_s"] == pytest.approx(2 * seen["blocked_slots_s"], rel=0.05)
        later = stats.enter("phase_fanout_s")
        assert stats.phase_sync_s == pytest.approx(later - now)
        assert stats.enter("phase_fanout_s") >= later  # the same phase: no switch
        assert stats._phase[1] == later
        stats.enter(None)


class TestAdmissionLedger:
    async def test_no_free_slot(self, params):
        engine = InferenceEngine(CFG, _rt(max_batch_size=1), params=params)
        await engine.start()
        try:
            waiting = await _queue_behind(engine, 120, 4)
        finally:
            await engine.stop()
        s = engine.stats
        queued = waiting.granted_at - waiting.started_at
        assert s.blocked_slots_s == pytest.approx(queued, rel=0.05)
        assert s.blocked_pages_s == s.blocked_wave_s == s.blocked_budget_s == 0.0
        # the four reasons add up to the time the queue was not empty
        assert sum(getattr(s, f) for f in BLOCKED) == pytest.approx(queued, rel=0.05)
        assert s.empty_slot_queued_s == 0.0  # no slot stood free meanwhile

    async def test_too_few_pages(self, params):
        # 12 usable pages: one request of 20 + 150 tokens reserves 11
        engine = InferenceEngine(CFG, _rt(num_kv_pages=13, prefix_cache=False), params=params)
        await engine.start()
        try:
            waiting = await _queue_behind(engine, 150, 150)
        finally:
            await engine.stop()
        s = engine.stats
        queued = waiting.granted_at - waiting.started_at
        assert s.alloc_stalls > 0
        assert s.blocked_pages_s == pytest.approx(queued, rel=0.05)
        assert s.blocked_slots_s == 0.0
        # three of the four slots stood free all the while: by hand, 3 x queued
        assert s.empty_slot_queued_s == pytest.approx(3 * queued, rel=0.05)

    async def test_wave_in_flight(self, params):
        # one-row waves of three chunks each: the second request finds the
        # first one's wave in flight, slots and pages to spare
        engine = InferenceEngine(CFG, _rt(max_prefill_wave=1), params=params)
        long_prompt = list(range(3, 3 + 40))
        await engine.start()
        try:
            await asyncio.gather(*[_gen(engine, long_prompt, 4) for _ in range(3)])
        finally:
            await engine.stop()
        s = engine.stats
        assert s.blocked_wave_s > 0.0
        assert s.blocked_slots_s == s.blocked_pages_s == 0.0
        assert s.empty_slot_queued_s > 0.0

    async def test_new_counters_stay_out_of_the_advert_window(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await _gen(engine, PROMPT, 8)
        finally:
            await engine.stop()
        cumulative, window = engine.stats.snapshot_and_delta()
        assert cumulative["phase_sync_s"] > 0.0
        assert not [k for k in window if k.startswith(("phase_", "blocked_"))]
        assert "starved_s" not in window and "empty_slot_queued_s" not in window
        assert window["decode_dispatches"] > 0

    async def test_new_counters_reach_the_metrics_text(self, params):
        from calfkit_tpu.observability.metrics import metrics_text

        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await _gen(engine, PROMPT, 12)
        finally:
            await engine.stop()
        text = metrics_text()
        for name in ("calfkit_engine_phase_sync_seconds_total",
                     "calfkit_engine_starved_seconds_total",
                     "calfkit_engine_blocked_pages_seconds_total",
                     "calfkit_engine_empty_slot_queued_seconds_total"):
            assert f"# TYPE {name} counter" in text, name
        value = next(float(line.split()[1]) for line in text.splitlines()
                     if line.startswith("calfkit_engine_phase_sync_seconds_total "))
        assert value > 0.0


class TestQueueSpan:
    async def test_span_from_submit_to_slot_grant(self, params):
        engine = InferenceEngine(CFG, _rt(max_batch_size=1), params=params)
        parent = TraceContext(trace_id="trace-24", span_id="prefill-span")
        await engine.start()
        try:
            first = asyncio.ensure_future(_gen(engine, PROMPT, 60))
            while not engine._active:
                await asyncio.sleep(0.001)
            first_token_at = None
            async for _ in engine.generate(PROMPT, max_new_tokens=4, trace=parent):
                first_token_at = first_token_at or time.time()
            await first
        finally:
            await engine.stop()
        (span,) = [s for s in TRACER.finished("trace-24") if s.name == "engine.queue"]
        assert span.parent_span_id == "prefill-span" and span.kind == "engine"
        assert span.attrs == {"blocked_on": "slots", "bucket": 32, "wave_rows": 1}
        assert span.duration_ms > 1.0  # it waited for the first request
        assert span.start_s + span.duration_ms / 1e3 <= first_token_at

    async def test_untraced_request_pays_no_span(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        TRACER.clear()
        await engine.start()
        try:
            await _gen(engine, PROMPT, 4)
        finally:
            await engine.stop()
        assert not [s for s in TRACER.finished() if s.name == "engine.queue"]

    async def test_request_abandoned_in_the_queue_ends_cancelled(self, params):
        engine = InferenceEngine(CFG, _rt(max_batch_size=1), params=params)
        parent = TraceContext(trace_id="trace-24-gone", span_id="p")
        await engine.start()
        try:
            first = asyncio.ensure_future(_gen(engine, PROMPT, 40))
            while not engine._active:
                await asyncio.sleep(0.001)
            stream = engine.generate(PROMPT, max_new_tokens=4, trace=parent)
            waiter = asyncio.ensure_future(stream.__anext__())
            await asyncio.sleep(0.01)
            waiter.cancel()
            with pytest.raises(asyncio.CancelledError):
                await waiter
            await stream.aclose()
            await first
        finally:
            await engine.stop()
        (span,) = [s for s in TRACER.finished("trace-24-gone") if s.name == "engine.queue"]
        assert span.status == "cancelled" and "bucket" not in span.attrs

    async def test_model_client_parents_it_under_engine_prefill(self):
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.inference import JaxLocalModelClient
        from calfkit_tpu.models.messages import ModelRequest, UserPart

        model = JaxLocalModelClient(
            config=preset("debug", max_seq_len=256),
            runtime=_rt(max_batch_size=2), max_new_tokens=6,
        )
        token = current_context.set(TraceContext(trace_id="trace-24-client", span_id="turn"))
        try:
            await model.start()
            await model.request(
                [ModelRequest(parts=[UserPart(content="hello there")])],
                ModelSettings(max_tokens=6),
            )
        finally:
            current_context.reset(token)
            await model.stop()
        spans = {s.name: s for s in TRACER.finished("trace-24-client")}
        queue, prefill = spans["engine.queue"], spans["engine.prefill"]
        assert queue.parent_span_id == prefill.span_id
        assert prefill.parent_span_id == spans["engine.generate"].span_id
        assert queue.duration_ms <= prefill.duration_ms
        assert prefill.attrs["ttft_ms"] > 0


class TestScopesOnTheDevice:
    def test_jit_bodies_carry_their_scopes(self, params):
        """In the lowered text a scan body is a function of its own, so an
        operation inside the layer loop shows its path from there on."""
        import re

        def scopes(lowered) -> set:
            paths = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
            return {part for path in paths for part in path.split("/")}

        engine = InferenceEngine(CFG, _rt(), params=params)
        args, window, steps, sampled = engine._decode_args()
        decode = scopes(engine._decode_jit(window, steps, sampled).lower(*args))
        assert {"decode_loop", "gather_window", "qkv", "attention", "attn_out", "mlp",
                "lm_head", "sample", "kv_write"} <= decode
        assert "chunk_loop" not in decode
        shape = (CFG.n_layers, 1, CFG.n_kv_heads, 32, CFG.head_dim)
        scratch = jnp.zeros(shape, engine._k.dtype)
        chunk = scopes(engine._chunk_jit(16, 1).lower(
            engine.params, scratch, scratch, jnp.zeros((1, 16), jnp.int32), jnp.int32(0)))
        assert {"chunk_loop", "qkv", "kv_write", "attention", "attn_out", "mlp",
                "lm_head"} <= chunk
        assert "decode_loop" not in chunk and "gather_window" not in chunk
