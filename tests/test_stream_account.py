"""A token's road from its dispatch's landing to the caller (ISSUE 52, as PR 50 built it).

- THE STAGE ACCOUNT of a request: ``engine.decode`` ends with the blocks it
  took, the landings of the first and the last one (the engine's own time,
  free of every consumer), the wait from a landing to the take, and its three
  stages ``block_wait_ms`` / ``emit_ms`` / ``backpressure_ms``; a slow
  consumer shows in the last and in the span's duration, never in the
  landings;
- ``agent.turn`` ends with its token events and their two stages
  (``step_build_ms``, ``publish_ms``), and a slow transport shows there;
- THE PROCESS TOTALS (``stream_*`` in ``EngineStats``) count with tracing
  off, and reach ``counters()`` and ``/metrics``;
- THE LOOP'S HEARTBEAT books a stall with no sync in it (``loop_stall_s``,
  ``loop_stalls``, a ``LOOP_STALL`` event on the request's timeline), and
  the block that waited for the loop says so (``deliver_wait_max_s``);
- every new synchronous site is a ``@hotpath`` root the linter holds.
"""

import asyncio
import sys
import time
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import (  # noqa: E402
    HEARTBEAT_S,
    InferenceEngine,
    StreamAccount,
)
from calfkit_tpu.observability import flightrec  # noqa: E402
from calfkit_tpu.observability.trace import TRACER, TraceContext, current_context  # noqa: E402

CFG = preset("debug", max_seq_len=256)
PROMPT = list(range(3, 23))
STREAM_COUNTERS = ("stream_blocks", "stream_events", "stream_deliver_wait_s",
                   "stream_emit_s", "stream_backpressure_s")
STAGES = ("block_wait_ms", "emit_ms", "backpressure_ms")


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


def _client(params, **over):
    from calfkit_tpu.inference import JaxLocalModelClient

    return JaxLocalModelClient(
        engine=InferenceEngine(CFG, _rt(**over), params=params), max_new_tokens=8)


async def _stream(model, trace_id, tokens, pause=0.0):
    """One traced request through the model client; the consumer sleeps
    ``pause`` at every text delta.  -> (deltas seen, the engine.decode span)."""
    from calfkit_tpu.engine.model_client import ModelSettings, TextDelta
    from calfkit_tpu.models.messages import ModelRequest, UserPart

    token = current_context.set(TraceContext(trace_id=trace_id, span_id="turn"))
    deltas = 0
    try:
        stream = model.request_stream(
            [ModelRequest(parts=[UserPart(content="hello there")])],
            ModelSettings(max_tokens=tokens))
        async for event in stream:
            if isinstance(event, TextDelta):
                deltas += 1
                if pause:
                    await asyncio.sleep(pause)
    finally:
        current_context.reset(token)
    spans = [s for s in TRACER.finished(trace_id) if s.name == "engine.decode"]
    return deltas, (spans[0] if spans else None)


class TestTheStageAccount:
    async def test_engine_decode_ends_with_the_account_and_it_closes(self, params):
        model = _client(params)
        try:
            await model.start()
            await _stream(model, "trace-50-warm", 8)  # the programs compile here
            _, span = await _stream(model, "trace-50-plain", 33)
        finally:
            await model.stop()
        a = span.attrs
        assert a["generated_tokens"] == 33 and "first_seq" in a and "last_seq" in a
        # 33 tokens at 4 a dispatch: the wave's first token, then 8 dispatches
        assert a["blocks"] == 9
        # an event at the first token and at every fourth after it, where the
        # text grew (the toy tokenizer's bytes do not always)
        assert 2 <= a["events"] <= 1 + 32 // 4
        # the first block landed BEFORE the span began (its first token began it)
        assert a["first_landed_ms"] < 0.0 < a["last_landed_ms"] <= span.duration_ms
        assert 0.0 < a["deliver_wait_max_ms"] <= a["deliver_wait_ms"]
        assert all(a[k] >= 0.0 for k in STAGES)
        # the three stages are the span's children in all but name
        own = span.duration_ms - sum(a[k] for k in STAGES)
        assert -0.01 <= own < 0.5 * span.duration_ms, (own, span.duration_ms, a)

    async def test_a_slow_consumer_is_backpressure_and_not_in_the_landings(self, params):
        """The consumer's time is in the span's duration (which is why
        ``mesh_stream_overhead_p95_ms``, the client's gap less THIS span,
        cancels what it was built to show) and not between the landings."""
        pause = 0.03
        model = _client(params)
        try:
            await model.start()
            await _stream(model, "trace-50-warm2", 8)
            deltas, span = await _stream(model, "trace-50-slow", 33, pause=pause)
        finally:
            await model.stop()
        a = span.attrs
        held_ms = a["events"] * pause * 1e3
        assert 2 <= a["events"] <= 9 and deltas >= a["events"]
        assert a["backpressure_ms"] >= 0.9 * held_ms
        assert span.duration_ms >= a["backpressure_ms"]
        # the engine went on decoding while its consumer slept: every block
        # had landed long before the consumer came for it
        landed_ms = a["last_landed_ms"] - a["first_landed_ms"]
        assert landed_ms < span.duration_ms - 0.5 * a["backpressure_ms"], a
        assert a["deliver_wait_max_ms"] > pause * 1e3  # a block waited for its consumer

    async def test_the_totals_count_with_tracing_off_and_no_span_is_built(self, params):
        from calfkit_tpu.observability.metrics import metrics_text

        model = _client(params)
        TRACER.set_enabled(False)
        try:
            await model.start()
            before = model._engine.stats.counters()
            deltas, span = await _stream(model, "trace-50-off", 17, pause=0.005)
            after = model._engine.stats.counters()
            model._engine._sync_metric_counters()
        finally:
            TRACER.set_enabled(True)
            await model.stop()
        assert span is None and not TRACER.finished("trace-50-off")
        grew = {k: after[k] - before[k] for k in STREAM_COUNTERS}
        assert grew["stream_blocks"] == 5 and 2 <= grew["stream_events"] <= 5, grew
        assert deltas >= grew["stream_events"]
        assert grew["stream_deliver_wait_s"] > 0.0 and grew["stream_emit_s"] > 0.0
        assert grew["stream_backpressure_s"] >= 0.9 * grew["stream_events"] * 0.005
        text = metrics_text()
        for name in ("calfkit_engine_stream_blocks_total", "calfkit_engine_stream_events_total",
                     "calfkit_engine_stream_deliver_wait_seconds_total",
                     "calfkit_engine_stream_emit_seconds_total",
                     "calfkit_engine_stream_backpressure_seconds_total",
                     "calfkit_engine_loop_stalls_total",
                     "calfkit_engine_loop_stall_seconds_total"):
            assert f"# TYPE {name} counter" in text, name
        # process totals: never on the heartbeat advert's window
        _, window = model._engine.stats.snapshot_and_delta()
        assert not [k for k in window if k.startswith(("stream_", "loop_"))]


class TestAStallWithNoSyncInIt:
    async def test_a_callback_that_holds_the_loop_is_booked_and_journaled(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        account = StreamAccount()
        loop = asyncio.get_running_loop()
        armed, held = [], []
        landed = engine._landed

        def landed_and_the_loop_is_taken(*args, **kwargs):
            # on the tick thread, at a landing: the loop is taken BEFORE the
            # fan-out that follows is handed to it, so this dispatch's block
            # waits out the callback whatever the machine's load (a callback
            # scheduled from the consumer may find the tick waiting for the
            # loop, and then nothing lands while it is held)
            if armed:
                armed.clear()
                held.append(True)
                loop.call_soon_threadsafe(time.sleep, 0.2)
            return landed(*args, **kwargs)

        engine._landed = landed_and_the_loop_is_taken
        await engine.start()
        try:
            async for _ in engine.generate(PROMPT, max_new_tokens=8):
                pass  # the programs compile here
            before = engine.stats.counters()
            n = 0
            async for _ in engine.generate(
                    PROMPT, max_new_tokens=48, corr="corr-50-stall", account=account):
                n += 1
                if n == 9:  # mid-stream: the next landing takes the loop
                    armed.append(True)
            after = engine.stats.counters()
        finally:
            await engine.stop()
        assert n == 48 and account.blocks == 1 + 47 // 4 + 1
        assert held == [True]
        # the dispatch that landed meanwhile waited for the loop with its block
        assert account.deliver_wait_max_s >= 0.1
        assert account.deliver_wait_s >= account.deliver_wait_max_s
        assert after["loop_stalls"] - before["loop_stalls"] >= 1
        assert after["loop_stall_s"] - before["loop_stall_s"] >= 0.15
        events = flightrec.parse_dump(engine._journal.dump_lines(reason="test"))
        stalls = [e for e in events if e["event"] == "LOOP_STALL"]
        assert stalls and max(e["a"] for e in stalls) >= 150, stalls
        # ``ck timeline`` shows it beside the request's dispatches
        names = [e["event"] for e in flightrec.timeline_events(events, "corr-50-stall")]
        assert "LOOP_STALL" in names and "DISPATCH_LAND" in names

    async def test_a_beat_on_time_books_nothing_and_the_beat_ends_with_the_engine(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await asyncio.sleep(2 * HEARTBEAT_S)  # the chain runs
            first = engine._beat
            # a beat on time (a loaded machine's loop may be late on its own:
            # the beat is called here with the moment it was due)
            before = engine.stats.counters()
            engine._beat.cancel()
            engine._heartbeat(time.perf_counter())
            quiet = engine.stats.counters()
            engine._beat.cancel()
            engine._heartbeat(time.perf_counter() - 3 * HEARTBEAT_S)
            late = engine.stats.counters()
        finally:
            await engine.stop()
        assert first is not None and engine._beat is not first
        assert quiet["loop_stalls"] == before["loop_stalls"]
        assert quiet["loop_stall_s"] == before["loop_stall_s"]
        assert late["loop_stalls"] == before["loop_stalls"] + 1
        assert late["loop_stall_s"] - before["loop_stall_s"] >= 3 * HEARTBEAT_S
        assert engine._beat.cancelled()


class _SlowMesh:
    """An in-memory mesh whose publish of a step record takes ``delay``."""

    def __new__(cls, delay):
        from calfkit_tpu import protocol
        from calfkit_tpu.mesh import InMemoryMesh

        class Slow(InMemoryMesh):
            steps_published = 0

            async def publish(self, topic, value, *, key=None, headers=None):
                if (headers or {}).get(protocol.HDR_WIRE) == "step" and b'"token"' in value:
                    Slow.steps_published += 1
                    await asyncio.sleep(delay)
                await super().publish(topic, value, key=key, headers=headers)

        return Slow()


class _FiveDeltas:
    model_name = "five"

    async def request(self, messages, settings=None, params=None):
        raise AssertionError("the token tap streams")

    async def request_stream(self, messages, settings=None, params=None):
        from calfkit_tpu.engine.model_client import ResponseDone, TextDelta
        from calfkit_tpu.models.messages import ModelResponse, TextOutput

        text = ""
        for i in range(5):
            chunk = f"a chunk of text long enough to flush, number {i}. "
            text += chunk
            yield TextDelta(chunk)
        yield ResponseDone(ModelResponse(parts=[TextOutput(text=text)]))


class TestTheAgentsSide:
    async def test_a_slow_transport_shows_in_agent_turn_s_publish_ms(self):
        from calfkit_tpu.client import Client
        from calfkit_tpu.nodes import Agent
        from calfkit_tpu.worker import Worker

        delay = 0.03
        mesh = _SlowMesh(delay)
        agent = Agent("slow_road", model=_FiveDeltas(), stream_tokens=True)
        async with Worker([agent], mesh=mesh, owns_transport=True):
            client = Client.connect(mesh)
            handle = await client.agent("slow_road").start("go", timeout=30)
            tokens = 0
            async for event in handle.stream():
                if getattr(getattr(event, "step", None), "kind", None) == "token":
                    tokens += 1
            await client.close()
        (turn,) = [s for s in TRACER.finished(handle.correlation_id) if s.name == "agent.turn"]
        a = turn.attrs
        assert a["token_events"] == type(mesh).steps_published == tokens == 5
        assert a["publish_ms"] >= 0.9 * 5 * delay * 1e3
        assert delay * 1e3 * 0.9 <= a["publish_max_ms"] <= a["publish_ms"]
        assert 0.0 < a["step_build_ms"] < a["publish_ms"]
        assert turn.duration_ms >= a["publish_ms"]


class TestTheNewSitesAreHeldByTheLinter:
    def test_the_loop_side_sites_are_hot_roots_and_the_tree_is_clean(self):
        scripts = str(Path(__file__).resolve().parents[1] / "scripts")
        if scripts not in sys.path:
            sys.path.insert(0, scripts)
        from meshlint import analyze, default_config

        report = analyze(default_config(Path(__file__).resolve().parents[1]))
        assert report.ok, report.render(chains=True)
        source = (Path(__file__).resolve().parents[1] / "calfkit_tpu" / "inference"
                  / "engine.py").read_text()
        for site in ("def _deliver_batch(", "def _heartbeat(", "def take("):
            at = source.index(site)
            assert "@hotpath" in source[at - 40:at], site


class TestTheLoopOnTheProfilersClock:
    """``devtrace.reduce_trace`` reads the loop-side stretches APART from
    the tick's phases: the phases stay exclusive and split every gap
    exactly, and ``gap_loop_s`` says what the event loop ran meanwhile."""

    def _capture(self):
        from tests.test_devtrace_queue import HOST, MODULES, OPS, US

        # the device idles 320-325 (queued), 335-392 and 393-400 (drained):
        # the loop delivers at 336-338, emits at 340-350 and 360-362, builds
        # a step at 362-365, encodes a Produce at 394-399, and emits once
        # more while the device is busy (410-420: no gap, nothing booked)
        loop = [("engine.deliver", 336 * US, 2 * US), ("engine.emit", 340 * US, 10 * US),
                ("engine.emit", 360 * US, 2 * US), ("node.publish", 362 * US, 3 * US),
                ("mesh.produce", 394 * US, 5 * US), ("engine.emit", 410 * US, 10 * US)]
        return OPS, MODULES, HOST, loop

    def test_the_phases_split_every_gap_as_they_did(self):
        from calfkit_tpu.observability import devtrace

        ops, modules, host, loop = self._capture()
        plain = devtrace.reduce_trace(ops, modules, host, 600e-6)
        both = devtrace.reduce_trace(ops, modules, host + loop, 600e-6)
        for key in ("gap_s", "gap_class_s", "gap_drained_s", "dispatches",
                    "gap_unattributed_pct"):
            assert both[key] == plain[key], key
        assert plain["gap_loop_s"] == {"drained": {}, "queued": {}}

    def test_gap_loop_s_names_what_the_loop_ran_in_each_class_of_gap(self):
        from calfkit_tpu.observability import devtrace

        ops, modules, host, loop = self._capture()
        out = devtrace.reduce_trace(ops, modules, host + loop, 600e-6)
        assert out["gap_loop_s"]["queued"] == {}
        assert out["gap_loop_s"]["drained"] == pytest.approx({
            "engine.emit": 12e-6, "mesh.produce": 5e-6, "node.publish": 3e-6,
            "engine.deliver": 2e-6})
        assert set(devtrace.LOOP_SIDE) == set(out["gap_loop_s"]["drained"])

    def test_annotate_is_the_profilers_where_jax_is_loaded_and_nothing_without(
            self, monkeypatch):
        from calfkit_tpu.observability import devtrace

        with devtrace.annotate("node.publish") as live:
            assert isinstance(live, jax.profiler.TraceAnnotation)
        monkeypatch.delitem(sys.modules, "jax")
        with devtrace.annotate("node.publish") as none:
            assert none is None
