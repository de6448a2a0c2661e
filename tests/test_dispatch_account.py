"""The engine's account of the device's queue (ISSUE 36): every program it
enqueues has a number, every designated sync records the number it proved
complete, and ``starved_s``, ``pipeline_drains`` and the ``engine.dispatch``
span are read off those two integers.

(a) a wave's last chunk rides a dispatch: the wave's first tokens come down
    with that dispatch's landing (ISSUE 37), the next one queued behind it:
    no drain, nothing starved.  A wave onto an engine with no active rows
    has nothing to ride: its landing is a sync of its own, the device is
    empty from there to the next enqueue, and the account says so;
(b) a steady decode-only overlap: one program always queued, nothing starved;
(c) lockstep: every dispatch drained;
(d) ``programs()`` counts each jit key once, an engine its own.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, SpecConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import InferenceEngine, programs_of_all_engines  # noqa: E402
from calfkit_tpu.observability import flightrec  # noqa: E402
from calfkit_tpu.observability.trace import TRACER, TraceContext, current_context  # noqa: E402

CFG = preset("debug")
LONG = list(range(3, 23))  # 20 tokens: a bucket of 32, two chunks of 16
SHORT = list(range(3, 13))  # 10 tokens: one chunk, so it is its wave's last
ACCOUNT = ("starved_s", "pipeline_drains", "pipeline_drains_wave", "wave_landings_deferred",
           "decode_dispatches", "programs_built", "program_build_s")


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


async def _gen(engine, prompt, n):
    return [t async for t in engine.generate(prompt, max_new_tokens=n)]


def _account(engine) -> dict:
    counters = engine.stats.counters()
    return {f: counters[f] for f in ACCOUNT}


def _grew(before: dict, after: dict) -> dict:
    return {f: after[f] - before[f] for f in ACCOUNT}


def _dispatch_spans() -> list:
    spans = [s for s in TRACER.finished() if s.name == "engine.dispatch"]
    return sorted(spans, key=lambda s: s.attrs["seq"])


async def _until(condition) -> None:
    while not condition():
        await asyncio.sleep(0.001)


class TestAWaveRidesADispatch:
    async def test_the_landing_rides_the_dispatch_and_nothing_drains(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            # every program of the scene, once: compiles stay outside
            warm = asyncio.ensure_future(_gen(engine, LONG, 24))
            await _until(lambda: engine._active)
            await _gen(engine, SHORT, 4)
            await warm
            first = asyncio.ensure_future(_gen(engine, LONG, 64))
            await _until(lambda: engine._active and engine.stats.decode_dispatches
                         and engine._pend is not None)
            TRACER.clear()
            before = _account(engine)
            await _gen(engine, SHORT, 4)  # its one chunk rides a dispatch of the first's
            after = _account(engine)
            await first
        finally:
            await engine.stop()
        grew = _grew(before, after)
        # the wave's first tokens came down with the dispatch that carried its
        # last chunk, the next dispatch queued behind it: no drain, nothing starved
        assert grew["wave_landings_deferred"] == 1 and grew["pipeline_drains_wave"] == 0
        assert grew["pipeline_drains"] == 0 and grew["starved_s"] == 0.0
        assert grew["programs_built"] == 0
        spans = _dispatch_spans()
        (rode,) = [s for s in spans if s.attrs["wave_landed"]]
        assert rode.attrs["kind"] == "ragged" and rode.attrs["chunk_rows"] == 1
        assert rode.attrs["chunk_tokens"] == 16
        # its own landing proved it, by the sync on the finalize program behind it
        assert rode.attrs["proved_by"] == rode.attrs["seq"] + 1
        after_it = next(s for s in spans if s.attrs["seq"] > rode.attrs["seq"])
        assert after_it.attrs["queued_behind"] >= 1
        # ... and was enqueued BEFORE that landing: the device had it to go on with
        assert after_it.start_s < rode.start_s + rode.duration_ms / 1e3
        assert all(s.attrs["queued_behind"] >= 1 for s in spans if s.attrs["seq"] > spans[0].attrs["seq"])

    async def test_every_dispatch_is_proved_in_order_and_once(self, params):
        """One wave lands on an engine with no active rows (a sync of its
        own: a drain), the next rides a dispatch (deferred: none)."""
        engine = InferenceEngine(CFG, _rt(), params=params)
        TRACER.clear()
        await engine.start()
        try:
            first = asyncio.ensure_future(_gen(engine, LONG, 48))
            await _until(lambda: engine._active)
            await _gen(engine, SHORT, 4)
            await first
        finally:
            await engine.stop()
        spans = _dispatch_spans()
        ends = [s.attrs["proved_by"] for s in spans]
        assert ends == sorted(ends)  # proved in order, each once
        assert len({s.attrs["seq"] for s in spans}) == len(spans)
        counters = engine.stats.counters()
        assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (1, 1)
        assert sum(s.attrs["wave_landed"] for s in spans) == 1  # the one that rode
        assert engine._done_seq == engine._enq_seq and not engine._unproved

    async def test_a_wave_onto_an_engine_with_no_active_rows_still_drains_once(self, params):
        """Nothing to ride: the landing stays a sync of its own, the device
        is empty from there to the first decode dispatch, and the account
        says so."""
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await _gen(engine, LONG, 8)  # compiles
            TRACER.clear()
            before = _account(engine)
            await _gen(engine, LONG, 8)
            after = _account(engine)
        finally:
            await engine.stop()
        grew = _grew(before, after)
        assert (grew["pipeline_drains_wave"], grew["wave_landings_deferred"]) == (1, 0)
        assert grew["starved_s"] > 0.0
        spans = _dispatch_spans()
        assert spans[0].attrs["queued_behind"] == 0 and not any(
            s.attrs["wave_landed"] for s in spans)

    async def test_a_chunk_in_its_own_invocation_rides_the_decode_dispatch_before_it(self, params):
        """The token budget refuses the fused launch: the chunk and the
        finalize program follow a plain decode dispatch, and the wave's
        landing rides THAT."""
        engine = InferenceEngine(CFG, _rt(ragged_token_budget=8), params=params)
        TRACER.clear()
        await engine.start()
        try:
            first = asyncio.ensure_future(_gen(engine, LONG, 48))
            await _until(lambda: engine._active and engine.stats.decode_dispatches
                         and engine._pend is not None)
            assert len(await _gen(engine, SHORT, 6)) == 6
            await first
        finally:
            await engine.stop()
        counters = engine.stats.counters()
        assert counters["unified_dispatches"] == 0
        assert (counters["pipeline_drains_wave"], counters["wave_landings_deferred"]) == (1, 1)
        (rode,) = [s for s in _dispatch_spans() if s.attrs["wave_landed"]]
        # the chunk and the finalize program behind the decode dispatch
        assert rode.attrs["kind"] == "decode" and rode.attrs["proved_by"] == rode.attrs["seq"] + 2


class TestSteadyOverlap:
    async def test_one_program_stays_queued_and_nothing_starves(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await _gen(engine, LONG, 8)  # compiles
            TRACER.clear()
            run = asyncio.ensure_future(_gen(engine, LONG, 80))
            await _until(lambda: engine.stats.decode_dispatches >= 4 and engine._pend is not None)
            before = _account(engine)
            await run
            after = _account(engine)
        finally:
            await engine.stop()
        grew = _grew(before, after)
        assert grew["decode_dispatches"] >= 10
        assert grew["starved_s"] == 0.0
        assert grew["pipeline_drains_wave"] == 0
        steady = [s for s in _dispatch_spans() if s.attrs["kind"] == "decode"][1:]
        assert len(steady) >= 10
        assert {s.attrs["queued_behind"] for s in steady} == {1}
        assert all(s.attrs["proved_by"] == s.attrs["seq"] for s in steady)
        assert all(s.attrs["exclusive_ms"] <= s.duration_ms + 1e-6 for s in steady)

    async def test_a_span_carries_what_the_issue_names(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            TRACER.clear()
            await _gen(engine, LONG, 12)
        finally:
            await engine.stop()
        spans = _dispatch_spans()
        assert spans and all(s.parent_span_id is None and s.kind == "engine" for s in spans)
        for s in spans:
            assert set(s.attrs) == {
                "seq", "kind", "steps", "rows", "chunk_rows", "chunk_tokens", "wave_landed",
                "first_use", "build_ms", "queued_behind", "enqueue_ms", "proved_by", "wait_ms",
                "exclusive_ms"}
            assert all(isinstance(v, (int, float, str)) for v in s.attrs.values())
        built = [s for s in spans if s.attrs["first_use"]]
        assert built and all(s.attrs["build_ms"] > 0 for s in built)
        assert all(s.attrs["build_ms"] == 0 for s in spans if not s.attrs["first_use"])

    async def test_a_disabled_tracer_ends_no_span_and_keeps_the_account(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        TRACER.clear()
        TRACER.set_enabled(False)
        try:
            await engine.start()
            await _gen(engine, LONG, 12)
        finally:
            TRACER.set_enabled(True)
            await engine.stop()
        assert not _dispatch_spans()
        assert engine._done_seq == engine._enq_seq > 0 and not engine._unproved
        assert engine.stats.pipeline_drains >= 1


LOCKSTEP = {
    "lockstep": dict(overlap_dispatch=False),
    "speculative": dict(speculative=SpecConfig(k=2), kv_layout="dense"),
}


class TestLockstep:
    @pytest.mark.parametrize("lane", sorted(LOCKSTEP))
    async def test_every_dispatch_is_drained(self, params, lane):
        engine = InferenceEngine(CFG, _rt(**LOCKSTEP[lane]), params=params)
        await engine.start()
        try:
            await _gen(engine, LONG, 8)  # compiles
            TRACER.clear()
            before = _account(engine)
            await _gen(engine, LONG, 40)
            after = _account(engine)
        finally:
            await engine.stop()
        grew = _grew(before, after)
        assert grew["decode_dispatches"] >= 5
        assert grew["pipeline_drains"] - grew["pipeline_drains_wave"] == grew["decode_dispatches"]
        assert grew["starved_s"] > 0.0
        spans = _dispatch_spans()
        assert len(spans) == grew["decode_dispatches"]
        assert {s.attrs["queued_behind"] for s in spans} == {0}
        assert {s.attrs["kind"] for s in spans} == (
            {"verify"} if lane == "speculative" else {"decode"})


class TestPrograms:
    async def test_each_key_counts_once_and_an_engine_counts_its_own(self, params):
        tables = []
        for _ in range(2):  # the same shapes twice: the second engine builds its own
            engine = InferenceEngine(CFG, _rt(), params=params)
            await engine.start()
            try:
                first = asyncio.ensure_future(_gen(engine, LONG, 32))
                await _until(lambda: engine._active)
                await _gen(engine, SHORT, 4)
                await first
                await _gen(engine, LONG, 8)  # again: nothing new to build
            finally:
                await engine.stop()
            table = engine.programs()
            used = [p for p in table if p["uses"]]
            assert len({(p["family"], *p["key"]) for p in table}) == len(table)
            # a key builds once, and once more where it meets arguments of another
            # kind (a fresh scratch is an uncommitted array, a carried one is not)
            assert engine.stats.programs_built == sum(p["builds"] for p in table) >= len(used) > 0
            assert engine.stats.program_build_s == pytest.approx(sum(p["build_s"] for p in used))
            assert all(p["build_s"] > 0 and 1 <= p["first_seq"] <= p["built_seq"] <= engine._enq_seq
                       for p in used)
            assert all(p["builds"] >= 1 for p in used)
            assert sum(p["uses"] for p in table) == engine._enq_seq
            assert {"decode", "ragged", "chunk", "finalize"} <= {p["family"] for p in used}
            tables.append(table)
            mine = [t for t in programs_of_all_engines() if t["programs"] == table]
            assert mine and mine[0]["enqueued"] == mine[0]["proved"] == engine._enq_seq
        assert [(p["family"], p["key"]) for p in tables[0]] == [
            (p["family"], p["key"]) for p in tables[1]]

    async def test_programs_over_http(self, params):
        import json

        from calfkit_tpu.observability.http import MetricsServer

        engine = InferenceEngine(CFG, _rt(), params=params)
        await engine.start()
        try:
            await _gen(engine, LONG, 6)
            async with MetricsServer() as server:
                reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
                writer.write(b"GET /programs HTTP/1.0\r\n\r\n")
                await writer.drain()
                raw = await reader.read()
                writer.close()
        finally:
            await engine.stop()
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"200 OK" in head
        rows = [r for t in json.loads(body) if t["programs"] == engine.programs()
                for r in t["programs"]]
        assert {"family", "key", "builds", "build_s", "first_seq", "built_seq", "uses"} == set(
            rows[0])
        assert all((r["builds"] >= 1) == bool(r["uses"]) for r in rows)

    def test_a_program_is_still_the_jitted_function(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        args, window, steps, sampled = engine._decode_args()
        program = engine._decode_jit(window, steps, sampled)
        assert program is engine._decode_jit(window, steps, sampled)
        assert program.lower(*args) is not None  # what the compile tests call
        assert program.uses == 0 and engine._enq_seq == 0 and program.builds == 0


class TestTheSameNumberEverywhere:
    async def test_the_journal_and_the_request_span_carry_it(self, params):
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.inference import JaxLocalModelClient
        from calfkit_tpu.models.messages import ModelRequest, UserPart

        model = JaxLocalModelClient(
            config=preset("debug", max_seq_len=256),
            runtime=_rt(max_batch_size=2), max_new_tokens=24,
        )
        TRACER.clear()
        token = current_context.set(TraceContext(trace_id="trace-36-client", span_id="turn"))
        try:
            await model.start()
            await model.request(
                [ModelRequest(parts=[UserPart(content="hello there")])],
                ModelSettings(max_tokens=24),
            )
            events = model._engine._journal.snapshot()
        finally:
            current_context.reset(token)
            await model.stop()
        spans = _dispatch_spans()
        launched = [e[4] for e in events if e[2] == flightrec.EV_DISPATCH_LAUNCH]
        landed = [e[4] for e in events if e[2] == flightrec.EV_DISPATCH_LAND]
        assert launched == [s.attrs["seq"] for s in spans] == landed
        mine = {s.name: s for s in TRACER.finished("trace-36-client")}
        decode, generate = mine["engine.decode"], mine["engine.generate"]
        first, last = decode.attrs["first_seq"], decode.attrs["last_seq"]
        # the wave's landing was proved when the first token came; every
        # dispatch after it, to the last one, carried this request's decode
        assert first < spans[0].attrs["seq"] and last == spans[-1].attrs["proved_by"]
        assert decode.attrs["generated_tokens"] == 24
        # the span ends where the stream ended, before the stream was closed
        assert decode.start_s + decode.duration_ms / 1e3 <= (
            generate.start_s + generate.duration_ms / 1e3)
        by_dispatches = sum(s.duration_ms for s in spans[1:])
        assert decode.duration_ms >= 0.5 * by_dispatches
