"""A phase that outlives the capture, and the tick's side of a stall (ISSUE 52).

- THE LONG PHASE: a phase other than ``idle`` that outlasts what the two-deep
  device queue hides (a host phase one dispatch's wall time, a ``sync`` four,
  ``LONG_FLOOR_S`` under the EWMA) is booked whole where it closes
  (``phase_longs``, ``phase_long_s``) and journalled (``PHASE_LONG`` with the
  phase and the ``seq``); ``counters()`` read DURING it already holds it; a
  run of ordinary ticks books none;
- ``EngineStats.restamp()``: the open phase's annotation ended and begun again
  under the same name and ``seq``, the phase's seconds untouched; ``enter``
  racing it from two threads never ends an annotation twice nor loses the
  phase;
- ``devtrace`` joins the pieces again, so a split trace reads as the unsplit
  one; and over the benchmark's ``trace_reduce.attribute_gaps`` (imported, not
  edited) a gap whose covering phase ends AFTER the capture reads
  ``unattributed`` without the re-stamped piece and the phase's name with it.
"""

import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu.inference import engine as E  # noqa: E402
from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import (  # noqa: E402
    ENQUEUE, FANOUT, HANDOFF, IDLE, LONG_FLOOR_S, PREP, SYNC, SYNC_DISPATCHES, EngineStats,
    InferenceEngine,
)
from calfkit_tpu.observability import devtrace, flightrec  # noqa: E402

CFG = preset("debug", max_seq_len=256)
PROMPT = list(range(3, 23))


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params):
    return InferenceEngine(CFG, RuntimeConfig(
        max_batch_size=4, max_seq_len=256, prefill_chunk=16, decode_steps_per_dispatch=4,
        page_size=16, chunked_prefill=True, kv_layout="paged"), params=params)


async def _settled(engine, tokens=24):
    """The warm-up: every program the run will use is built here (twice: the
    second request finds the first one's prefix in the cache, another
    program; an ``enqueue`` that builds IS long).  The EWMA of the dispatch's
    wall time still holds the dispatches that compiled (seconds, a fifth less
    a dispatch after); a standing engine's is its steps' own."""
    for _ in range(2):
        async for _ in engine.generate(PROMPT, max_new_tokens=tokens):
            pass
    engine.stats.dispatch_ewma_ms = 5.0
    return engine.stats.counters()


def _longs(engine):
    events = flightrec.parse_dump(engine._journal.dump_lines(reason="test"))
    return [e for e in events if e["event"] == "PHASE_LONG"]


class TestTheLongPhase:
    @pytest.mark.parametrize("phase, hook, held", [
        ("sync", "_landed", SYNC_DISPATCHES * LONG_FLOOR_S + 0.15),  # before the switch to fanout
        ("prep", "_decode_args", LONG_FLOOR_S + 0.15),  # the dispatch's host inputs
    ])
    async def test_a_tick_held_past_the_bound_is_booked_where_it_closes(
            self, params, phase, hook, held):
        engine = _engine(params)
        inner = getattr(engine, hook)
        armed, during = [], []

        def hold(*args, **kwargs):
            if armed:
                armed.clear()
                # a reader on another thread, mid-stall: the open phase counts
                reader = threading.Timer(
                    held - 0.05, lambda: during.append(engine.stats.counters()))
                reader.start()
                time.sleep(held)
                reader.join()
            return inner(*args, **kwargs)

        setattr(engine, hook, hold)
        await engine.start()
        try:
            before = await _settled(engine)
            quiet = len(_longs(engine))
            n = 0
            async for _ in engine.generate(PROMPT, max_new_tokens=24, corr="corr-52-long"):
                n += 1
                if n == 9:
                    armed.append(True)
            after = engine.stats.counters()
        finally:
            await engine.stop()
        field = f"phase_{phase}_s"
        assert after["phase_longs"] - before["phase_longs"] == 1
        grew = after["phase_long_s"] - before["phase_long_s"]
        assert held <= grew < held + 0.1  # the WHOLE phase's seconds, and no other's
        assert after[field] - before[field] >= held
        # read during the stall: the open phase is long already, up to then
        (mid,) = during
        assert mid["phase_longs"] - before["phase_longs"] == 1
        assert held - 0.1 <= mid["phase_long_s"] - before["phase_long_s"] <= grew
        (event,) = _longs(engine)[quiet:]
        assert event["note"] == f"engine.{phase}" and event["a"] >= int(held * 1e3)
        # a sync's annotation carries the program it waits for; ``prep`` has none
        assert event["b"] > 0 if phase == "sync" else event["b"] == -1
        names = [e["event"] for e in flightrec.timeline_events(
            flightrec.parse_dump(engine._journal.dump_lines(reason="test")), "corr-52-long")]
        assert "PHASE_LONG" in names and "DISPATCH_LAND" in names

    async def test_a_run_of_ordinary_ticks_books_none(self, params):
        from calfkit_tpu.observability.metrics import metrics_text

        engine = _engine(params)
        await engine.start()
        try:
            before = await _settled(engine, 16)
            quiet = len(_longs(engine))
            async for _ in engine.generate(PROMPT, max_new_tokens=16):
                pass
            after = engine.stats.counters()
            engine._sync_metric_counters()
        finally:
            await engine.stop()
        assert after["decode_dispatches"] - before["decode_dispatches"] >= 4
        assert after["phase_longs"] == before["phase_longs"]
        assert after["phase_long_s"] == before["phase_long_s"]
        assert len(_longs(engine)) == quiet
        text = metrics_text()
        for name in ("calfkit_engine_phase_longs_total", "calfkit_engine_phase_long_seconds_total"):
            assert f"# TYPE {name} counter" in text, name
        assert all(isinstance(v, (int, float)) for k, v in after.items() if k != "occupancy_hist")
        _, window = engine.stats.snapshot_and_delta()  # never on the advert's window
        assert "phase_longs" not in window and "phase_long_s" not in window

    def test_the_bound_is_a_rule_over_the_dispatch_s_wall_time(self, monkeypatch):
        """A host phase is long past ONE dispatch's wall time, a sync past
        FOUR, the floor under an unprimed or a toy EWMA; ``idle`` never."""
        clock = [100.0]
        monkeypatch.setattr(E.time, "perf_counter", lambda: clock[0])
        stats = EngineStats()
        assert stats.long_after(PREP) == LONG_FLOOR_S
        assert stats.long_after(SYNC) == SYNC_DISPATCHES * LONG_FLOOR_S == 0.4
        stats.dispatch_ewma_ms = 177.0
        assert stats.long_after(HANDOFF) == pytest.approx(0.177)
        assert stats.long_after(SYNC) == pytest.approx(0.708)
        assert stats.long_after(IDLE) == float("inf")
        for phase, took in ((PREP, 0.170), (SYNC, 0.700), (HANDOFF, 0.180), (SYNC, 0.720),
                            (IDLE, 30.0), (FANOUT, 0.001)):
            stats.enter(phase, 7 if phase == SYNC else None)
            clock[0] += took
        stats.enter(None)
        assert stats.phase_longs == 2  # the handoff of 180 ms, the sync of 720
        assert stats.phase_long_s == pytest.approx(0.180 + 0.720)
        assert stats.phase_idle_s == pytest.approx(30.0)


class _Recorded:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that records what
    the profiler would: one piece an annotation that ENDED."""

    made: list = []

    def __init__(self, name, **metadata):
        self.name, self.seq = name, metadata.get("seq")
        self.entered = self.exited = 0
        _Recorded.made.append(self)

    def __enter__(self):
        self.entered += 1

    def __exit__(self, *exc):
        self.exited += 1


@pytest.fixture
def recorded(monkeypatch):
    _Recorded.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorded)
    return _Recorded.made


class TestRestamp:
    def test_one_closed_piece_and_one_open_under_the_same_name_and_seq(self, recorded):
        stats = EngineStats()
        began = stats.enter(SYNC, 41)
        time.sleep(0.02)
        stats.restamp()
        first, second = recorded
        assert (first.name, first.seq) == (second.name, second.seq) == ("engine.sync", 41)
        assert (first.entered, first.exited) == (1, 1)  # written, whatever comes after
        assert (second.entered, second.exited) == (1, 0)  # the phase is still open
        assert stats.phase_sync_s == 0.0 and stats._phase[:2] == (SYNC, began)
        ended = stats.enter(FANOUT)
        assert stats.phase_sync_s == pytest.approx(ended - began)  # the whole phase, once
        assert second.exited == 1 and len(recorded) == 3
        stats.enter(None)
        stats.restamp()  # no phase open: nothing to re-stamp
        assert len(recorded) == 3 and stats._phase is None

    def test_enter_racing_restamp_never_ends_an_annotation_twice(self, recorded):
        stats = EngineStats()
        switches, stop = 10_000, threading.Event()
        began = stats.enter(PREP)

        def beat():
            while not stop.is_set():
                stats.restamp()

        loop_side = threading.Thread(target=beat)
        loop_side.start()
        try:
            for i in range(switches):
                stats.enter(ENQUEUE if i % 2 == 0 else SYNC, i)
        finally:
            stop.set()
            loop_side.join()
        ended = stats.enter(None)
        assert all(a.entered == 1 and a.exited == 1 for a in recorded)
        assert len(recorded) > switches  # (the beat's pieces beside the phases')
        # no phase lost: the clock's seconds are the wall's, and in order
        total = stats.phase_prep_s + stats.phase_enqueue_s + stats.phase_sync_s
        assert total == pytest.approx(ended - began, rel=1e-6)
        phases = [(a.name, a.seq) for a in recorded]
        deduped = [p for i, p in enumerate(phases) if i == 0 or p != phases[i - 1]]
        assert deduped == [("engine.prep", None)] + [
            ("engine.enqueue" if i % 2 == 0 else "engine.sync", i) for i in range(switches)]

    async def test_the_heartbeat_re_stamps_a_phase_past_its_bound_and_no_other(
            self, params, recorded):
        """While the tick stands in a long ``sync`` the loop's beats end and
        begin its annotation again; a phase of ordinary length is one piece."""
        engine = _engine(params)
        inner, armed = engine._landed, []

        def hold(*args, **kwargs):
            if armed:
                armed.clear()
                time.sleep(SYNC_DISPATCHES * LONG_FLOOR_S + 0.2)
            return inner(*args, **kwargs)

        engine._landed = hold
        await engine.start()
        try:
            await _settled(engine)
            del recorded[:]
            n = 0
            async for _ in engine.generate(PROMPT, max_new_tokens=24):
                n += 1
                if n == 9:
                    armed.append(True)
        finally:
            await engine.stop()
        pieces: dict = {}
        for a in recorded:
            if a.name == "engine.sync":
                pieces[a.seq] = pieces.get(a.seq, 0) + 1
        split = {seq: n for seq, n in pieces.items() if n > 1}
        assert len(split) == 1 and len(pieces) >= 4, pieces
        # past four times the floor a piece a beat of 20 ms, for the 0.2 s left
        assert 3 <= next(iter(split.values())) <= 14
        assert all(a.entered == 1 and a.exited == 1 for a in recorded)

    def test_a_capture_re_stamps_the_open_phase_at_both_edges(self, recorded, monkeypatch):
        """``devtrace.capture`` (``GET /profile``): what the open phase held
        before the profiler stopped is written, and what it holds after the
        profiler started begins inside the capture."""
        calls = []
        monkeypatch.setattr(E, "restamp_all_engines", lambda: calls.append(time.perf_counter()))
        out = devtrace.capture(0.05)
        assert len(calls) == 2 and calls[1] - calls[0] >= 0.05
        assert out["captured"] in (True, False)


US = 1_000


class TestThePiecesAreJoinedAgain:
    def test_a_split_trace_reads_as_the_unsplit_one(self):
        from tests.test_devtrace_queue import HOST, MODULES, OPS, host

        # the sync on 9 (160-340) re-stamped at 250 and 300 by a beat on the
        # loop's thread (a microsecond between a piece's end and the next
        # one's start), the admit (350-370) at 360
        split = [h for h in HOST if h not in (host("sync", 160, 340, 9), host("admit", 350, 370))]
        split += [("engine.sync", 160 * US, 90 * US - 1, 9), ("engine.sync", 250 * US, 50 * US - 1, 9),
                  ("engine.sync", 300 * US, 40 * US, 9),
                  ("engine.admit", 350 * US, 10 * US - 1), ("engine.admit", 360 * US, 10 * US)]
        whole = devtrace.reduce_trace(OPS, MODULES, HOST, 600e-6)
        pieces = devtrace.reduce_trace(OPS, MODULES, split, 600e-6)
        for key in ("gap_class_s", "dispatches", "gap_drained_s", "gap_s"):
            assert pieces[key] == pytest.approx(whole[key]) if key != "dispatches" else (
                pieces[key] == whole[key]), key
        joined = devtrace.join_pieces(split)
        assert sorted(joined, key=lambda h: h[1]) == sorted(HOST, key=lambda h: h[1])
        assert devtrace.join_pieces(joined) == joined

    def test_two_phases_of_one_name_stay_two(self):
        """Two handoffs with a tick between them, two syncs on two programs,
        and the loop's stretches, which overlap one another: none is a piece."""
        host = [("engine.handoff", 0, 10 * US), ("engine.prep", 10 * US, 5 * US),
                ("engine.handoff", 15 * US, 10 * US), ("engine.sync", 25 * US, 10 * US, 3),
                ("engine.sync", 35 * US, 10 * US, 4), ("engine.emit", 0, 4 * US),
                ("engine.emit", 4 * US, 4 * US)]
        assert sorted(devtrace.join_pieces(host)) == sorted(host)
        # two neighbours with no ``seq`` a beat's re-stamp cannot explain stay apart
        apart = [("engine.idle", 0, 10 * US), ("engine.idle", 10 * US + 2 * devtrace.PIECE_GAP_NS, US)]
        assert devtrace.join_pieces(apart) == apart


class TestAGapThatReachesTheCapturesEnd:
    """The benchmark's own attribution (``trace_reduce.attribute_gaps``, as
    it stands): the device idles from 2.0 s to the capture's end at 5.0 s
    while the tick stands in a ``sync`` that ends at 5.4 s."""

    HOST = "/host:CPU"
    GAP = [(2_000_000_000, 5_000_000_000)]

    def _event(self, name, start_s, end_s):
        return (self.HOST, "python", name, int(start_s * 1e9), int((end_s - start_s) * 1e9), "")

    def test_without_the_re_stamp_it_has_no_name_and_with_it_the_phase_s(self):
        from benchmarks import trace_reduce

        before = [self._event("engine.fanout", 1.90, 1.95), self._event("engine.enqueue", 1.95, 1.99)]
        # the control, today's behaviour: the sync began at 1.99 and its end
        # found no capture running, so the profiler wrote nothing of it
        assert trace_reduce.attribute_gaps(self.GAP, before) == [
            [trace_reduce.UNATTRIBUTED, pytest.approx(3.0)]]
        # the heartbeat re-stamped it from 2.39 s on (four dispatches of 0.1 s
        # past its start), a beat every 20 ms, the last one 10 ms before the end
        beats = [1.99, 2.39] + [2.41 + 0.02 * i for i in range(130)]
        pieces = [self._event("engine.sync", a, b) for a, b in zip(beats, beats[1:])]
        (top, *rest) = trace_reduce.attribute_gaps(self.GAP, before + pieces)
        assert top == ["engine.sync", pytest.approx(beats[-1] - 2.0, abs=1e-6)]
        assert rest == [[trace_reduce.UNATTRIBUTED, pytest.approx(5.0 - beats[-1], abs=1e-6)]]
        assert rest[0][1] <= 0.021  # what is lost is less than a beat
