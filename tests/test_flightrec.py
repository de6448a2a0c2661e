"""ISSUE 4: the engine flight recorder.

Covers the ring journal itself (append/overflow/dump), the fault-dump
trigger (an exception crossing the dispatch loop must produce a parseable
JSONL dump AND still tear serving down cleanly — fail-open even when the
journal writer itself is broken), and the acceptance path: ``ck
timeline`` reconstructing a request end-to-end from a real debug-engine
dump with ≥ 6 distinct event types.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

from calfkit_tpu.inference.config import RuntimeConfig, preset
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.observability import flightrec
from calfkit_tpu.observability.flightrec import (
    EV_ADMIT,
    EV_DISPATCH_LAND,
    EV_DISPATCH_LAUNCH,
    EV_RETIRE,
    EV_SUBMIT,
    FlightRecorder,
)


class TestFlightRecorder:
    def test_append_and_order(self):
        fr = FlightRecorder(8)
        fr.append(EV_SUBMIT, "r1", -1, 17, 32)
        fr.append(EV_ADMIT, "r1", 3, 17, 0)
        events = fr.snapshot()
        assert [e[0] for e in events] == [0, 1]  # seq order
        assert events[0][2] == EV_SUBMIT and events[0][3] == "r1"
        assert fr.counts() == {"appended": 2, "dropped": 0, "dumped": 0}

    def test_capacity_rounds_to_power_of_two_and_overflow_counts(self):
        fr = FlightRecorder(10)
        assert fr.capacity == 16
        for _ in range(36):
            fr.append(EV_DISPATCH_LAUNCH, None, -1, 8, 4)
        counts = fr.counts()
        assert counts["appended"] == 36
        assert counts["dropped"] == 20  # overwritten, counted — not silent
        # the ring keeps the NEWEST events
        assert [e[0] for e in fr.snapshot()] == list(range(20, 36))

    def test_zero_capacity_disables(self):
        fr = FlightRecorder(0)
        fr.append(EV_SUBMIT, "r1")
        assert fr.snapshot() == []
        assert fr.counts() == {"appended": 0, "dropped": 0, "dumped": 0}
        assert fr not in flightrec.journals()

    def test_dump_is_parseable_jsonl(self, tmp_path):
        fr = FlightRecorder(8, label="debug")
        fr.append(EV_SUBMIT, "r1", -1, 17, 32)
        fr.append(EV_RETIRE, "r1", 2, 10, 0, "bye")
        path = fr.dump(reason="test", path=str(tmp_path / "d.jsonl"))
        lines = open(path).read().splitlines()
        meta = json.loads(lines[0])["flightrec"]
        assert meta["label"] == "debug" and meta["reason"] == "test"
        events = [json.loads(line) for line in lines[1:]]
        assert [e["event"] for e in events] == ["SUBMIT", "RETIRE"]
        assert events[1]["note"] == "bye"
        assert events[0]["t_s"] <= events[1]["t_s"]
        assert fr.counts()["dumped"] == 1

    def test_parse_dump_skips_garbage_and_meta(self):
        good = {"seq": 1, "t_s": 1.0, "event": "SUBMIT", "corr": "r",
                "slot": -1, "a": 0, "b": 0}
        events = flightrec.parse_dump(
            [json.dumps({"flightrec": {}}), "not json", "",
             json.dumps(good)]
        )
        assert [e["event"] for e in events] == ["SUBMIT"]

    def test_sigusr2_dumps_registered_journals(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path))
        fr = FlightRecorder(8, label="sig")
        fr.append(EV_SUBMIT, "r1")
        assert flightrec.install_sigusr2()
        os.kill(os.getpid(), signal.SIGUSR2)
        dumps = [p for p in os.listdir(tmp_path) if "sig" in p]
        assert dumps, "SIGUSR2 produced no dump"
        events = flightrec.parse_dump(
            open(tmp_path / dumps[0]).read().splitlines()
        )
        assert any(e["corr"] == "r1" for e in events)


class TestTimelineJoin:
    def _events(self):
        mk = lambda seq, ev, corr=None, slot=-1: {  # noqa: E731
            "seq": seq, "t_s": float(seq), "event": ev, "corr": corr,
            "slot": slot, "a": 0, "b": 0,
        }
        return [
            mk(0, "SUBMIT", "A"),
            mk(1, "SUBMIT", "B"),
            mk(2, "WAVE_FORM"),
            mk(3, "ADMIT", "A", slot=1),
            mk(4, "ADMIT", "B", slot=2),
            mk(5, "DISPATCH_LAUNCH"),
            mk(6, "RETIRE_DEFER", "A", slot=1),
            mk(7, "DISPATCH_LAND"),
            mk(8, "SLOT_FREE", slot=1),
            mk(9, "DISPATCH_LAUNCH"),  # past A's window
            mk(10, "SLOT_FREE", slot=2),
        ]

    def test_join_selects_own_batch_and_slot_events(self):
        timeline = flightrec.timeline_events(self._events(), "A")
        names = [e["event"] for e in timeline]
        # own events + batch events in window + the DEFERRED free past the
        # last own event (window extends to the slot's next SLOT_FREE)
        assert names == [
            "SUBMIT", "WAVE_FORM", "ADMIT", "DISPATCH_LAUNCH",
            "RETIRE_DEFER", "DISPATCH_LAND", "SLOT_FREE",
        ]
        # B's admission (another corr) and the post-window launch excluded
        assert all(e.get("corr") in (None, "A") for e in timeline)

    def test_unknown_corr_is_empty(self):
        assert flightrec.timeline_events(self._events(), "nope") == []

    def test_render_timeline(self):
        from calfkit_tpu.cli.obs import render_timeline

        timeline = flightrec.timeline_events(self._events(), "A")
        out = render_timeline(timeline, "A")
        assert "timeline A" in out
        assert "slot 1" in out
        assert "WAVE_FORM" in out and "(batch)" in out
        assert "SLOT_FREE" in out
        assert render_timeline([], "A") == "no events"


def _debug_engine(**overrides) -> InferenceEngine:
    rt = RuntimeConfig(
        max_batch_size=4, max_seq_len=256, kv_layout="paged",
        chunked_prefill=True, prefill_chunk=32, page_size=16,
        decode_steps_per_dispatch=4, **overrides,
    )
    return InferenceEngine(preset("debug"), rt)


class TestEngineTimelineAcceptance:
    async def test_timeline_reconstructs_request_end_to_end(self, tmp_path):
        """The ISSUE 4 acceptance bar: a dump from the REAL debug engine
        reconstructs one request with ≥ 6 distinct event types —
        admission, wave, page alloc, an overlap dispatch event,
        retirement, and the (deferred) free."""
        engine = _debug_engine()
        await engine.start()

        async def one(i: int) -> list[int]:
            out = []
            async for token in engine.generate(
                list(range(1, 20)), max_new_tokens=10, corr=f"req-{i}"
            ):
                out.append(token)
            return out

        outs = await asyncio.gather(*[one(i) for i in range(3)])
        assert all(len(o) == 10 for o in outs)
        path = engine._journal.dump(
            reason="test", path=str(tmp_path / "dump.jsonl")
        )
        await engine.stop()
        with open(path) as f:
            events = flightrec.parse_dump(f)
        timeline = flightrec.timeline_events(events, "req-1")
        kinds = {e["event"] for e in timeline}
        assert {"ADMIT", "WAVE_FORM", "PAGE_ALLOC"} <= kinds
        assert kinds & {"DISPATCH_LAUNCH", "DISPATCH_LAND", "SPEC_TICK"}
        assert kinds & {"RETIRE", "RETIRE_DEFER"}
        assert kinds & {"SLOT_FREE", "PAGE_FREE"}
        assert len(kinds) >= 6
        # the lifecycle reads in causal order: admission before dispatches
        # before the slot free
        names = [e["event"] for e in timeline]
        assert names.index("ADMIT") < names.index("DISPATCH_LAUNCH")
        assert names[-1] in ("SLOT_FREE", "PAGE_FREE", "DISPATCH_LAND")
        # and the CLI renders it
        from calfkit_tpu.cli.obs import render_timeline

        out = render_timeline(timeline, "req-1")
        assert "ADMIT" in out and "DISPATCH_LAUNCH" in out

    async def test_stats_snapshot_reports_flightrec_counts(self):
        from calfkit_tpu.inference.client import JaxLocalModelClient

        engine = _debug_engine()
        client = JaxLocalModelClient(engine=engine)
        # cold (engine built but idle) and live both carry the key set
        snap = client.stats_snapshot()
        assert snap["flightrec"] == {"appended": 0, "dropped": 0, "dumped": 0}
        await engine.start()
        async for _ in engine.generate([1, 2, 3], max_new_tokens=4):
            pass
        snap = client.stats_snapshot()
        assert snap["flightrec"]["appended"] > 0
        await engine.stop()

    async def test_flightrec_off_records_nothing(self):
        engine = _debug_engine(flightrec_events=0)
        await engine.start()
        async for _ in engine.generate([1, 2, 3], max_new_tokens=4):
            pass
        assert engine._journal.counts()["appended"] == 0
        await engine.stop()


class TestFaultDump:
    async def _run_to_fault(self, engine, tmp_path, monkeypatch) -> None:
        """Serve until the 3rd decode tick raises (so the dump holds real
        pre-fault dispatch events)."""
        monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path))
        # patch whichever dispatch lane is live: the ragged unified tick
        # (chunked + overlap, the default) or the legacy decode tick
        lane = "_ragged_tick" if engine._ragged else "_decode_tick"
        original = getattr(engine, lane)
        ticks = {"n": 0}

        def exploding_tick():
            ticks["n"] += 1
            if ticks["n"] >= 3:
                raise RuntimeError("injected dispatch fault")
            return original()

        setattr(engine, lane, exploding_tick)
        await engine.start()
        out = []
        async for token in engine.generate(
            list(range(1, 20)), max_new_tokens=64, corr="doomed"
        ):
            out.append(token)
        # the fault tore serving down mid-stream: the consumer got _DONE
        # (clean early end), not a hang and not an exception
        assert len(out) < 64

    async def test_fault_produces_parseable_dump_and_clean_teardown(
        self, tmp_path, monkeypatch
    ):
        engine = _debug_engine()
        await self._run_to_fault(engine, tmp_path, monkeypatch)
        dumps = os.listdir(tmp_path)
        assert len(dumps) == 1, f"expected one fault dump, got {dumps}"
        with open(tmp_path / dumps[0]) as f:
            lines = f.read().splitlines()
        meta = json.loads(lines[0])["flightrec"]
        assert meta["reason"] == "fault"
        events = flightrec.parse_dump(lines)
        kinds = [e["event"] for e in events]
        # the dump holds the faulting window: the request's admission,
        # the dispatches that ran before the injected fault, and the
        # FAULT event carrying the exception
        assert "ADMIT" in kinds and "DISPATCH_LAUNCH" in kinds
        assert kinds[-1] == "FAULT"
        fault = events[-1]
        assert "injected dispatch fault" in fault["note"]
        # teardown completed: scheduler task finished, stop() is clean
        assert engine._running is False
        await engine.stop()

    async def test_broken_journal_writer_never_masks_the_fault(
        self, tmp_path, monkeypatch
    ):
        """Fail-open: a dump writer that itself raises must not block
        teardown or hang consumers — the original fault stays the story."""
        engine = _debug_engine()

        def broken_dump(self, **kwargs):
            raise OSError("disk full")

        # class-level patch: FlightRecorder uses __slots__ (no instance
        # attribute shadowing); monkeypatch restores the method after
        monkeypatch.setattr(flightrec.FlightRecorder, "dump", broken_dump)
        await self._run_to_fault(engine, tmp_path, monkeypatch)
        assert os.listdir(tmp_path) == []  # nothing written...
        assert engine._running is False  # ...and teardown still completed
        await engine.stop()

    async def test_fault_dump_writes_into_env_dir(self, tmp_path, monkeypatch):
        # unset: one fixed directory inside the checkout, never the home
        monkeypatch.delenv("CALFKIT_FLIGHTREC_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert flightrec.default_dump_dir() == os.path.join(repo, ".flightrec")
        monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path / "sub"))
        assert flightrec.default_dump_dir() == str(tmp_path / "sub")
        fr = FlightRecorder(8, label="envdir")
        fr.append(EV_SUBMIT, "r")
        path = fr.dump(reason="manual")
        assert path.startswith(str(tmp_path / "sub"))
        assert os.path.exists(path)
