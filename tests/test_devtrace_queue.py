"""``devtrace`` on the device's queue (ISSUE 36): the ``seq`` that the
engine's ``enqueue`` and ``sync`` annotations carry joins the host's clock
to the device's module runs, an idle gap is ``queued`` or ``drained`` by
what stood on the queue when it began, and ``dispatches`` is one row a
numbered program run.  Synthetic tuples for the arithmetic, a capture on the
CPU for what the profiler does with an annotation's metadata."""

import re

import pytest

from calfkit_tpu.observability import devtrace

TPU0 = "/device:TPU:0"
US = 1_000  # ns


def run(name, start_us, dur_us):
    """A program run: its module, and one operation that fills it."""
    return ((TPU0, f"jit_{name}(1)", start_us * US, dur_us * US),
            (TPU0, "%fusion.1", "decode_loop/mlp", start_us * US, dur_us * US))


def host(name, start_us, end_us, seq=None):
    return (f"engine.{name}", start_us * US, (end_us - start_us) * US,
            *(() if seq is None else (seq,)))


# program 7 runs, 8 (a wave's last chunk rides it) waits behind it, the
# landing 9 was enqueued with 8 and starts 5 us after it: launch latency.
# The host waits for 9, fans out, admits, hops, prepares, and only then
# enqueues 10: the device stood empty for 65 us, but for one of them.
RUNS = [run("decode", 100, 100), run("ragged_paged", 200, 120), run("finalize", 325, 10),
        run("decode", 400, 100)]
# (an eager operation of the host's, a fresh scratch's zeros, runs at 392: it
# splits the idle in front of 10 in two, and is no program of the engine's)
EAGER = ((TPU0, "jit_broadcast_in_dim(3)", 392 * US, 1 * US),
         (TPU0, "%broadcast.1", "", 392 * US, 1 * US))
MODULES = [m for m, _ in RUNS] + [EAGER[0]]
OPS = [o for _, o in RUNS] + [EAGER[1]]
HOST = [
    host("enqueue", 80, 90, 7), host("sync", 90, 150, 6), host("enqueue", 150, 160, 8),
    host("sync", 160, 340, 9), host("fanout", 340, 350), host("admit", 350, 370),
    host("handoff", 370, 380), host("prep", 380, 390), host("enqueue", 390, 396, 10),
    host("sync", 396, 505, 10),
]


class TestQueuedOrDrained:
    def test_module_runs_join_the_engines_numbers(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=600e-6)
        rows = out["dispatches"]
        assert [(r["seq"], r["module"]) for r in rows] == [
            (7, "jit_decode"), (8, "jit_ragged_paged"), (9, "jit_finalize"), (10, "jit_decode")]
        assert [r["device_s"] for r in rows] == pytest.approx([100e-6, 120e-6, 10e-6, 100e-6])

    def test_a_gap_is_classed_by_what_stood_on_the_queue_when_it_began(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=600e-6)
        by_seq = {r["seq"]: r for r in out["dispatches"]}
        assert (by_seq[8]["gap_before_s"], by_seq[8]["gap"]) == (0.0, None)
        assert by_seq[9]["gap"] == devtrace.QUEUED  # enqueued at 150-160, idle from 320
        assert by_seq[9]["gap_before_s"] == pytest.approx(5e-6)
        assert by_seq[10]["gap"] == devtrace.DRAINED  # idle from 335, enqueued at 390-396
        assert by_seq[10]["gap_before_s"] == pytest.approx(64e-6)  # both pieces
        assert out["gap_class_s"] == pytest.approx({"drained": 64e-6, "queued": 5e-6})

    def test_the_classes_add_up_to_the_idle_and_the_drained_go_by_phase(self):
        out = devtrace.reduce_trace(OPS, MODULES, HOST, window_s=600e-6)
        assert sum(out["gap_class_s"].values()) == pytest.approx(sum(out["gap_s"].values()))
        assert out["gap_drained_s"] == pytest.approx({
            "engine.admit": 20e-6, "engine.fanout": 10e-6, "engine.handoff": 10e-6,
            "engine.prep": 10e-6, "engine.enqueue": 5e-6,
            "engine.sync": 9e-6})  # 335-340 waiting for 9, 396-400 for 10 to start
        assert out["gap_s"]["engine.sync"] == pytest.approx(14e-6)  # and 5 queued

    def test_a_capture_that_begins_mid_stream_finds_the_same_offset(self):
        """The first run of the capture is program 8: nothing before it."""
        out = devtrace.reduce_trace(OPS[1:], MODULES[1:], HOST[2:], window_s=600e-6)
        assert [r["seq"] for r in out["dispatches"]] == [8, 9, 10]
        assert out["gap_class_s"] == pytest.approx({"drained": 64e-6, "queued": 5e-6})

    def test_a_program_without_the_account_joins_nothing(self):
        plain = [h[:3] for h in HOST]
        out = devtrace.reduce_trace(OPS, MODULES, plain, window_s=600e-6)
        assert out["dispatches"] == [] and out["gap_drained_s"] == {}
        assert out["gap_class_s"] == pytest.approx({devtrace.UNJOINED: 69e-6})
        assert out["gap_s"] == devtrace.reduce_trace(OPS, MODULES, HOST, 600e-6)["gap_s"]


class TestTheProgramsNames:
    def test_every_program_the_engine_numbers_is_a_known_module(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        from calfkit_tpu.inference import model as M
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        config = preset("debug")
        params = M.init_params(config, jax.random.key(0), dtype=jnp.float32)
        names = set()
        for layout in ("paged", "dense"):
            engine = InferenceEngine(config, RuntimeConfig(
                max_batch_size=2, max_seq_len=64, prefill_chunk=16, page_size=16,
                decode_steps_per_dispatch=4, kv_layout=layout), params=params)
            built = [engine._decode_jit(16, 4), engine._ragged_jit(16, 4, False, 16, 1),
                     engine._verify_jit(16, 2, False), engine._finalize_jit(16, 1, False),
                     engine._chunk_jit(16, 1), engine._prefill_jit(16, 1)]
            if layout == "paged":
                built.append(engine._seed_scratch_jit(32, 1, 1))
            assert {p["family"] for p in engine.programs()} == {
                "decode", "ragged", "verify", "finalize", "chunk", "prefill",
                *(["seed"] if layout == "paged" else [])}
            names |= {f"jit_{program.fn.__name__}" for program in built}
        assert all(devtrace.PROGRAM_MODULE.match(name) for name in names)
        alternatives = re.search(r"\((.*)\)", devtrace.PROGRAM_MODULE.pattern).group(1)
        assert names == {f"jit_{a}" for a in alternatives.split("|")}


class TestOneClockWithTheDevice:
    def test_seq_rides_the_annotation_and_its_name_stays(self, tmp_path):
        """What ``benchmarks/trace_reduce.py`` reads of a capture is what it
        read before (names, six-long tuples); the program's reader finds the
        number beside them."""
        jax = pytest.importorskip("jax")
        import time

        from benchmarks import trace_reduce
        from calfkit_tpu.inference.engine import ENQUEUE, PREP, SYNC, EngineStats

        stats = EngineStats()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for seq in (41, 42):
                stats.enter(PREP)
                time.sleep(0.002)
                stats.enter(ENQUEUE, seq)
                time.sleep(0.002)
                stats.enter(SYNC, seq)
                time.sleep(0.002)
            stats.enter(None)
        finally:
            jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(str(tmp_path))
        events = [e for e in trace_reduce.load_events(path) if e[2].startswith("engine.")]
        assert [e[2] for e in sorted(events, key=lambda e: e[3])] == [
            "engine.prep", "engine.enqueue", "engine.sync"] * 2
        assert all(len(e) == 6 for e in events)
        _, _, annotations = devtrace.read_trace(path)
        annotations.sort(key=lambda h: h[1])
        assert [(h[0], *h[3:]) for h in annotations] == [
            ("engine.prep",), ("engine.enqueue", 41), ("engine.sync", 41),
            ("engine.prep",), ("engine.enqueue", 42), ("engine.sync", 42)]
        assert all(h[2] >= 1_500_000 for h in annotations)  # the 2 ms each, in ns
