"""The reduction from profiler events to device numbers: on hand-made events
and on a small trace recorded on the chip (tests/recorded_trace.json, the
first events of a traced run of this benchmark on a TPU v5e)."""

import json
import os

import pytest

from benchmarks import trace_reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def test_union_and_gaps():
    busy, gaps = R.union_seconds([(0, 10), (5, 20), (30, 40), (32, 35), (40, 45)])
    assert busy == pytest.approx(35e-9)
    assert gaps == [(20, 30)]
    assert R.union_seconds([]) == (0.0, [])


def test_reduce_hand_made_events():
    ms = 1_000_000
    events = [
        (DEV, R.MODULES_LINE, "jit_ragged_paged(111)", 0, 60 * ms),
        (DEV, R.MODULES_LINE, "jit_decode(222)", 70 * ms, 20 * ms),
        (DEV, R.MODULES_LINE, "jit_decode(333)", 95 * ms, 5 * ms),
        (DEV, R.OPS_LINE, "fusion.1", 0, 40 * ms),
        (DEV, R.OPS_LINE, "all-reduce.7", 40 * ms, 20 * ms),
        (DEV, R.OPS_LINE, "fusion.1", 70 * ms, 20 * ms),
        (DEV, R.OPS_LINE, "gather.2", 95 * ms, 5 * ms),
        ("/host:CPU", "python", "bench.traced_window", 0, 100 * ms),
        ("/host:CPU", "python", "bench.recv", 60 * ms, 9 * ms),
    ]
    out = R.reduce(events, window_s=0.1)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(0.085)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.15)  # the idle share
    assert out["by_module"] == {"jit_ragged_paged": pytest.approx(0.06),
                                "jit_decode": pytest.approx(0.025)}
    assert out["module_runs"] == {"jit_ragged_paged": 1, "jit_decode": 2}
    assert out["collective_s"] == pytest.approx(0.02)
    assert R.module_seconds(out, [r"^jit_decode$", r"^jit_ragged_"]) == pytest.approx(0.085)
    assert R.module_seconds(out, [r"^jit_decode$"]) == pytest.approx(0.025)
    assert R.top_ops(out, 2) == [["fusion.1", pytest.approx(0.06)],
                                 ["all-reduce.7", pytest.approx(0.02)]]
    gaps = dict(out["idle_gaps"])
    # 60..70 ms is covered by bench.recv for 9 of its 10 ms; 90..95 ms by the
    # window annotation only, which says nothing
    assert gaps == {"bench.recv": pytest.approx(0.010), "unattributed": pytest.approx(0.005)}


def test_two_devices_are_averaged():
    ms = 1_000_000
    events = [
        ("/device:TPU:0", R.OPS_LINE, "a", 0, 10 * ms),
        ("/device:TPU:1", R.OPS_LINE, "a", 0, 30 * ms),
    ]
    out = R.reduce(events, 0.04)
    assert out["devices"] == 2 and out["busy_s"] == pytest.approx(0.02)
    assert R.reduce([], 1.0)["devices"] == 0


def test_recorded_trace_from_the_chip():
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    out = R.reduce(events, rec["window_s"])
    assert out["devices"] == rec["expect"]["devices"]
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= rec["window_s"]
    for family, seconds in rec["expect"]["by_module"].items():
        assert out["by_module"][family] == pytest.approx(seconds, rel=1e-9)
    # independent re-derivation of the busy union, the slow way
    ops = sorted((e[3], e[3] + e[4]) for e in events
                 if e[0] == "/device:TPU:0" and e[1] == R.OPS_LINE)
    covered, end = 0, 0
    for s, e in ops:
        covered += max(0, e - max(s, end))
        end = max(end, e)
    assert out["busy_s"] == pytest.approx(covered / 1e9)


def test_trace_readers_on_the_recorded_trace():
    """The readers of the registered trace metrics, on the recorded trace
    and hand-made counters: a step time, and a roofline share under 100."""
    import types

    from benchmarks import manifest as M
    from benchmarks.metrics import Sample

    root = os.path.dirname(os.path.dirname(HERE))
    man = M.load_manifest(root)
    cell = M.resolve_cell(man, man["workloads"][0]["name"], root)
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    done = Sample(due=0.0, budget=64, prompt_tokens=400)
    done.events = [(0.1, 1), (0.9, 63)]
    ctx = types.SimpleNamespace(
        trace_reduced=R.reduce([tuple(e) for e in rec["events"]], rec["window_s"]),
        trace_counters={"decode_dispatches": 1, "short_dispatches": 1, "decode_tokens": 80,
                        "prefill_tokens": 300, "unified_dispatches": 1},
        runtime=types.SimpleNamespace(decode_steps_per_dispatch=8),
        samples=[done], config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
    )
    got = {m.name: m.read(ctx) for m in cell.per_layer}
    assert got["dispatch_step_ms"] == pytest.approx(278.532262 / 4)  # one short dispatch: 4 steps
    assert got["device_idle_closed_pct"] == pytest.approx(100 * (1 - 0.278527249 / rec["window_s"]))
    assert got["prefill_device_pct"] == pytest.approx(100 * 0.278532262 / 0.278527249)
    assert 0 < got["dispatch_roofline"] < 100
    ctx.trace_reduced = None  # nothing to read: nothing returned
    assert all(m.read(ctx) is None for m in cell.per_layer)
