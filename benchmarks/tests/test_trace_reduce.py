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
    assert R.top_ops(out, 2) == [["(unscoped) fusion", pytest.approx(0.06)],
                                 ["(unscoped) all-reduce", pytest.approx(0.02)]]
    gaps = dict(out["idle_gaps"])
    # 60..70 ms is covered by bench.recv for 9 of its 10 ms, and split there;
    # 90..95 ms by the window annotation only, which says nothing
    assert gaps == {"bench.recv": pytest.approx(0.009), "unattributed": pytest.approx(0.006)}


def test_idle_gaps_go_by_the_engines_phases():
    """The engine's phase clock is exclusive: a gap is split exactly where
    one phase ends and the next begins, the harness's own longer
    annotation gets only what no phase covers, and the rest is
    ``unattributed``."""
    ms = 1_000_000
    host = [
        ("/host:CPU", "loop", "bench.traced_window", 0, 100 * ms),
        ("/host:CPU", "loop", "engine.sync", 0, 12 * ms),
        ("/host:CPU", "loop", "engine.handoff", 12 * ms, 3 * ms),
        ("/host:CPU", "loop", "engine.prep", 15 * ms, 4 * ms),
        ("/host:CPU", "main", "bench.send", 10 * ms, 30 * ms),
        ("/host:CPU", "loop", "engine.sync", 50 * ms, 40 * ms),
    ]
    events = [
        (DEV, R.OPS_LINE, "a", 0, 10 * ms, "decode_loop/mlp"),
        (DEV, R.OPS_LINE, "a", 20 * ms, 35 * ms, "decode_loop/mlp"),  # gap 10..20
        (DEV, R.OPS_LINE, "a", 60 * ms, 40 * ms, "decode_loop/mlp"),  # gap 55..60
        *host,
    ]
    gaps = dict(R.reduce(events, 0.1)["idle_gaps"])
    assert gaps == {"engine.sync": pytest.approx(0.007), "engine.handoff": pytest.approx(0.003),
                    "engine.prep": pytest.approx(0.004), "bench.send": pytest.approx(0.001)}
    assert R.UNATTRIBUTED not in gaps
    assert dict(R.attribute_gaps([(0, 10 * ms)], [])) == {R.UNATTRIBUTED: pytest.approx(0.01)}
    assert R.attribute_gaps([], host) == []


def test_operations_go_by_scope_path_and_their_own_time():
    """A ``while`` is not counted for its body; an operation reads
    ``<scope path> <its own name>`` with the ``%`` and the number that
    differs from one program variant to the next cut; what has no scope
    (the copies XLA inserts) is gathered under ``(unscoped)``."""
    ms = 1_000_000
    events = [
        (DEV, R.OPS_LINE, "%while.47 = (s32[]) while(...)", 0, 90 * ms, "decode_loop"),
        (DEV, R.OPS_LINE, "%fusion.12 = bf16[32,4096] fusion(...)", 0, 50 * ms, "decode_loop/mlp"),
        (DEV, R.OPS_LINE, "%paged_decode_attention.11 = ...", 50 * ms, 30 * ms,
         "decode_loop/attention"),
        (DEV, R.OPS_LINE, "%fusion.98 = bf16[32,4096] fusion(...)", 100 * ms, 20 * ms,
         "decode_loop/mlp"),
        (DEV, R.OPS_LINE, "%copy.271 = ...", 120 * ms, 5 * ms, ""),
        (DEV, R.OPS_LINE, "%copy.3", 125 * ms, 1 * ms),  # five long: a trace from before PR 26
    ]
    out = R.reduce(events, 0.2)
    assert out["busy_s"] == pytest.approx(0.116)
    assert R.top_ops(out) == [
        ["decode_loop/mlp fusion", pytest.approx(0.07)],
        ["decode_loop/attention paged_decode_attention", pytest.approx(0.03)],
        ["decode_loop while", pytest.approx(0.01)],
        ["(unscoped) copy", pytest.approx(0.006)]]
    assert out["by_scope"] == {"decode_loop": pytest.approx(0.01),
                               "decode_loop/mlp": pytest.approx(0.07),
                               "decode_loop/attention": pytest.approx(0.03),
                               "(unscoped)": pytest.approx(0.006)}
    assert out["by_op"]["%while.47 = (s32[]) while(...)"] == pytest.approx(0.09)  # as before


@pytest.mark.parametrize("tf_op,path", [
    ("jit(ragged_paged)/decode_loop/while/body/qkv/dot_general:", "decode_loop/qkv"),
    ("jit(decode)/jit(main)/decode_loop/while/body/attention/pallas_call/"
     "paged_decode_attention:", "decode_loop/attention"),
    ("jit(f)/chunk_loop/while/body/closed_call/mlp/dequant/convert_element_type",
     "chunk_loop/mlp/dequant"),
    ("jit(f)/cond/branch_1_fun/sample/argmax:", "sample"),
    ("jit(f)/decode_loop/while/body/mlp/bsf,fd->bsd/dot_general:", "decode_loop/mlp"),
    ("jit(f)/transpose(jvp(mixer))/scan/mul:", ""), ("copy.3", ""), ("", ""),
])
def test_scope_path_keeps_what_the_program_named(tf_op, path):
    assert R.scope_path(tf_op) == path


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def test_load_events_reads_scopes_and_both_host_prefixes(tmp_path):
    """A hand-encoded .xplane.pb: a device plane with two operations (one
    scoped through a str stat, one through a ref stat) and a module; a
    host plane with an engine phase, a harness annotation and an event
    that is nobody's."""
    stat_md = (_field(5, _entry(1, _field(2, "tf_op")))
               + _field(5, _entry(2, _field(2, "jit(f)/chunk_loop/mlp/dot_general:"))))
    event_md = (
        _field(4, _entry(1, _field(2, "%fusion.1 = f32[8]") + _field(
            5, _field(1, 1) + _field(5, "jit(f)/decode_loop/while/body/attention/exp:"))))
        + _field(4, _entry(2, _field(2, "%fusion.2") + _field(5, _field(1, 1) + _field(7, 2))))
        + _field(4, _entry(3, _field(2, "jit_f(77)"))))
    ops = _field(2, "XLA Ops") + _field(3, 1000) + _field(
        4, _field(1, 1) + _field(2, 5_000_000) + _field(3, 2_000_000)) + _field(
        4, _field(1, 2) + _field(2, 8_000_000) + _field(3, 1_000_000))
    mods = _field(2, "XLA Modules") + _field(3, 1000) + _field(
        4, _field(1, 3) + _field(2, 5_000_000) + _field(3, 4_000_000))
    other = _field(2, "Steps") + _field(3, 1000) + _field(4, _field(1, 3) + _field(3, 9))
    device = _field(2, DEV) + _field(3, ops) + _field(3, mods) + _field(
        3, other) + event_md + stat_md
    host = (_field(2, "/host:CPU")
            + _field(4, _entry(1, _field(2, "engine.sync")))
            + _field(4, _entry(2, _field(2, "PjitFunction(f)")))
            + _field(4, _entry(3, _field(2, "bench.send")))
            + _field(3, _field(2, "python3") + _field(3, 2000) + _field(
                4, _field(1, 1) + _field(2, 1_000_000) + _field(3, 7_000_000)) + _field(
                4, _field(1, 2) + _field(2, 1_000_000) + _field(3, 1_000_000)) + _field(
                4, _field(1, 3) + _field(2, 2_000_000) + _field(3, 1_000_000))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host))
    assert R.load_events(str(path)) == [
        (DEV, R.OPS_LINE, "%fusion.1 = f32[8]", 6000, 2000, "decode_loop/attention"),
        (DEV, R.OPS_LINE, "%fusion.2", 9000, 1000, "chunk_loop/mlp"),
        (DEV, R.MODULES_LINE, "jit_f(77)", 6000, 4000, ""),
        ("/host:CPU", "python3", "engine.sync", 3000, 7000, ""),
        ("/host:CPU", "python3", "bench.send", 4000, 1000, ""),
    ]
    out = R.reduce(R.load_events(str(path)), 1e-5)
    assert R.top_ops(out) == [["decode_loop/attention fusion", pytest.approx(2e-6)],
                              ["chunk_loop/mlp fusion", pytest.approx(1e-6)]]
    assert dict(out["idle_gaps"]) == {"engine.sync": pytest.approx(1e-6)}
    assert out["by_module"] == {"jit_f": pytest.approx(4e-6)}


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
        arch=cell.arch,
    )
    got = {m.name: m.read(ctx) for m in cell.per_layer if m.source == "device_trace"}
    # to the last digit what the parent of PR 26 returned, whose readers took
    # the work from opcount.py and not from the cell's architecture
    assert got == {"dispatch_step_ms": 69.6330655, "prefill_device_pct": 100.0017998239016,
                   "dispatch_roofline": 22.29515373799802,
                   "device_idle_closed_pct": 0.001799791508527715}
    assert got["dispatch_step_ms"] == pytest.approx(278.532262 / 4)  # one short dispatch: 4 steps
    assert got["device_idle_closed_pct"] == pytest.approx(100 * (1 - 0.278527249 / rec["window_s"]))
    assert got["prefill_device_pct"] == pytest.approx(100 * 0.278532262 / 0.278527249)
    assert 0 < got["dispatch_roofline"] < 100
    ctx.trace_reduced = None  # nothing to read: nothing returned
    assert all(m.read(ctx) is None for m in cell.per_layer if m.source == "device_trace")


def test_the_wire_loader_equals_profiledata_on_a_trace_from_the_chip():
    """tests/chip_trace.xplane.pb is a short trace recorded on a TPU v5e
    (a jitted loop under named scopes, ``engine.``/``bench.`` annotations;
    45 KB).  A whole traced window of the benchmark (35.9 MB, 463,526
    events) agreed in the same way when PR 26 changed the loader.
    ``load_events`` reads it as wire format; the loader it replaced in PR 26
    read it through ``jax.profiler.ProfileData``.  Their first five fields
    must agree event for event, so that no device metric moved with the
    loader; the sixth is what the wire format adds."""
    profiler = pytest.importorskip("jax.profiler")
    if not hasattr(profiler, "ProfileData"):
        pytest.skip("this JAX has no ProfileData")
    path = os.path.join(HERE, "chip_trace.xplane.pb")
    through_jax = []
    for plane in profiler.ProfileData.from_file(path).planes:
        device = bool(R.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (R.OPS_LINE, R.MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(R.HOST_PREFIXES):
                    through_jax.append((plane.name, line.name, ev.name,
                                        int(ev.start_ns), int(ev.duration_ns)))
    events = R.load_events(path)
    assert sorted(e[:5] for e in events) == sorted(through_jax)
    # what the file holds, as reduced when it was recorded (PR 26)
    assert len(events) == 97 and sum(1 for e in events if e[5]) == 56
    out = R.reduce(events, window_s=0.02)
    assert out["devices"] == 1 and out["busy_s"] == pytest.approx(3.8413e-05, rel=1e-9)
    assert out["by_module"] == {"jit_step": pytest.approx(3.626e-05, rel=1e-9),
                                "jit_finalize": pytest.approx(3.278e-06, rel=1e-9)}
    assert out["module_runs"] == {"jit_step": 4.0, "jit_finalize": 4.0}
    assert set(out["by_scope"]) == {R.UNSCOPED, "decode_loop", "decode_loop/mlp",
                                    "decode_loop/attn_out", "lm_head", "finalize"}
    assert R.top_ops(out, 1)[0] == ["decode_loop/mlp convolution_tanh_fusion",
                                    pytest.approx(1.7584e-05, rel=1e-9)]
    assert [name for name, _ in out["idle_gaps"]] == [
        "engine.prep", "engine.sync", "engine.enqueue", R.UNATTRIBUTED]
