"""The four readers of the engine's device-queue account (ISSUE 36) over a
made-up run: what each computes from recorded spans and counters, that each
returns nothing (and does not raise) against the parent's spans and
counters, and that the manifest carries each, in every cell, with the
metric it moves."""

import json
import time
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks.metrics import Sample, percentile

NAMES = ("drained_syncs_pct", "dispatch_step_span_p95_ms", "mesh_stream_overhead_p95_ms",
         "program_build_s")
READ = {name: M.load_reader(name) for name in NAMES}
MOVES = dict(zip(NAMES, ("tpot_p95_ms", "tpot_p95_ms", "tpot_p95_ms", "setup_s")))
T0 = 1000.0  # the window, on the monotonic clock
WALL = time.time() - time.perf_counter()  # a span's start_s is on the wall clock


def dispatch(seq, end_s, exclusive_ms, steps=8, by=None):
    """An ``engine.dispatch`` span that ended ``end_s`` into the window."""
    return SimpleNamespace(
        name="engine.dispatch", trace_id=f"d{seq}", status="ok", duration_ms=exclusive_ms,
        start_s=T0 + end_s - exclusive_ms / 1e3 + WALL, span_id="s", parent_span_id=None,
        attrs={"seq": seq, "steps": steps, "exclusive_ms": exclusive_ms,
               "proved_by": seq if by is None else by})


def decode(trace_id, ms, tokens, status="ok", ended_where_the_stream_did=True):
    attrs = {"generated_tokens": tokens, **({"last_seq": 9} if ended_where_the_stream_did else {})}
    return SimpleNamespace(name="engine.decode", trace_id=trace_id, status=status,
                           duration_ms=ms, attrs=attrs, span_id="s", parent_span_id="p")


def sample(cid, first, last, tokens):
    return Sample(due=T0, correlation_id=cid, events=[(first, 1), (last, tokens - 1)])


def run(counters=None, spans=(), samples=(), engine=None):
    return SimpleNamespace(
        trace_counters=counters, trace_reduced={"window_s": 8.0} if counters else None,
        spans=list(spans), samples=list(samples), t0=T0, t_end=T0 + 51.0, engine=engine)


def test_drains_as_a_share_of_the_dispatches_landed():
    counters = {"decode_dispatches": 50, "pipeline_drains": 35, "pipeline_drains_wave": 33}
    assert READ["drained_syncs_pct"](run(counters)) == pytest.approx(70.0)
    assert READ["drained_syncs_pct"](run(dict(counters, decode_dispatches=0))) is None


def test_step_tail_over_the_windows_spans_with_shared_syncs_as_one(capsys):
    # 30 dispatches of 8 steps at 32 ms a step, one slow one (64), and a pair that
    # ONE sync proved: 512 ms over the first's 8 steps and 0 over the second's 4
    spans = [dispatch(i, 1.0 + i, 256.0) for i in range(30)]
    spans += [dispatch(40, 40.0, 512.0), dispatch(41, 41.0, 512.0, by=42),
              dispatch(42, 41.0, 0.0, steps=4, by=42)]
    spans += [dispatch(50, -1.0, 9e6), dispatch(51, 52.0, 9e6)]  # ramp-in, drain: not counted
    value = READ["dispatch_step_span_p95_ms"](run(spans=spans))
    per_step = [32.0] * 30 + [64.0, 512.0 / 12]
    assert value == pytest.approx(percentile(per_step, 95))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "dispatch_step_span_p95_ms" and line["spans"] == 33
    assert line["syncs"] == 32 and line["p50_ms"] == pytest.approx(32.0)
    assert line["max_ms"] == pytest.approx(64.0)
    assert line["mean_ms"] == pytest.approx((30 * 256.0 + 512.0 + 512.0) / (30 * 8 + 8 + 12))


def test_fewer_than_twenty_spans_read_as_nothing():
    spans = [dispatch(i, 1.0 + i, 256.0) for i in range(19)]
    assert READ["dispatch_step_span_p95_ms"](run(spans=spans)) is None


def test_stream_overhead_is_the_clients_time_per_token_less_the_engines():
    samples = [sample("a", T0 + 1, T0 + 11, 101),  # 100 ms a token at the client
               sample("b", T0 + 2, T0 + 7, 51),  # 100
               sample("c", T0 + 3, T0 + 4, 11),  # no engine span: left out
               sample(None, T0 + 3, T0 + 4, 11)]
    spans = [decode("a", 9_000.0, 101), decode("b", 4_900.0, 51),  # 90 and 98 in the engine
             decode("ramp-in", 1.0, 9), decode("c", 1.0, 11, status="cancelled"),
             decode("d", 1.0, 1)]
    value = READ["mesh_stream_overhead_p95_ms"](run(spans=spans, samples=samples))
    assert value == pytest.approx(2.0 + 0.95 * 8.0)


def test_build_seconds_are_summed_over_the_engines_table():
    table = [{"family": "decode", "key": [16, 4], "builds": 2, "build_s": 1.5, "first_seq": 4,
              "built_seq": 30, "uses": 9},
             {"family": "ragged", "key": ["ragged", 16], "builds": 1, "build_s": 2.25,
              "first_seq": 9, "built_seq": 9, "uses": 1},
             {"family": "seed", "key": ["seed", 1], "builds": 0, "build_s": 0.0,
              "first_seq": None, "built_seq": None, "uses": 0}]
    engine = SimpleNamespace(programs=lambda: table)
    assert READ["program_build_s"](run(engine=engine)) == pytest.approx(3.75)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_and_counters_read_as_nothing(name):
    """The parent counts no drain, ends no ``engine.dispatch`` span, ends
    ``engine.decode`` after the stream was closed (no ``last_seq``) and its
    engine keeps no table."""
    counters = {"decode_dispatches": 16, "decode_tokens": 3000, "starved_s": 0.0}
    spans = [decode("a", 9_000.0, 101, ended_where_the_stream_did=False),
             SimpleNamespace(name="engine.queue", trace_id="a", status="ok", duration_ms=5.0,
                             attrs={}, span_id="s", parent_span_id="p")]
    parent = run(counters, spans, [sample("a", T0 + 1, T0 + 11, 101)], engine=SimpleNamespace())
    assert READ[name](parent) is None
    assert READ[name](run()) is None  # an untraced run: no counters, no spans, no engine


def test_the_manifest_carries_all_four_in_every_cell():
    man = M.load_manifest(M.ROOT)
    entries = {e["name"]: e for e in man["per_layer"]}
    assert [e["name"] for e in man["per_layer"]][-4:] == list(NAMES)  # appended, in order
    for name in NAMES:
        assert "workloads" not in entries[name] and entries[name]["moves"] == MOVES[name]
    for row in man["workloads"]:
        cell = M.resolve_cell(man, row["name"], M.ROOT)
        registered = {m.name: m for m in cell.per_layer}
        for name in NAMES:
            assert registered[name].moves == MOVES[name]
            assert registered[name].read is not None
