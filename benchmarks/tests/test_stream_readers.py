"""The eight readers of a token's road from its dispatch's landing to the
caller and of the stall no phase names (ISSUE 52; seven of them ISSUE 50's)
over a made-up run: what each computes from the stage account on
``engine.decode`` and ``agent.turn`` and from the ``stream_*`` /
``loop_stall_s`` / ``phase_long_s`` counters, that a slow consumer moves the
stages and not the landings (where ``mesh_stream_overhead_p95_ms`` reads
nothing), that each returns nothing against the parent's spans and counters
and a number (0.0) against this program's with nothing booked, and that the
manifest carries each, in every cell."""

import json
import time
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks.metrics import Sample, percentile

NAMES = ("engine_tpot_landed_p95_ms", "stream_deliver_wait_p95_ms", "loop_stall_pct",
         "phase_long_pct", "stream_emit_ms_per_event", "stream_backpressure_p95_ms",
         "publish_ack_p95_ms", "stream_path_p95_ms")
LAYER = dict(zip(NAMES, (*["admission and batching"] * 4, *["node and agent"] * 2,
                         *["client and mesh"] * 2)))
SOURCE = dict(zip(NAMES, ("program_span", "program_span", "program_counter", "program_counter",
                          "program_counter", "program_span", "program_span", "program_span")))
READ = {name: M.load_reader(name) for name in NAMES}
T0 = 1000.0  # the window, on the monotonic clock
WALL = time.time() - time.perf_counter()  # a span's start_s is on the wall clock


def decode(cid, start, tokens, first_landed_ms, last_landed_ms, blocks, events, wait_ms,
           wait_max_ms, stages, status="ok", account=True):
    """An ``engine.decode`` span that began ``start`` s into the window."""
    block_wait_ms, emit_ms, backpressure_ms, own_ms = stages
    attrs = {"generated_tokens": tokens, "first_seq": 1, "last_seq": 9}
    if account:
        attrs.update(blocks=blocks, events=events, first_landed_ms=first_landed_ms,
                     last_landed_ms=last_landed_ms, deliver_wait_ms=wait_ms,
                     deliver_wait_max_ms=wait_max_ms, block_wait_ms=block_wait_ms,
                     emit_ms=emit_ms, backpressure_ms=backpressure_ms)
    return SimpleNamespace(name="engine.decode", trace_id=cid, status=status, attrs=attrs,
                           start_s=T0 + start + WALL, duration_ms=sum(stages),
                           span_id="d" + cid, parent_span_id="g" + cid)


def turn(cid, token_events, publish_ms, account=True):
    attrs = {"model": "m", "generated_tokens": 9}
    if account:
        attrs.update(token_events=token_events, step_build_ms=0.1 * token_events,
                     publish_ms=publish_ms, publish_max_ms=publish_ms / 2)
    return SimpleNamespace(name="agent.turn", trace_id=cid, status="ok", attrs=attrs,
                           start_s=T0 + WALL, duration_ms=9e3, span_id="t" + cid,
                           parent_span_id="h")


def sample(cid, first, last, tokens):
    return Sample(due=T0, correlation_id=cid, events=[(T0 + first, 1), (T0 + last, tokens - 1)])


def run(counters=None, spans=(), samples=(), engine=None):
    """(``counters`` stands for the traced interval's AND the whole window's:
    a reader takes the one or the other)"""
    return SimpleNamespace(
        trace_counters=counters, trace_reduced={"window_s": 8.0} if counters else None,
        counters={"window": counters} if counters else {}, seconds=51.0, engine=engine,
        spans=list(spans), samples=list(samples), t0=T0, t_end=T0 + 51.0)


# request a: 101 tokens; the span began 1 s in, its first block landed 2 ms
# BEFORE that, its last 9,000 ms after; b: 51 tokens from 2 s in; "gone" was
# cancelled, "ramp" was due before the window, "one" made a single token
SPANS = [
    decode("a", 1.0, 101, -2.0, 9_000.0, 26, 26, 52.0, 7.0, (8_000.0, 13.0, 1_040.0, 47.0)),
    decode("b", 2.0, 51, -1.0, 4_899.0, 14, 13, 14.0, 3.0, (4_500.0, 6.5, 390.0, 3.5)),
    decode("gone", 3.0, 11, -1.0, 99.0, 3, 3, 900.0, 800.0, (1.0, 1.0, 1.0, 1.0),
           status="cancelled"),
    decode("ramp", 0.5, 21, -1.0, 999.0, 6, 6, 600.0, 500.0, (1.0, 1.0, 1.0, 1.0)),
    decode("one", 4.0, 1, -1.0, -1.0, 1, 1, 1.0, 1.0, (0.0, 0.5, 0.5, 0.0)),
    turn("a", 27, 540.0), turn("b", 14, 350.0), turn("ramp", 6, 6e4),
]
SAMPLES = [sample("a", 1.004, 10.030, 101), sample("b", 2.001, 6.910, 51),
           sample("gone", 3.0, 3.1, 11), sample("one", 4.0, 4.0, 1),
           sample("nospan", 5.0, 6.0, 11), sample(None, 5.0, 6.0, 11)]
COUNTERS = {"decode_dispatches": 50, "stream_blocks": 4_000, "stream_events": 4_000,
            "stream_deliver_wait_s": 9.0, "stream_emit_s": 0.6, "stream_backpressure_s": 70.0,
            "loop_stalls": 2, "loop_stall_s": 0.2, "phase_longs": 1, "phase_long_s": 2.04}


def last_line(capsys, metric):
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    return next(row for row in reversed(lines) if row["metric"] == metric)


def test_tpot_at_the_landings_is_the_engines_own_time_a_token(capsys):
    value = READ["engine_tpot_landed_p95_ms"](run(COUNTERS, SPANS, SAMPLES))
    per_token = [9_002.0 / 100, 4_900.0 / 50]  # "one" has no second token: left out
    assert value == pytest.approx(percentile(per_token, 95))
    row = last_line(capsys, "engine_tpot_landed_p95_ms")
    assert row["requests"] == 3 and row["values"] == 2 and row["max_ms"] == pytest.approx(98.0)


def test_deliver_wait_a_block_and_the_longest_single_wait(capsys):
    value = READ["stream_deliver_wait_p95_ms"](run(COUNTERS, SPANS, SAMPLES))
    assert value == pytest.approx(percentile([52.0 / 26, 14.0 / 14, 1.0], 95))
    assert last_line(capsys, "stream_deliver_wait_p95_ms")["longest_wait_ms"] == 7.0


def test_backpressure_an_event_and_the_publish_to_its_acknowledgement(capsys):
    ctx = run(COUNTERS, SPANS, SAMPLES)
    assert READ["stream_backpressure_p95_ms"](ctx) == pytest.approx(
        percentile([1_040.0 / 26, 390.0 / 13, 0.5], 95))
    assert READ["publish_ack_p95_ms"](ctx) == pytest.approx(percentile([20.0, 25.0], 95))
    row = last_line(capsys, "publish_ack_p95_ms")
    assert row["longest_publish_ms"] == pytest.approx(270.0)  # a's: half its 540
    assert row["step_build_p50_ms"] == pytest.approx(0.1)


def test_the_counters_by_difference():
    ctx = run(COUNTERS, SPANS, SAMPLES)
    assert READ["stream_emit_ms_per_event"](ctx) == pytest.approx(0.15)
    assert READ["loop_stall_pct"](ctx) == pytest.approx(2.5)  # of the traced 8 s
    assert READ["phase_long_pct"](ctx) == pytest.approx(4.0)  # of the window's 51 s


def test_a_long_phase_is_a_share_of_the_whole_window_with_the_journal_beside_it(capsys):
    """The log line holds the count and the window's ``PHASE_LONG`` /
    ``LOOP_STALL`` events where the engine's journal is in reach; events
    outside the window and of other kinds are left out."""
    from calfkit_tpu.observability import flightrec as F

    journal = SimpleNamespace(snapshot=lambda: [
        (1, T0 - 5.0, F.EV_PHASE_LONG, None, -1, 900, 3, "engine.enqueue"),  # the ramp-in's
        (2, T0 + 20.0, F.EV_DISPATCH_LAND, None, -1, 4, 8, None),
        (3, T0 + 30.5, F.EV_PHASE_LONG, None, -1, 2040, 77, "engine.sync"),
        (4, T0 + 30.6, F.EV_LOOP_STALL, None, -1, 180, 0, None),
        (5, T0 + 52.0, F.EV_LOOP_STALL, None, -1, 99, 0, None),  # the drain's
    ])
    ctx = run(COUNTERS, SPANS, SAMPLES, engine=SimpleNamespace(_journal=journal))
    assert READ["phase_long_pct"](ctx) == pytest.approx(100.0 * 2.04 / 51.0)
    row = last_line(capsys, "phase_long_pct")
    assert row["phase_longs"] == 1 and row["loop_stall_s"] == 0.2 and row["window_s"] == 51.0
    assert row["journal"] == [
        {"event": "PHASE_LONG", "at_s": 30.5, "ms": 2040, "phase": "engine.sync", "seq": 77},
        {"event": "LOOP_STALL", "at_s": 30.6, "ms": 180}]
    READ["phase_long_pct"](run(COUNTERS, SPANS, SAMPLES))  # no engine in reach
    assert last_line(capsys, "phase_long_pct")["journal"] is None


def test_the_road_of_the_last_block_on_one_clock_and_the_account_closing(capsys):
    value = READ["stream_path_p95_ms"](run(COUNTERS, SPANS, SAMPLES))
    # a: last event 10.030 s, last landing 1 s + 9,000 ms; b: 6.910 against 2 s + 4,899 ms
    roads = [30.0, 11.0, 1.0]  # ("one": its only event at 4.0 s, its landing 1 ms before)
    assert value == pytest.approx(percentile(roads, 95), abs=0.05)
    row = last_line(capsys, "stream_path_p95_ms")
    assert row["requests"] == 3
    assert row["least_last_block_road_ms"] == pytest.approx(1.0, abs=0.05)
    assert row["least_first_block_road_ms"] == pytest.approx(1.0, abs=0.05)  # a: 6, b: 2
    own = [100 * 47.0 / 9_100.0, 100 * 3.5 / 4_900.0, 0.0]
    assert row["decode_self_time_p50_pct"] == pytest.approx(percentile(own, 50))
    assert row["backpressure_p50_pct"] == pytest.approx(
        percentile([100 * 1_040.0 / 9_100.0, 100 * 390.0 / 4_900.0, 50.0], 50))


def test_a_slow_consumer_moves_the_stages_and_not_the_landings():
    """The consumer holds every event 40 ms longer: the client's gap, the
    span's duration and its ``backpressure_ms`` grow by the same, the
    landings stand.  ``mesh_stream_overhead_p95_ms`` (the client's gap less
    the span's duration) reads what it read; the new pair tells them apart."""
    control = M.load_reader("mesh_stream_overhead_p95_ms")
    fast = [decode("a", 1.0, 101, -2.0, 9_000.0, 26, 26, 52.0, 7.0, (8_960.0, 13.0, 26.0, 1.0))]
    slow = [decode("a", 1.0, 101, -2.0, 9_000.0, 26, 26, 52.0, 7.0,
                   (7_920.0, 13.0, 26.0 + 26 * 40.0, 1.0))]
    fast_run = run(COUNTERS, fast, [sample("a", 1.004, 10.004, 101)])
    slow_run = run(COUNTERS, slow, [sample("a", 1.004, 10.004 + 1.040, 101)])
    assert slow[0].duration_ms == fast[0].duration_ms  # the stream went on at the engine's pace
    slow[0].duration_ms += 1_040.0  # ... and stood 40 ms an event longer in its consumer
    assert control(slow_run) == pytest.approx(control(fast_run))  # the control reads nothing
    landed = READ["engine_tpot_landed_p95_ms"]
    assert landed(slow_run) == pytest.approx(landed(fast_run)) == pytest.approx(90.02)
    assert READ["stream_backpressure_p95_ms"](slow_run) == pytest.approx(41.0)
    assert READ["stream_backpressure_p95_ms"](fast_run) == pytest.approx(1.0)
    road = READ["stream_path_p95_ms"]
    assert road(slow_run) - road(fast_run) == pytest.approx(1_040.0, abs=0.05)


@pytest.mark.parametrize("name", NAMES)
def test_the_parents_spans_and_counters_read_as_nothing(name):
    """The parent ends ``engine.decode`` and ``agent.turn`` without the
    account and keeps no ``stream_*`` / ``loop_stall_s`` counter."""
    counters = {"decode_dispatches": 16, "decode_tokens": 3000, "starved_s": 0.0}
    spans = [decode("a", 1.0, 101, 0, 0, 0, 0, 0, 0, (9_000.0, 0.0, 0.0, 0.0), account=False),
             turn("a", 0, 0.0, account=False)]
    parent = run(counters, spans, [sample("a", 1.0, 11.0, 101)])
    assert READ[name](parent) is None
    assert READ[name](run()) is None  # an untraced run: no counters, no spans


@pytest.mark.parametrize("name", NAMES)
def test_this_program_with_nothing_booked_reads_zero_and_never_nothing(name):
    """No request due in the window finished (their spans are the ramp-in's)
    and the loop booked nothing: a number all the same, for the result line
    holds one for every metric its cell registers."""
    counters = dict.fromkeys(COUNTERS, 0)
    spans = [s for s in SPANS if s.trace_id == "ramp"]
    assert READ[name](run(counters, spans, [sample("late", 1.0, 2.0, 11)])) == 0.0


def test_the_manifest_carries_all_eight_in_every_cell():
    man = M.load_manifest(M.ROOT)
    entries = {e["name"]: e for e in man["per_layer"]}
    assert [e["name"] for e in man["per_layer"]][-8:] == list(NAMES)  # appended, in order
    for name in NAMES:
        assert entries[name] == {
            "name": name, "unit": "%" if name.endswith("_pct") else "ms", "better": "lower",
            "source": SOURCE[name], "layer": LAYER[name], "moves": "tpot_p95_ms"}
        assert SOURCE[name] in M.SOURCES
    for row in man["workloads"]:
        cell = M.resolve_cell(man, row["name"], M.ROOT)
        registered = {m.name: m for m in cell.per_layer}
        for name in NAMES:
            assert registered[name].moves == "tpot_p95_ms" and registered[name].read is not None
