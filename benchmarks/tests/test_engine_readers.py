"""The four readers of the engine's phase clock, admission ledger and
``engine.queue`` span over a made-up run: what each computes, that each
returns nothing (and does not raise) against a program that keeps no
such counter or span, and that the manifest carries each with the metric it moves."""

from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks.metrics import Sample

CELL = "mistral-7b-v0.3-int8.batch-closed"
NAMES = ("engine_starved_pct", "host_work_per_dispatch_ms", "empty_slot_queued_pct",
         "queue_wait_span_p95_ms")
READ = {name: M.load_reader(name) for name in NAMES}

# 8 traced seconds, 16 dispatches: 7.5 s in the sync, 0.4 s of host work
COUNTERS = {
    "decode_dispatches": 16, "starved_s": 0.12, "empty_slot_queued_s": 51.2,
    "phase_reap_s": 0.016, "phase_admit_s": 0.064, "phase_handoff_s": 0.08,
    "phase_prep_s": 0.12, "phase_enqueue_s": 0.04, "phase_fanout_s": 0.08,
    "phase_sync_s": 7.5, "phase_idle_s": 0.1,
}


def span(name, trace_id, ms, status="ok"):
    return SimpleNamespace(name=name, trace_id=trace_id, duration_ms=ms, status=status,
                           attrs={}, span_id="s", parent_span_id="p")


def run(counters=COUNTERS, spans=(), ids=("a", "b", "c")):
    return SimpleNamespace(
        trace_counters=counters, trace_reduced={"window_s": 8.0} if counters else None,
        runtime=SimpleNamespace(max_batch_size=32), spans=list(spans),
        samples=[Sample(due=0.0, correlation_id=i) for i in ids],
    )


def test_starved_share_of_the_traced_interval():
    assert READ["engine_starved_pct"](run()) == pytest.approx(1.5)


def test_host_work_leaves_out_sync_and_idle():
    assert READ["host_work_per_dispatch_ms"](run()) == pytest.approx(25.0)


def test_empty_slots_as_a_share_of_all_slot_seconds():
    assert READ["empty_slot_queued_pct"](run()) == pytest.approx(20.0)


def test_queue_wait_takes_the_windows_own_finished_spans():
    spans = [span("engine.queue", "a", 100.0), span("engine.queue", "b", 300.0),
             span("engine.queue", "ramp-in", 9e9),  # not due in the window
             span("engine.queue", "c", 9e9, status="cancelled"),  # never granted a slot
             span("engine.prefill", "a", 9e9)]
    assert READ["queue_wait_span_p95_ms"](run(spans=spans)) == pytest.approx(290.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counter_or_span_reads_as_nothing(name):
    parent = {"decode_dispatches": 16, "decode_tokens": 3000, "occupancy_sum": 10.5}
    assert READ[name](run(counters=parent)) is None
    assert READ[name](run(counters=None)) is None  # an untraced run


def test_no_dispatch_in_the_interval_reads_as_nothing():
    assert READ["host_work_per_dispatch_ms"](run(dict(COUNTERS, decode_dispatches=0))) is None


def test_the_manifest_carries_all_four_each_moving_what_its_file_says():
    """Registered where the cell reports the end-to-end metric it moves
    (PR 26 made delivered tokens/s and first-token time such metrics),
    logged ``recorded-only`` where it does not."""
    man = M.load_manifest(M.ROOT)
    cell = M.resolve_cell(man, CELL, M.ROOT)
    registered = {m.name: m for m in cell.per_layer}
    known = {**{m.name: m for m in M.unregistered(cell, M.ROOT)}, **registered}
    reported = {m.name for m in cell.end_to_end}
    moves = dict(zip(NAMES, ("tpot_p95_ms", "tpot_p95_ms", "out_tok_s_per_chip", "ttft_p95_ms")))
    for name in NAMES:
        assert known[name].moves == moves[name]
        assert known[name].layer == "admission and batching"
        assert name not in registered or moves[name] in reported
    assert {"engine_starved_pct", "host_work_per_dispatch_ms"} <= set(registered)
    assert known["queue_wait_span_p95_ms"].source == "program_span"
    assert known["empty_slot_queued_pct"].source == "program_counter"
