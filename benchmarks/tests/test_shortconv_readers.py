"""The two readers of the gated short convolution (ISSUE 44) over reduced
traces: events with the scope paths the new cell's program names
(``decode_loop/shortconv/{in_proj,conv,out_proj}``, ``chunk_loop/shortconv/..``),
reduced by ``trace_reduce.reduce``; and the trace recorded on the chip before
there was such a scope (``recorded_trace.json``), where each reads nothing
and does not raise."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import trace_reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
READ = {n: M.load_reader(n) for n in ("shortconv_device_pct", "shortconv_mixer_roofline")}
CELL = "lfm2-8b-a1b.longform-closed"
MS = 1_000_000


def cell_run(reduced, counters, cell_name=CELL):
    try:
        cell = M.resolve_cell(M.load_manifest(M.ROOT), cell_name, M.ROOT)
    except M.ManifestError as e:  # these files laid over a program without the layer
        pytest.skip(str(e))
    return SimpleNamespace(
        trace_reduced=reduced, trace_counters=counters, counters={"window": counters or {}},
        arch=cell.arch, config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8))


def test_the_readers_over_a_reduced_trace_of_the_new_cell_s_scopes():
    # one ragged dispatch of 100 ms (8 decode steps of 128 rows and a chunk): the decode
    # steps' nine mixers 5 ms, the chunk's 2 ms, the rest experts and attention
    events = [
        (DEV, R.MODULES_LINE, "jit_ragged_paged(1)", 0, 100 * MS),
        (DEV, R.OPS_LINE, "%fusion.1", 0, 2 * MS, "decode_loop/shortconv/in_proj"),
        (DEV, R.OPS_LINE, "%fusion.2", 2 * MS, 1 * MS, "decode_loop/shortconv/conv"),
        (DEV, R.OPS_LINE, "%fusion.3", 3 * MS, 2 * MS, "decode_loop/shortconv/out_proj"),
        (DEV, R.OPS_LINE, "%fusion.4", 5 * MS, 2 * MS, "chunk_loop/shortconv/in_proj"),
        (DEV, R.OPS_LINE, "%paged_decode_attention.5", 7 * MS, 8 * MS, "decode_loop/attention"),
        (DEV, R.OPS_LINE, "%ragged-dot.6", 15 * MS, 85 * MS, ""),
    ]
    reduced = R.reduce(events, window_s=0.1)
    assert reduced["by_scope"]["decode_loop/shortconv/conv"] == pytest.approx(0.001)
    counters = {"decode_tokens": 8 * 128, "decode_dispatches": 1, "short_dispatches": 0}
    run = cell_run(reduced, counters)
    assert READ["shortconv_device_pct"](run) == pytest.approx(7.0)
    # nine mixers: W_in, W_out, the taps and the norm once a step, 128 rows' tails in and out
    D = 2048
    numbers = 9 * (4 * D * D + 3 * D + D)
    tails = 2 * 128 * 9 * 2 * D * 2
    least = (numbers * 2 + tails) / 819e9
    assert run.arch.shortconv_step(run.config, 128)["bytes"] == numbers * 2 + tails
    assert READ["shortconv_mixer_roofline"](run) == pytest.approx(100 * 8 * least / 0.005)
    assert 0 < READ["shortconv_mixer_roofline"](run) < 100


def test_a_trace_recorded_before_the_scope_reads_as_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    reduced = R.reduce([tuple(e) for e in recorded["events"]], recorded["window_s"])
    assert reduced["busy_s"] == pytest.approx(recorded["expect"]["busy_s"])
    older = {"decode_tokens": 3000, "decode_dispatches": 16, "short_dispatches": 0}
    for name, read in READ.items():
        assert read(cell_run(reduced, older)) is None, name
        assert read(cell_run(None, None)) is None, name
        # an architecture without the count, whatever its trace
        assert read(cell_run(reduced, older, "granite-4.0-h-micro.chat-closed")) is None, name


def test_a_share_of_the_roofline_cannot_pass_its_peak_by_the_count():
    """The least time counts every mixer's weights ONCE a step and the rows'
    tails in and out: a mixer that takes exactly that long reads 100%; bytes
    bound it at a decode step's rows (2 FLOPs a weight a row against 2 bytes)."""
    cell = cell_run(None, None)
    work = cell.arch.shortconv_step(cell.config, 128)
    assert work["flops"] / 197e12 < work["bytes"] / 819e9
    assert 0.30e9 < work["bytes"] < 0.33e9  # 33.6 MB a mixer, nine of them, 9.4 MB of tails twice


def test_the_manifest_carries_the_cell_and_its_two_metrics():
    manifest = M.load_manifest(M.ROOT)
    cell = cell_run(None, None)  # (skips on a program without the layer)
    cell = M.resolve_cell(manifest, CELL, M.ROOT)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "lfm2-8b-a1b", "longform-closed")
    assert cell.params == {"callers": 128} and cell.traffic["loop"] == "closed"
    budgets = cell.traffic["output_tokens"]  # the uniform law's 64 quantiles, equally likely
    assert budgets["law"] == "choice" and len(set(budgets["values"])) == 64
    assert (min(budgets["values"]), max(budgets["values"])) == (262, 1018)
    assert [m.name for m in cell.end_to_end] == ["tpot_p95_ms", "out_tok_s_per_chip", "setup_s"]
    named = {m.name: m for m in cell.per_layer}
    assert named["shortconv_device_pct"].moves == "tpot_p95_ms" == named[
        "shortconv_mixer_roofline"].moves
    assert named["shortconv_device_pct"].layer == "model step"
    assert named["shortconv_mixer_roofline"].layer == "kernels"
    for name in ("batch_occupancy_pct", "empty_slot_queued_pct", "kv_pages_peak_pct", "hbm_peak_gb",
                 "moe_device_pct", "moe_expert_load_ratio", "dispatch_roofline"):
        assert name in named, name
    # the decode steps' expert products are GROUPED at this shape (moe._DENSE_TO_THE_CROSSING):
    # they land in a kernel of the compiler's own name under no scope, which the
    # reader of moe_expert_roofline does not read
    assert "moe_expert_roofline" not in named
    for entry in manifest["per_layer"][-3:]:  # the two here and moe_grouped_roofline
        assert entry["workloads"] == [CELL]
