"""The three readers of the window layers (ISSUE 38) over reduced traces:
events with the scope paths the new cell's program names, as the chip's
traced run of ``command-a-plus-05-2026.longdoc-closed`` showed them (PR 38),
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
READ = {n: M.load_reader(n) for n in
        ("swa_device_pct", "swa_cache_roofline", "kv_pages_given_back_pct")}
MS = 1_000_000


def cell_run(reduced, counters):
    cell = M.resolve_cell(M.load_manifest(M.ROOT), "command-a-plus-05-2026.longdoc-closed", M.ROOT)
    return SimpleNamespace(
        trace_reduced=reduced, trace_counters=counters, counters={"window": counters or {}},
        arch=cell.arch, config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
        model_config=cell.arch.model(cell.config, False)[0])


def test_the_readers_over_a_reduced_trace_of_the_new_cell_s_scopes():
    # one ragged dispatch of 100 ms: the decode steps' window read 16 ms, the chunk's
    # window layers 20 ms, both kinds' global attention 11 ms, the rest products
    events = [
        (DEV, R.MODULES_LINE, "jit_ragged_paged(1)", 0, 100 * MS),
        (DEV, R.OPS_LINE, "%paged_decode_attention.1", 0, 12 * MS, "decode_loop/attention/window"),
        (DEV, R.OPS_LINE, "%fusion.2", 12 * MS, 4 * MS, "decode_loop/attention/window"),
        (DEV, R.OPS_LINE, "%paged_decode_attention.3", 16 * MS, 6 * MS, "decode_loop/attention/global"),
        (DEV, R.OPS_LINE, "%fusion.4", 22 * MS, 20 * MS, "chunk_loop/attention/window"),
        (DEV, R.OPS_LINE, "%fusion.5", 42 * MS, 5 * MS, "chunk_loop/attention/global"),
        (DEV, R.OPS_LINE, "%fusion.6", 47 * MS, 53 * MS, "decode_loop/mlp/moe/experts"),
    ]
    reduced = R.reduce(events, window_s=0.1)
    assert reduced["by_scope"]["decode_loop/attention/window"] == pytest.approx(0.016)
    # 8 steps x 20 rows: every row past the window reads 4,096 keys of 3 layers a step
    counters = {"decode_tokens": 160, "decode_window_tokens_read": 8 * 20 * 4096 * 3,
                "decode_global_tokens_read": 8 * 20 * 9000 * 1}
    run = cell_run(reduced, counters)
    assert READ["swa_device_pct"](run) == pytest.approx(36.0)
    least = 8 * 20 * 4096 * 3 * 4096 / 819e9  # the keys' and values' bytes over the HBM peak
    assert READ["swa_cache_roofline"](run) == pytest.approx(100 * least / 0.016)
    assert 0 < READ["swa_cache_roofline"](run) < 100
    assert READ["kv_pages_given_back_pct"](run) == pytest.approx(
        100 * (1 - (9000 + 3 * 4096) / (4 * 9000)))


def test_a_trace_recorded_before_the_scopes_reads_as_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    reduced = R.reduce([tuple(e) for e in recorded["events"]], recorded["window_s"])
    assert reduced["busy_s"] == pytest.approx(recorded["expect"]["busy_s"])
    older = {"decode_tokens": 3000, "decode_dispatches": 16}
    for name, read in READ.items():
        assert read(cell_run(reduced, older)) is None, name
        assert read(cell_run(None, None)) is None, name


def test_a_share_of_a_roofline_cannot_pass_its_peak_by_the_count():
    """The least time counts each key and value once a step a layer, in the
    cache's own type: a read that takes exactly that long reads 100%."""
    tokens = 3 * 32 * 4096 * 8.0
    cell = cell_run(None, None)
    work = cell.arch.window_layers_step(cell.config, 32, tokens)
    assert work["bytes"] == tokens * 4096  # 8 KV heads x 128 x K and V x 2 B a token a layer
    assert work["flops"] / 197e12 < work["bytes"] / 819e9  # bytes bound at one query a row
