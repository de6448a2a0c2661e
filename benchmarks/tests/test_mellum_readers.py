"""The two readers ISSUE 47 adds, ``global_cache_roofline`` and
``chunk_padding_pct``: over a reduced trace of events with the scope paths
the window stack's decode program names (``decode_loop/.../attention/global``)
and the engine's counters; and over the trace recorded on the chip before
there was such a scope or such a counter (``recorded_trace.json``) and a
program without them (the parent under these files), where each reads
nothing and does not raise."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import trace_reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
READ = {n: M.load_reader(n) for n in ("global_cache_roofline", "chunk_padding_pct")}
CELL = "mellum2-12b-a2.5b-instruct.mixed-lengths-closed"
MS = 1_000_000


def cell_run(reduced, counters, cell_name=CELL):
    try:
        cell = M.resolve_cell(M.load_manifest(M.ROOT), cell_name, M.ROOT)
    except M.ManifestError as e:  # these files laid over a program without the configuration
        pytest.skip(str(e))
    return SimpleNamespace(
        trace_reduced=reduced, trace_counters=counters, counters={"window": counters or {}},
        arch=cell.arch, config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
        runtime=SimpleNamespace(decode_steps_per_dispatch=4))


def test_the_readers_over_a_reduced_trace_of_the_window_stack_s_scopes():
    # one dispatch of 60 ms: 4 decode steps of 64 rows at 3,000 tokens each and a chunk
    events = [
        (DEV, R.MODULES_LINE, "jit_ragged_paged(1)", 0, 60 * MS),
        (DEV, R.OPS_LINE, "%paged_decode_attention.1", 0, 4 * MS,
         "decode_loop/while/body/attention/global"),
        (DEV, R.OPS_LINE, "%fusion.2", 4 * MS, 1 * MS, "decode_loop/while/body/attention/global"),
        (DEV, R.OPS_LINE, "%paged_decode_attention.3", 5 * MS, 6 * MS,
         "decode_loop/while/body/attention/window"),
        (DEV, R.OPS_LINE, "%chunk_attention.4", 11 * MS, 9 * MS, "chunk_loop/attention/global"),
        (DEV, R.OPS_LINE, "%fusion.5", 20 * MS, 1 * MS, "decode_loop/rope/global"),
        (DEV, R.OPS_LINE, "%fusion.6", 21 * MS, 39 * MS, "decode_loop/mlp/moe/experts"),
    ]
    reduced = R.reduce(events, window_s=0.06)
    global_tokens = 2 * 64 * 3000 * 4  # global layers x rows x len x steps
    counters = {"decode_tokens": 4 * 64, "decode_dispatches": 1, "short_dispatches": 0,
                "decode_global_tokens_read": global_tokens,
                "chunk_tokens": 2048, "chunk_tokens_padding": 1024}
    run = cell_run(reduced, counters)
    work = run.arch.global_layers_step(run.config, 256, global_tokens)
    assert work == {"bytes": 2048.0 * global_tokens, "flops": 4.0 * 32 * 128 * global_tokens}
    # bytes bound it: 8 query heads a KV head at one query; the chunk's and the window
    # layers' seconds and the rope table's are not the read's
    least = 2048 * global_tokens / 819e9
    assert READ["global_cache_roofline"](run) == pytest.approx(100 * least / 0.005)
    assert 0 < READ["global_cache_roofline"](run) < 100
    assert READ["chunk_padding_pct"](run) == pytest.approx(50.0)
    slower = cell_run(R.reduce(events + [
        (DEV, R.OPS_LINE, "%fusion.7", 60 * MS, 5 * MS, "decode_loop/attention/global")], 0.065),
        counters)
    assert READ["global_cache_roofline"](slower) == pytest.approx(100 * least / 0.010)


def test_a_read_that_takes_its_least_time_reads_a_hundred_and_no_more():
    """The count holds each key and value ONCE: a read at the chip's peak
    stream reads 100%, so a share over it would mean the count is too high."""
    run = cell_run(None, None)
    tokens = 2 * 64 * 8000 * 4
    seconds = 2048 * tokens / 819e9
    reduced = {"busy_s": 1.0, "by_scope": {"decode_loop/attention/global": seconds}}
    full = SimpleNamespace(**{**vars(run), "trace_reduced": reduced, "trace_counters": {
        "decode_tokens": 256, "decode_global_tokens_read": tokens}})
    assert READ["global_cache_roofline"](full) == pytest.approx(100.0)


def test_where_there_is_nothing_to_read_they_read_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    reduced = R.reduce([tuple(e) for e in recorded["events"]], recorded["window_s"])
    assert reduced["busy_s"] == pytest.approx(recorded["expect"]["busy_s"])
    older = {"decode_tokens": 3000, "decode_dispatches": 16, "short_dispatches": 0}
    for name, read in READ.items():
        assert read(cell_run(reduced, older)) is None, name  # a program before the counters
        assert read(cell_run(None, None)) is None, name  # an untraced run, no window yet
    # a window without a chunk; a trace without the scope; an architecture without the count
    assert READ["chunk_padding_pct"](cell_run(None, {"chunk_tokens": 0})) is None
    counted = {**older, "decode_global_tokens_read": 10**6}
    assert READ["global_cache_roofline"](cell_run(reduced, counted)) is None
    scoped = {"busy_s": 1.0, "by_scope": {"decode_loop/attention/global": 0.1}}
    assert READ["global_cache_roofline"](
        cell_run(scoped, counted, "command-a-plus-05-2026.longdoc-closed")) is None
    assert READ["global_cache_roofline"](cell_run(scoped, counted)) is not None
