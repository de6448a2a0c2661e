"""The reader of the grouped expert products' share of their roofline
(``moe_grouped_roofline``, PR 44) over reduced traces: the kernel the TPU
compiler runs a grouped product as stands under NO scope, so it is taken by
its name; the least time is the decode steps' (where the program's own rule
runs them grouped) and the grouped chunks'.  Over the trace recorded before
there was such a kernel, over a cell without routed experts and over a cell
whose decode steps run dense, it reads nothing or the chunks alone, and
never raises."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import trace_reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
READ = M.load_reader("moe_grouped_roofline")
CELL = "lfm2-8b-a1b.longform-closed"
MS = 1_000_000
EXPERT = 3 * 2048 * 1792  # one expert's numbers
GATE = 2048 * 32


def cell_run(reduced, counters, cell_name=CELL):
    try:
        cell = M.resolve_cell(M.load_manifest(M.ROOT), cell_name, M.ROOT)
        model_config, runtime = cell.arch.model(cell.config, False)
    except M.ManifestError as e:  # these files laid over a program without the layer
        pytest.skip(str(e))
    return SimpleNamespace(
        trace_reduced=reduced, trace_counters=counters, counters={"window": counters or {}},
        arch=cell.arch, config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
        model_config=model_config, runtime=runtime)


def dispatch(ragged_ms: int):
    # one ragged dispatch: 8 decode steps of 128 rows and a chunk; the grouped products of
    # both in the compiler's kernel, twice by name (the number after the dot differs)
    return R.reduce([
        (DEV, R.MODULES_LINE, "jit_ragged_paged(1)", 0, (ragged_ms + 20) * MS),
        (DEV, R.OPS_LINE, "%fusion.1", 0, 10 * MS, "decode_loop/mlp/moe/router"),
        (DEV, R.OPS_LINE, "%ragged-dot-none.6", 10 * MS, (ragged_ms - 10) * MS, ""),
        (DEV, R.OPS_LINE, "%ragged-dot-none.7", ragged_ms * MS, 10 * MS, ""),
        (DEV, R.OPS_LINE, "%copy.8", (ragged_ms + 10) * MS, 10 * MS, ""),
    ], window_s=0.5)


def test_the_reader_over_a_reduced_trace_of_the_new_cell():
    reduced = dispatch(300)
    steps, layers = 8, 10
    counters = {"decode_tokens": steps * 120, "decode_dispatches": 1, "short_dispatches": 0,
                "moe_experts_hit": 32 * layers * steps, "prefill_tokens": 1500,
                "moe_grouped_chunks": 1, "moe_dense_chunks": 0}
    run = cell_run(reduced, counters)
    assert run.model_config.n_moe_layers == layers and run.runtime.max_batch_size == 128
    # a step reads all 32 experts of a layer and the gate: bytes bound it at 120 rows, and
    # at a chunk's 1,500 tokens too (2 x 1,500 x 4 experts' FLOPs against 32 experts' bytes)
    a_layer = (32 * EXPERT + GATE) * 2 / 819e9
    assert 2 * 1500 * (4 * EXPERT + GATE) / 197e12 < a_layer
    least = (steps + 1) * layers * a_layer
    assert READ(run) == pytest.approx(100 * least / 0.3, rel=1e-3)
    assert 0 < READ(run) < 100
    # the same work in a kernel that took exactly the least time reads 100%, never more
    exact = dict(reduced, own_by_op={"(unscoped) ragged-dot-none": least})
    assert READ(cell_run(exact, counters)) == pytest.approx(100.0, rel=1e-3)
    # no chunk rode along: the decode steps alone
    alone = {**counters, "prefill_tokens": 0, "moe_grouped_chunks": 0}
    assert READ(cell_run(reduced, alone)) == pytest.approx(
        100 * steps * layers * a_layer / 0.3, rel=1e-3)


def test_where_there_is_nothing_to_read_it_reads_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    older = R.reduce([tuple(e) for e in recorded["events"]], recorded["window_s"])
    counters = {"decode_tokens": 3000, "decode_dispatches": 16, "short_dispatches": 0}
    assert READ(cell_run(older, counters)) is None  # no such kernel in the trace
    assert READ(cell_run(None, None)) is None  # an untraced run
    # no routed experts, whatever the trace
    assert READ(cell_run(dispatch(300), counters, "granite-4.0-h-micro.chat-closed")) is None
    # a program without the counters (the kernel ran, nothing says what work it did)
    assert READ(cell_run(dispatch(300), counters)) is None


def test_a_cell_whose_decode_steps_run_dense_counts_its_grouped_chunks_alone():
    """Kimi's shape crosses at 1,536 tokens: its 128 rows run dense, and only a
    wide chunk's products reach the grouped kernel."""
    counters = {"decode_tokens": 8 * 100, "decode_dispatches": 1, "short_dispatches": 0,
                "moe_experts_hit": 40 * 26 * 8, "prefill_tokens": 4000,
                "moe_grouped_chunks": 1, "moe_dense_chunks": 1}
    run = cell_run(dispatch(100), counters, "kimi-vl-a3b-instruct.history-closed")
    layers = run.model_config.n_moe_layers
    tokens = 4000 / 2  # the counters' prompt tokens over BOTH chunks
    work = run.arch.expert_layer_step(run.config, tokens, run.arch.experts_hit(run.config, tokens))
    least = layers * max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert READ(run) == pytest.approx(100 * least / 0.1, rel=1e-3)
