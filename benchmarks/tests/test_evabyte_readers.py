"""The cell and the four readers ISSUE 54 adds (``eva_device_pct``,
``eva_cache_roofline``, ``eva_chunk_roofline``, ``eva_cache_kept_pct``): the
manifest resolves ``evabyte.bytedoc-closed``; each reader over a reduced
trace of events with the scope paths an EVA stack's programs name and the
engine's counters; over the trace recorded on the chip before there was such
a scope or counter (``recorded_trace.json``) and a program without them (the
parent under these files), where each reads nothing and does not raise; and
the cell's rehearsal end to end on the CPU."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import trace_reduce as R

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
NAMES = ("eva_device_pct", "eva_cache_roofline", "eva_chunk_roofline", "eva_cache_kept_pct")
READ = {n: M.load_reader(n) for n in NAMES}
CELL = "evabyte.bytedoc-closed"
MS = 1_000_000
ENTRY = 2 * 32 * 128 * 2  # bytes of a key and a value, every head, one layer


def resolved(cell_name=CELL):
    try:
        return M.resolve_cell(M.load_manifest(M.ROOT), cell_name, M.ROOT)
    except M.ManifestError as e:  # these files laid over a program without the configuration
        pytest.skip(str(e))


def cell_run(reduced, counters, cell_name=CELL, chunk_size=16):
    cell = resolved(cell_name)
    return SimpleNamespace(
        trace_reduced=reduced, trace_counters=counters, counters={"window": counters or {}},
        arch=cell.arch, config=cell.config, chips=1, peaks=M.load_peaks("TPU v5 lite"),
        model_config=SimpleNamespace(chunk_size=chunk_size),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8))


def test_the_manifest_resolves_the_cell_and_its_files_state_the_cut():
    cell = resolved()
    manifest = M.load_manifest(M.ROOT)
    assert (cell.chips, cell.params["callers"], cell.traffic["loop"]) == (1, 16, "closed")
    assert cell.traffic["prompt_tokens"] == {
        "law": "lognormal", "median": 14336, "sigma": 0.35, "min": 8193, "max": 24000}
    assert cell.traffic["output_tokens"] == {
        "law": "choice", "values": [1024, 2048, 3072], "weights": [0.4, 0.4, 0.2]}
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers"] and config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 32}
    assert len(config["assumed"]) >= 3 and config["architecture"] == "evabyte-eva"
    for key in ("deployment", "precision", "hbm", "agreement", "rehearsal", "worker", "runtime"):
        assert key in config, key
    entry = next(c for c in manifest["configs"] if c["name"] == "evabyte")
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    arch, hbm = cell.arch, config["hbm"]
    assert arch.weight_bytes(config) == 2 * config["parameters"] == hbm["weights_bytes"]
    assert config["published_parameters"] == 6_488_330_240
    assert arch.state_bytes_per_token(config) == 8 * ENTRY / 16
    assert hbm["held_before_temporaries_bytes"] > 4e9  # the driver's floor for a new cell
    registered = {m.name for m in cell.per_layer}
    assert set(NAMES[:3]) <= registered
    reported = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"tpot_p95_ms", "setup_s"} <= reported
    # entered only if the cell reports the metric it moves; else recorded-only
    assert ("eva_cache_kept_pct" in registered) == ("out_tok_s_per_chip" in reported)
    step = arch.decode_step(config, 16, 16000)
    # 16 rows at 16,000: 1,665 exact + 896 pooled entries a row a layer beside the weights
    assert arch.live_entries(config, 16000) == (16000 - 7 * 2048 + 1, 7 * 128)
    assert step["bytes"] == pytest.approx(
        hbm["weights_bytes"] - 2 * 320 * 4096 + 16 * 8 * (1665 + 896) * ENTRY, rel=1e-3)


def test_the_readers_over_a_reduced_trace_of_an_eva_stack_s_scopes():
    # one dispatch of 120 ms: 8 decode steps of 16 rows and a chunk of one window
    events = [
        (DEV, R.MODULES_LINE, "jit_ragged_paged(1)", 0, 120 * MS),
        (DEV, R.OPS_LINE, "%paged_decode_attention.1", 0, 30 * MS,
         "decode_loop/while/body/eva/attention/window"),
        (DEV, R.OPS_LINE, "%paged_decode_attention.2", 30 * MS, 25 * MS,
         "decode_loop/while/body/eva/attention/summary"),
        (DEV, R.OPS_LINE, "%fusion.3", 55 * MS, 5 * MS, "decode_loop/while/body/eva/merge"),
        (DEV, R.OPS_LINE, "%fusion.4", 60 * MS, 5 * MS, "decode_loop/while/body/eva/qkv"),
        (DEV, R.OPS_LINE, "%chunk_attention.5", 65 * MS, 6 * MS, "chunk_loop/while/body/eva/attention"),
        (DEV, R.OPS_LINE, "%fusion.6", 71 * MS, 1 * MS, "chunk_loop/while/body/eva/pool"),
        (DEV, R.OPS_LINE, "%fusion.7", 72 * MS, 3 * MS, "decode_loop/kv_write/pool"),
        (DEV, R.OPS_LINE, "%fusion.8", 75 * MS, 45 * MS, "decode_loop/while/body/mlp"),
    ]
    reduced = R.reduce(events, window_s=0.12)
    exact, pooled = 8 * 16 * 8 * 1000, 8 * 16 * 8 * 896  # layers x rows x steps x entries
    pairs_w, pairs_s, chunks = 8 * 2048 * 2049 // 2, 8 * 2048 * 512, 8 * 128
    counters = {"decode_tokens": 128, "decode_dispatches": 1, "short_dispatches": 0,
                "decode_eva_window_tokens_read": exact, "decode_eva_summaries_read": pooled,
                "chunk_attn_pairs_eva_window": pairs_w, "chunk_attn_pairs_eva_summary": pairs_s,
                "eva_chunks_pooled": chunks}
    run = cell_run(reduced, counters)
    assert READ["eva_device_pct"](run) == pytest.approx(100 * 72 / 120)  # not kv_write/pool, not mlp
    work = run.arch.eva_cache_step(run.config, exact, pooled)
    assert work == {"bytes": float(ENTRY * (exact + pooled)),
                    "flops": 4.0 * 32 * 128 * (exact + pooled)}
    # bytes bound it: one query a KV head
    assert READ["eva_cache_roofline"](run) == pytest.approx(
        100 * ENTRY * (exact + pooled) / 819e9 / 0.060)
    chunk = run.arch.eva_chunk(run.config, pairs_w, pairs_s, chunks)
    assert chunk["flops"] == 4.0 * 32 * 128 * (pairs_w + pairs_s + 16 * chunks)
    assert chunk["bytes"] == 17.0 * ENTRY * chunks
    assert READ["eva_chunk_roofline"](run) == pytest.approx(100 * chunk["flops"] / 197e12 / 0.007)
    for name in NAMES[:3]:
        assert 0 < READ[name](run) < 100, name
    # 1,000 exact + 896 pooled where a global layer would hold 1,000 + 896 x 16
    assert READ["eva_cache_kept_pct"](run) == pytest.approx(100 * 1896 / (1000 + 896 * 16))


def test_a_read_that_takes_its_least_time_reads_a_hundred_and_no_more():
    """The counts hold each entry ONCE and each pair once: work at the chip's
    peak reads 100%, so a share over it would mean the count is too high."""
    run = cell_run(None, None)
    exact, pooled = 10**9, 7 * 10**8
    seconds = ENTRY * (exact + pooled) / 819e9
    at_peak = SimpleNamespace(**{**vars(run), "trace_reduced": {
        "busy_s": 100.0, "by_scope": {"decode_loop/eva/attention/window": seconds / 2,
                                      "decode_loop/eva/attention/summary": seconds / 4,
                                      "decode_loop/eva/merge": seconds / 4}},
        "trace_counters": {"decode_eva_window_tokens_read": exact,
                           "decode_eva_summaries_read": pooled}})
    assert READ["eva_cache_roofline"](at_peak) == pytest.approx(100.0)
    pairs = 10**12
    work = run.arch.eva_chunk(run.config, pairs, 0, 0)
    chunk = SimpleNamespace(**{**vars(run), "trace_reduced": {
        "busy_s": 100.0, "by_scope": {"chunk_loop/eva/attention": work["flops"] / 197e12}},
        "trace_counters": {"chunk_attn_pairs_eva_window": pairs}})
    assert READ["eva_chunk_roofline"](chunk) == pytest.approx(100.0)


def test_where_there_is_nothing_to_read_they_read_nothing():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    reduced = R.reduce([tuple(e) for e in recorded["events"]], recorded["window_s"])
    assert reduced["busy_s"] == pytest.approx(recorded["expect"]["busy_s"])
    older = {"decode_tokens": 3000, "decode_dispatches": 16, "short_dispatches": 0}
    counted = {**older, "decode_eva_window_tokens_read": 10**6, "decode_eva_summaries_read": 10**6,
               "chunk_attn_pairs_eva_window": 10**6}
    scoped = {"busy_s": 1.0, "by_scope": {"decode_loop/eva/attention/window": 0.1,
                                          "chunk_loop/eva/attention": 0.1}}
    for name, read in READ.items():
        assert read(cell_run(reduced, older)) is None, name  # a program before the counters
        assert read(cell_run(None, None)) is None, name  # an untraced run, no window yet
        # another cell's architecture has no such count, its program no such scope or counter
        other = cell_run(reduced, older, "mistral-7b-v0.3-int8.batch-closed", chunk_size=0)
        assert read(other) is None, name
    # counters without the scopes; scopes without the counters; an architecture without the count
    for name in ("eva_cache_roofline", "eva_chunk_roofline"):
        assert READ[name](cell_run(reduced, counted)) is None, name
        assert READ[name](cell_run(scoped, older)) is None, name
        assert READ[name](cell_run(scoped, counted, "mistral-7b-v0.3-int8.batch-closed")) is None
        assert READ[name](cell_run(scoped, counted)) is not None, name
    assert READ["eva_device_pct"](cell_run(scoped, None)) == pytest.approx(20.0)
    assert READ["eva_cache_kept_pct"](cell_run(None, counted, chunk_size=0)) is None


def test_the_new_cell_s_rehearsal_ends_on_the_cpu():
    """``benchmarks/run.py --workload evabyte.bytedoc-closed --rehearse``: the
    benchmark's one command end to end on the CPU at the file's toy widths
    (engine, broker, worker, agreement with what the rows left behind,
    warm-up, ramp-in, window, drain); its last line names ``platform: cpu``
    and carries no metric."""
    resolved()
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(M.ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "2147483777", "--seconds", "4", "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=M.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    last = lines[-1]
    assert last["platform"] == "cpu" and last["rehearsal"] is True and "metrics" not in last
    assert last["attempted"] >= 1 and last["failed"] == 0 and last["correct"] is True
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["compiles_in_window"] == 0 and window["planned_equals_realised"]
    assert window["counters"]["decode_eva_summaries_read"] > 0
    reference = next(l for l in lines if l.get("phase") == "reference")
    assert len(reference["summary_error_by_layer"]) == 3 and reference["over_their_limit"] == []
    # bfloat16 pages fed by a bfloat16 stream: rounding, far under any fault's size
    assert reference["summary_error"] < 0.02 < reference["summary_error_if_uniform_weights"]
    assert sorted(reference["slots"]) == list(range(8))
