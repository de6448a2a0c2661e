#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python3 chip_smoke.py              # one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4    # the tensor-parallel path, four chips

One process (a chip belongs to one process).  Every phase prints one JSON
object; any failing phase makes the exit code non-zero.  The LAST line of
stdout is ``{"ok": true, "device": {...}}`` only when every phase passed on
a TPU — without one the script exits non-zero and prints no result.

Default phases (one chip, TinyLlama-1.1B at its published width and depth,
bf16, random weights from ``--seed``):

- serve  — Worker/Agent/JaxLocalModelClient behind the in-repo Kafka-wire
  broker (built here from native/*.cpp), Client.execute()/start(): single
  requests, a token stream, a concurrent burst, an abandoned stream; then
  the engine must be drained (all slots and pages back).
- agree  — the engine's greedy tokens vs a plain full-recompute
  ``model.forward`` wherever the reference's top-2 logit margin exceeds
  ``--margin``.
- pallas — the same engine configuration with ``attention_impl="pallas"``
  asked for by name, same check.
- serve-wide — heads of 128 (Llama-3-8B's widths, 4 layers) under
  ``attention_impl="auto"``; same checks.
- hybrid-xla, hybrid — one period of granite-4.0-h-micro (nine Mamba-2
  layers around one attention layer, published widths) under
  ``attention_impl="xla"`` and under ``"auto"``: the same agreement check
  against a full recompute, so the positions the margin decides are equal
  in the two; under ``auto`` BOTH kernels must have been built, compiled
  (the paged decode read and the SSM step's one pass over the state).
- kernels — the paged decode read in place, compiled, against the XLA
  gather it replaces at Mistral-7B's and granite-4.0-h-micro's widths,
  batch and window.

In every serving phase on a chip (TinyLlama's heads of 64, two positions a
lane row, under ``auto`` and by name; heads of 128 under ``auto``) the
paged decode read must have resolved to ``pallas`` and ``KERNEL_TRACES``
must hold that kernel alone, compiled by Mosaic: never interpreted, and no
other kernel built (a dense model has no other to build).

``--rehearse`` shrinks everything and uses interpret mode on the CPU; it
can never print ``"ok": true``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import functools
import gc
import json
import os
import subprocess
import sys
import time
from typing import NoReturn

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

BUILD_DIR = os.path.join(ROOT, ".build", "native")  # git-ignored
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]  # seconds JAX spent tracing, lowering and compiling


def _on_duration(event: str, secs: float, **_: object) -> None:
    if event in COMPILE_EVENTS:
        _compile_s[0] += secs


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def die(code: int, message: str) -> NoReturn:
    print(f"chip_smoke: {message}", file=sys.stderr, flush=True)
    sys.exit(code)


# --------------------------------------------------------------------------- #
# set-up helpers
# --------------------------------------------------------------------------- #


def build_broker() -> str:
    """Build kafkad from native/*.cpp into a git-ignored directory and point
    the spawn helper at it — the run depends on no committed binary."""
    subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native"), f"BIN={BUILD_DIR}",
         f"{BUILD_DIR}/kafkad", f"{BUILD_DIR}/libcrc32c.so"],
        check=True, stdout=subprocess.DEVNULL,
    )
    os.environ["CALFKIT_KAFKAD"] = os.path.join(BUILD_DIR, "kafkad")
    os.environ["CALFKIT_CRC32C"] = os.path.join(BUILD_DIR, "libcrc32c.so")
    return os.environ["CALFKIT_KAFKAD"]


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def memory(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def prompts_for(vocab: int, lengths: tuple[int, ...], seed: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(3, vocab, size=n)] for n in lengths]


# --------------------------------------------------------------------------- #
# serve: the normal entry points over the Kafka wire path
# --------------------------------------------------------------------------- #


async def settle_drained(engine, free_pages: int, timeout: float = 60.0) -> None:
    """Bounded wait for in-flight retirement, then THE no-leak oracle."""
    from calfkit_tpu.sim.chaos import assert_engine_drained

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            assert_engine_drained(engine, free_pages)
            return
        except AssertionError:
            await asyncio.sleep(0.05)
    assert_engine_drained(engine, free_pages)


async def serve_phase(name: str, model, *, singles: int, burst: int) -> dict:
    """Drive ``model`` (a JaxLocalModelClient) through Worker + Client over
    a freshly spawned kafkad.  Raises on any failure — no other transport is
    tried."""
    from calfkit_tpu.client import Client
    from calfkit_tpu.mesh.kafka_wire import KafkaWireMesh, spawn_kafkad
    from calfkit_tpu.nodes import Agent
    from calfkit_tpu.worker import Worker

    def is_token(event) -> bool:
        return getattr(getattr(event, "step", None), "kind", "") == "token"

    t_phase = time.perf_counter()
    c_phase = _compile_s[0]
    proc = spawn_kafkad(0)
    try:
        url = f"127.0.0.1:{proc.kafkad_port}"
        mesh, client_mesh = KafkaWireMesh(url), KafkaWireMesh(url)
        await client_mesh.start()
        try:
            t0 = time.perf_counter()
            await model.start()  # param init + placement + engine thread
            start_s = time.perf_counter() - t0
            engine = model._engine
            free_pages = engine._page_alloc.free_pages
            agent = Agent("smoke_agent", model=model, stream_tokens=True)
            async with Worker([agent], mesh=mesh, owns_transport=True):
                client = Client.connect(client_mesh)
                gateway = client.agent("smoke_agent")

                # a handful of single requests (the first pays the compiles)
                single_s = []
                for i in range(singles):
                    t0 = time.perf_counter()
                    result = await gateway.execute(
                        f"single request {i} " + "x" * (17 * i), timeout=900
                    )
                    single_s.append(round(time.perf_counter() - t0, 3))
                    assert str(result.output).strip(), "empty single output"

                # one token-streaming request, read to its end
                handle = await gateway.start("stream me some tokens", timeout=900)
                streamed = 0
                async for event in handle.stream():
                    streamed += is_token(event)
                result = await handle.result(timeout=900)
                assert streamed > 0, "no token step streamed"
                assert str(result.output).strip(), "empty streamed output"

                # a concurrent burst wider than the batch: continuous
                # batching fills every slot and retired slots are reused
                before = engine.stats.decode_tokens
                t0 = time.perf_counter()
                results = await asyncio.gather(*[
                    gateway.execute(
                        f"burst {i} " + "y" * (11 * (i % 7)), timeout=900
                    )
                    for i in range(burst)
                ])
                burst_s = time.perf_counter() - t0
                assert all(str(r.output).strip() for r in results), (
                    "a burst request returned nothing"
                )
                burst_tokens = engine.stats.decode_tokens - before

                # one stream abandoned mid-way: the cancel must reach the
                # engine and its slot and pages must come back
                handle = await gateway.start(
                    "abandon this one " + "z" * 40, timeout=900
                )
                seen = 0
                async for event in handle.stream():
                    seen += is_token(event)
                    if seen >= 2:
                        break
                await handle.cancel()
                await settle_drained(engine, free_pages)
                await client.close()
        finally:
            await client_mesh.stop()
    finally:
        proc.terminate()
        with contextlib.suppress(Exception):
            proc.wait(timeout=5)

    stats = engine.stats
    slots = engine.runtime.max_batch_size
    assert burst <= slots or stats.mean_occupancy > 0, "no batching observed"
    compile_s = _compile_s[0] - c_phase
    wall_s = time.perf_counter() - t_phase
    return {
        "phase": name,
        "ok": True,
        "transport": "kafkad-wire",
        "model": engine.config.name,
        "params": engine.config.param_count,
        "n_layers": engine.config.n_layers,
        "quantization": engine.runtime.quantization,
        "tp": engine.runtime.tp,
        "attention_impl": engine._attn_impl,  # of the paged decode read
        "engine_start_s": round(start_s, 2),
        "compile_s": round(compile_s, 2),
        "wall_s": round(wall_s, 2),
        "serving_s": round(max(0.0, wall_s - compile_s), 2),
        "single_request_s": single_s,
        "streamed_token_steps": streamed,
        "burst": {
            "requests": burst, "slots": slots, "seconds": round(burst_s, 3),
            "decode_tokens": burst_tokens,
            "slot_reuse": burst > slots,
        },
        "abandoned_after_token_steps": seen,
        "drained": {"free_slots": len(engine._free),
                    "free_pages": engine._page_alloc.free_pages},
        "tokens_produced": stats.decode_tokens,
        "decode_dispatches": stats.decode_dispatches,
        "unified_dispatches": stats.unified_dispatches,
        "mean_batch_occupancy": round(stats.mean_occupancy, 3),
    }


# --------------------------------------------------------------------------- #
# agree: engine greedy tokens vs a plain full-recompute forward
# --------------------------------------------------------------------------- #


async def greedy_tokens(engine, prompts: list[list[int]], n: int) -> list[list[int]]:
    async def one(prompt):
        return [t async for t in engine.generate(prompt, max_new_tokens=n)]

    return list(await asyncio.gather(*[one(p) for p in prompts]))


@functools.lru_cache(maxsize=None)
def reference_fn(config):
    """ONE plain ``model.forward`` (XLA attention, no engine, no cache reuse)
    over a whole padded sequence → (argmax, top-2 margin) per position.
    Cached per config so both agreement phases share one compile."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M

    @jax.jit
    def ref(params, tokens, n):  # tokens [1, P]
        P = tokens.shape[1]
        cache = M.make_empty_cache(config, 1, P)
        pos = jnp.arange(P, dtype=jnp.int32)[None]
        hybrid = {}
        if config.recurrent:  # zero state in, the padding moves none
            from calfkit_tpu.inference.mamba import make_recurrent_state

            hybrid = {"state": make_recurrent_state(config, 1), "n_valid": n[None]}
        logits, *_ = M.forward(params, config, tokens, pos, cache, n[None], **hybrid)
        top2 = jax.lax.top_k(logits[0].astype(jnp.float32), 2)
        return top2[1][:, 0], top2[0][:, 0] - top2[0][:, 1]

    return ref


def reference_check(
    params, config, prompts, outputs, margin: float, pad_to: int
) -> dict:
    """Teacher-forced agreement: the reference recomputes every position of
    prompt + generated tokens; wherever its top-2 logit margin exceeds
    ``margin`` the engine's token must be its argmax."""
    import jax.numpy as jnp
    import numpy as np

    ref = reference_fn(config)
    compared = equal = total = 0
    for prompt, out in zip(prompts, outputs):
        seq = prompt + out
        tokens = np.zeros((1, pad_to), np.int32)
        tokens[0, : len(seq)] = seq
        arg, gap = ref(params, jnp.asarray(tokens), jnp.int32(len(seq)))
        arg, gap = np.asarray(arg), np.asarray(gap)
        assert np.isfinite(gap[: len(seq)]).all(), "non-finite reference logits"
        for i, tok in enumerate(out):
            at = len(prompt) - 1 + i  # position whose logits chose out[i]
            total += 1
            if gap[at] > margin:
                compared += 1
                equal += int(arg[at] == tok)
    return {"positions": total, "compared": compared, "equal": equal,
            "margin": margin}


def count_equal(xs: list[list[int]], ys: list[list[int]]) -> int:
    return sum(int(a == b) for x, y in zip(xs, ys) for a, b in zip(x, y))


async def agree_phase(name, engine, prompts, new_tokens, margin, pad_to) -> tuple[dict, list]:
    t0, c0 = time.perf_counter(), _compile_s[0]
    outputs = await greedy_tokens(engine, prompts, new_tokens)
    assert all(len(o) == new_tokens for o in outputs), "short generation"
    check = reference_check(
        engine.params, engine.config, prompts, outputs, margin, pad_to
    )
    ok = check["compared"] > 0 and check["equal"] == check["compared"]
    return {
        "phase": name, "ok": ok, **check,
        "prompt_lens": [len(p) for p in prompts],
        "attention_impl": engine._attn_impl,  # of the paged decode read
        "compile_s": round(_compile_s[0] - c0, 2),
        "seconds": round(time.perf_counter() - t0, 2),
    }, outputs


# --------------------------------------------------------------------------- #
# kernels: the paged decode read, compiled, against its XLA reference
# --------------------------------------------------------------------------- #


def kernels_phase(seed: int, interpret: bool) -> dict:
    """The paged decode read in place against the XLA gather it replaces
    (``masked_attention_source`` over ``gather_window_paged``), outside any
    engine, at the two benchmark configurations' widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference import pallas_attention as PA

    t0, c0 = time.perf_counter(), _compile_s[0]
    page = 32 if interpret else 64  # heads of 64 in bf16: pages of 32 or more
    bf = jnp.bfloat16
    rng = np.random.default_rng(seed)
    worst: dict[str, float] = {}

    def close(name, got, want):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
        worst[name] = max(worst.get(name, 0.0), err)

    # the paged decode read in place at Mistral-7B's widths (32 rows) and
    # granite-4.0-h-micro's (64 rows, heads of 64: the pool stored two
    # positions a row, model.positions_per_row): the 2048 window, row
    # lengths around page edges, a row that reads nothing
    for name, (K, G, hd, rows) in {
        "": (8, 4, 128, 32), "/hd64": (8, 4, 64, 64),
    }.items():
        B, W = (4, 128) if interpret else (rows, 2048)
        wpages = W // page
        key = jax.random.split(jax.random.key(seed + 7), 3)
        f = M.positions_per_row(hd, page, bf)  # the pool as make_page_pool stores it
        stored = (2, 1 + B * wpages, K, page // f, f * hd)
        pool_k = jax.random.normal(key[0], stored, bf)
        pool_v = jax.random.normal(key[1], stored, bf)
        tables = jnp.asarray(
            1 + np.arange(B * wpages, dtype=np.int32).reshape(B, wpages))
        lens = rng.integers(1, W, size=B)
        lens[:4] = (0, page - 1, page + 1, W)
        lens = jnp.asarray(lens, jnp.int32)
        q = jax.random.normal(key[2], (B, K, G, hd), bf)
        o, m, z = PA.paged_decode_attention_pallas(
            q, pool_k, pool_v, jnp.int32(1), tables, lens, wpages=wpages,
            interpret=interpret)
        o_x, m_x, z_x = M.masked_attention_source(
            q, M.gather_window_paged(pool_k[1], tables, wpages, hd),
            M.gather_window_paged(pool_v[1], tables, wpages, hd),
            jnp.arange(W)[None, :] < lens[:, None])
        close(f"paged-decode-in-place{name}",
              o / jnp.maximum(z[..., None], 1e-30),
              o_x / jnp.maximum(z_x, 1e-30))
        close(f"paged-decode-in-place{name}/m", m, m_x[..., 0])
    tol = 3e-2  # bf16 inputs and outputs, values O(1)
    return {
        "phase": "kernels", "ok": all(e < tol for e in worst.values()),
        "max_abs_err_vs_xla": {k: round(v, 5) for k, v in worst.items()},
        "tolerance": tol,
        "widths": ["mistral-7b", "granite-4.0-h-micro"],
        "mode": "interpreted" if interpret else "compiled",
        "compile_s": round(_compile_s[0] - c0, 2),
        "seconds": round(time.perf_counter() - t0, 2),
    }


# --------------------------------------------------------------------------- #
# the runs
# --------------------------------------------------------------------------- #


def sizes(rehearse: bool) -> dict:
    if rehearse:
        return dict(preset="debug", seq=256, chunk=32, page=32, bs=4, burst=6,
                    singles=2, new_tokens=8, agree_lens=(5, 20, 40),
                    agree_new=8, pad_to=64)
    return dict(preset="tinyllama-1.1b", seq=1024, chunk=128, page=64, bs=16,
                burst=24, singles=4, new_tokens=24, agree_lens=(12, 70, 200),
                agree_new=48, pad_to=256)


def narrow_config(sz: dict, rehearse: bool):
    """Heads of 64, two positions a lane row in the kernel's view of the
    pool: TinyLlama-1.1B as published (the debug preset widened, in a
    rehearsal: its own heads of 16 are outside the kernel's rule)."""
    from dataclasses import replace

    from calfkit_tpu.inference.config import preset

    config = preset(sz["preset"], max_seq_len=sz["seq"])
    return replace(config, name="debug-64", d_model=256) if rehearse else config


def wide_config(sz: dict, rehearse: bool):
    """Heads of 128, the shape the paged decode kernel reads in place:
    Llama-3-8B's widths cut to 4 layers (the debug preset widened, in a
    rehearsal)."""
    from dataclasses import replace

    from calfkit_tpu.inference.config import preset

    if rehearse:
        return replace(preset("debug", max_seq_len=sz["seq"]), name="debug-wide",
                       d_model=256, n_heads=2, n_kv_heads=1)
    return replace(preset("llama-3-8b", max_seq_len=sz["seq"]),
                   name="llama-3-8b/4-layers", n_layers=4)


def serving_runtime(sz: dict, impl: str, **kw):
    """The serving defaults the bench uses: paged KV, chunked prefill (the
    ragged-wave substrate), overlap dispatch and ragged waves on."""
    from calfkit_tpu.inference.config import RuntimeConfig

    return RuntimeConfig(
        max_batch_size=sz["bs"], max_seq_len=sz["seq"],
        prefill_chunk=sz["chunk"], page_size=sz["page"],
        kv_layout="paged", chunked_prefill=True, attention_impl=impl, **kw,
    )


def hybrid_config(sz: dict, rehearse: bool):
    """One period of granite-4.0-h-micro's stack at its published widths
    (in a rehearsal a toy inside both kernels' rules: attention heads of
    64, a float32 state of 32 heads of 8 in two groups, d_state 128)."""
    from dataclasses import replace

    from calfkit_tpu.inference.config import ModelConfig, preset

    if rehearse:
        return ModelConfig(
            name="debug-hybrid", vocab_size=256, d_model=256, n_layers=3, n_heads=4,
            n_kv_heads=2, d_ff=128, layer_types=("mamba", "mamba", "attention"),
            mamba_n_heads=32, mamba_d_head=8, mamba_d_state=128, mamba_n_groups=2,
            mamba_d_conv=4, mamba_chunk_size=8, dtype="float32", position_embedding="none",
            attention_multiplier=0.125, logits_scaling=8.0, max_seq_len=sz["seq"],
        )
    base = preset("granite-4.0-h-micro", max_seq_len=sz["seq"])
    period = len(base.layer_period)
    return replace(base, name=base.name + "/one-period", n_layers=period,
                   layer_types=base.layer_types[:period])


async def run_hybrid(args, sz: dict) -> bool:
    """The hybrid stack through the engine under "xla" and under the
    kernels: each agrees with the full recompute wherever the margin
    decides (so the decided positions are equal in the two), and under the
    kernels both were built as asked."""
    import jax

    from calfkit_tpu.inference import pallas_attention as PA
    from calfkit_tpu.inference.engine import InferenceEngine
    from calfkit_tpu.inference.pallas_ssm import ssm_step_pallas

    config = hybrid_config(sz, args.rehearse)
    # a CPU has no "auto" that selects a kernel: the rehearsal asks by name
    resolved, want = ("pallas_interpret", "interpreted") if args.rehearse else ("pallas", "compiled")
    kernels = resolved if args.rehearse else "auto"
    prompts = prompts_for(config.vocab_size, sz["agree_lens"], args.seed)
    ok, xla_outputs = True, None
    for phase, impl in (("hybrid-xla", "xla"), ("hybrid", kernels)):
        # each entry point is a jit of its own, traced once a process a shape
        PA.paged_decode_attention_pallas.clear_cache()
        ssm_step_pallas.clear_cache()
        PA.KERNEL_TRACES.clear()
        engine = InferenceEngine(config, serving_runtime(sz, impl), seed=args.seed)
        await engine.start()
        try:
            row, outputs = await agree_phase(
                phase, engine, prompts, sz["agree_new"],
                args.margin / config.logits_scaling, sz["pad_to"],
            )
        finally:
            await engine.stop()
        row["ssm_impl"] = engine._ssm_impl
        row["kernel_traces"] = {f"{k}:{mode}": n for (k, mode), n in PA.KERNEL_TRACES.items()}
        if impl == "xla":
            xla_outputs = outputs
            built_ok = not row["kernel_traces"] and engine._ssm_impl == "xla"
        else:
            row["equal_to_xla_engine"] = count_equal(xla_outputs, outputs)
            built_ok = (
                engine._ssm_impl == resolved
                and set(row["kernel_traces"]) == {f"paged_decode:{want}", f"ssm_step:{want}"}
            )
        row["kernels_built_as_asked"] = built_ok
        row["ok"] = row["ok"] and built_ok
        row["memory"] = memory(jax.devices()[:1])
        emit(row)
        ok = ok and row["ok"]
        del engine
        gc.collect()
    return ok


async def run_one_chip(args, sz: dict) -> bool:
    import jax

    from calfkit_tpu.inference import pallas_attention as PA
    from calfkit_tpu.inference.client import JaxLocalModelClient
    from calfkit_tpu.inference.tokenizer import IdTokenizer

    config = narrow_config(sz, args.rehearse)
    pallas = "pallas_interpret" if args.rehearse else "pallas"
    ok = True
    auto_outputs = None
    want = "interpreted" if args.rehearse else "compiled"
    # a CPU has no "auto" that selects a kernel: the rehearsal asks for it
    wide_impl = pallas if args.rehearse else "auto"
    for phase, agree_name, impl, config in (
        ("serve", "agree", "auto", config),
        ("pallas", "pallas-agree", pallas, config),
        ("serve-wide", "wide-agree", wide_impl, wide_config(sz, args.rehearse)),
    ):
        # the entry point is a jit of its own, traced once a process a shape:
        # forget the last phase's trace so that this phase's is counted
        PA.paged_decode_attention_pallas.clear_cache()
        PA.KERNEL_TRACES.clear()
        model = JaxLocalModelClient(
            config=config, runtime=serving_runtime(sz, impl),
            tokenizer=IdTokenizer(config.vocab_size),
            max_new_tokens=sz["new_tokens"], seed=args.seed,
        )
        row = await serve_phase(
            phase, model, singles=sz["singles"], burst=sz["burst"]
        )
        engine = model._engine
        agree, outputs = await agree_phase(
            agree_name, engine,
            prompts_for(config.vocab_size, sz["agree_lens"], args.seed),
            sz["agree_new"], args.margin, sz["pad_to"],
        )
        if phase == "serve":
            auto_outputs = outputs
        if impl != "auto" or not args.rehearse:  # a CPU's "auto" is XLA
            traces = {f"{k}:{mode}": n for (k, mode), n in PA.KERNEL_TRACES.items()}
            row["kernel_traces"] = agree["kernel_traces"] = traces
            # a dense model's one kernel, built as asked, and no other
            kernels_ok = (
                row["attention_impl"] == pallas
                and set(traces) == {f"paged_decode:{want}"}
            )
            agree["paged_decode_in_place_" + want] = kernels_ok
            if phase == "pallas":
                agree["equal_to_auto_engine"] = count_equal(auto_outputs, outputs)
            agree["ok"] = agree["ok"] and kernels_ok
        row["memory"] = agree["memory"] = memory(jax.devices()[:1])
        emit(row)
        emit(agree)
        ok = ok and row["ok"] and agree["ok"]
        await model.stop()
        del model, engine
        gc.collect()  # the next engine's weights must not sit beside these
    ok = await run_hybrid(args, sz) and ok
    row = kernels_phase(args.seed, interpret=args.rehearse)
    emit(row)
    return ok and row["ok"]


async def run_llama8b_int8(args) -> bool:
    """Optional, builder-run: the north-star shape on one chip."""
    import jax

    from calfkit_tpu.inference.client import JaxLocalModelClient
    from calfkit_tpu.inference.config import preset
    from calfkit_tpu.inference.engine import InferenceEngine
    from calfkit_tpu.inference.quant import random_quantized_params_host
    from calfkit_tpu.inference.tokenizer import IdTokenizer

    config = preset("llama-3-8b", max_seq_len=1024)
    sz = dict(bs=16, seq=1024, chunk=128, page=64)
    engine = InferenceEngine(
        config, serving_runtime(sz, "auto", quantization="int8"),
        params=random_quantized_params_host(config, seed=args.seed),
    )
    model = JaxLocalModelClient(
        engine=engine, tokenizer=IdTokenizer(config.vocab_size),
        max_new_tokens=16,
    )
    row = await serve_phase("serve-llama8b-int8", model, singles=2, burst=20)
    row["memory"] = memory(jax.devices()[:1])
    emit(row)
    await model.stop()
    return row["ok"]


async def run_four_chips(args) -> bool:
    """Only the tensor-parallel path and what it is compared with."""
    import jax

    from calfkit_tpu.inference.client import JaxLocalModelClient
    from calfkit_tpu.inference.config import preset
    from calfkit_tpu.inference.engine import InferenceEngine
    from calfkit_tpu.inference.sharding import make_mesh
    from calfkit_tpu.inference.tokenizer import IdTokenizer

    if args.rehearse:
        base = preset("debug", max_seq_len=256, n_kv_heads=4)
        sz = dict(bs=4, seq=256, chunk=32, page=16)
        full_depth, cut_depth, lens, new, pad_to = 2, 1, (5, 20, 40), 8, 64
        serve, serve_new = dict(singles=1, burst=6), 8
    else:
        base = preset("llama-3-8b", max_seq_len=1024)
        sz = dict(bs=16, seq=1024, chunk=128, page=64)
        full_depth, cut_depth, lens, new, pad_to = 32, 4, (12, 70, 200), 32, 256
        serve, serve_new = dict(singles=2, burst=20), 16
    devices = jax.devices()
    ok = True

    # ---- tp=4 at full depth: 16 GB of bf16 weights, more than one chip holds
    from dataclasses import replace

    config = replace(base, n_layers=full_depth)
    model = JaxLocalModelClient(
        config=config, runtime=serving_runtime(sz, "auto", tp=4),
        tokenizer=IdTokenizer(config.vocab_size),
        max_new_tokens=serve_new, seed=args.seed,
    )
    row = await serve_phase("serve-tp4", model, **serve)
    row["memory"] = memory(devices[:4])
    used = [m["bytes_in_use"] for m in row["memory"]]
    if all(u is not None for u in used):
        # sharded must be SHOWN: every device holds a real share and none
        # holds (nearly) the whole tree
        weights = 2 * config.param_count
        row["weights_bytes"] = weights
        row["sharded"] = min(used) > 0.15 * weights and max(used) < 0.6 * weights
        row["ok"] = row["ok"] and row["sharded"]
    emit(row)
    ok = ok and row["ok"]
    await model.stop()
    del model
    gc.collect()

    # ---- the comparison: same widths at a depth one chip holds, tp=4 vs tp=1
    config = replace(base, n_layers=cut_depth)
    prompts = prompts_for(config.vocab_size, lens, args.seed)
    outs = {}
    for tp in (4, 1):
        rt = serving_runtime(sz, "auto", tp=tp)
        engine = InferenceEngine(
            config, rt, mesh=make_mesh(tp=tp, devices=devices[:tp]),
            seed=args.seed,
        )
        await engine.start()
        agree, outs[tp] = await agree_phase(
            f"agree-tp{tp}", engine, prompts, new, args.margin, pad_to
        )
        agree["n_layers"] = cut_depth
        agree["memory"] = memory(devices[:4])
        emit(agree)
        ok = ok and agree["ok"]
        await engine.stop()
        del engine
        gc.collect()
    emit({"phase": "tp4-vs-tp1", "ok": True, "positions": len(lens) * new,
          "equal_tokens": count_equal(outs[4], outs[1]),
          "note": "free-running streams part at the first near-tie; the "
                  "margin rule above is the pass/fail check"})

    if args.long_context:
        ok = await run_long_context(args, base, full_depth) and ok
    return ok


async def run_long_context(args, base, depth: int) -> bool:
    """One request through the ring-attention lane over all four devices."""
    from dataclasses import replace

    from calfkit_tpu.inference.config import RuntimeConfig
    from calfkit_tpu.inference.engine import InferenceEngine

    seq = 256 if args.rehearse else 1024
    config = replace(base, n_layers=1 if args.rehearse else 4, max_seq_len=seq)
    engine = InferenceEngine(config, RuntimeConfig(
        max_batch_size=2, max_seq_len=seq, prefill_chunk=seq // 4, tp=4,
        long_context=True, long_new_cap=16,
    ), seed=args.seed)
    await engine.start()
    t0 = time.perf_counter()
    prompt = prompts_for(config.vocab_size, (3 * seq,), args.seed)[0]
    out = [t async for t in engine.generate(prompt, max_new_tokens=8)]
    await engine.stop()
    row = {"phase": "long-context", "ok": len(out) == 8,
           "prompt_len": len(prompt), "tokens": len(out),
           "seconds": round(time.perf_counter() - t0, 2)}
    emit(row)
    return row["ok"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--margin", type=float, default=0.25, help=(
        "top-2 logit margin (f32 logits of the bf16 reference) above which "
        "the engine's token must equal the reference argmax"
    ))
    ap.add_argument("--rehearse", action="store_true", help=(
        "tiny sizes + interpret mode on the CPU; never prints ok:true"
    ))
    ap.add_argument("--llama8b-int8", action="store_true", help=(
        "one chip: also serve llama-3-8b int8 + paged KV (builder-run)"
    ))
    ap.add_argument("--long-context", action="store_true", help=(
        "with --chips 4: also one request through the ring-attention lane"
    ))
    args = ap.parse_args()

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
    try:
        import jax

        import calfkit_tpu  # noqa: F401 - the repo must be around this file
        from calfkit_tpu.inference.compile_cache import enable_compile_cache
    except ImportError as e:
        die(3, f"cannot import the program: {e}")
    try:
        device = device_info()
    except RuntimeError as e:
        die(2, f"JAX found no backend: {e}")
    if device["platform"] != "tpu" and not args.rehearse:
        die(2, f"no TPU: jax.devices()[0].platform == {device['platform']!r}")
    if device["count"] < args.chips:
        die(2, f"--chips {args.chips} needs {args.chips} devices, "
               f"found {device['count']}")

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cache_dir = enable_compile_cache()
    cache_before = cache_entries(cache_dir)
    t0 = time.perf_counter()
    broker = build_broker()
    emit({"phase": "setup", "ok": True, "device": device,
          "broker": os.path.relpath(broker, ROOT),
          "broker_build_s": round(time.perf_counter() - t0, 2),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_before": cache_before,
          "rehearsal": args.rehearse, "seed": args.seed})

    async def run() -> bool:
        if args.chips == 4:
            return await run_four_chips(args)
        ok = await run_one_chip(args, sizes(args.rehearse))
        if args.llama8b_int8:
            ok = await run_llama8b_int8(args) and ok
        return ok

    ok = asyncio.run(run())
    after = cache_entries(cache_dir)
    emit({"phase": "summary", "ok": ok, "wall_s": round(time.perf_counter() - t0, 1),
          "compile_s": round(_compile_s[0], 1),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_after": after,
          "compile_cache_written": after > cache_before})
    if not ok:
        die(1, "a phase failed")
    if args.rehearse:
        emit({"ok": False, "rehearsal_passed": True, "device": device})
        return
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
