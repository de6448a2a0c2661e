"""Headline benchmark: agent-serving decode throughput on the local chip(s).

One in-process run; prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tok/s/chip", "vs_baseline": N,
   "platform": ..., "device_kind": ..., "devices": N, ...}

Without an accelerator it FAILS (non-zero exit, no JSON) — unless
``JAX_PLATFORMS=cpu`` was set explicitly, and then the output names
``platform: cpu`` and the metric carries a ``cpu_`` prefix: a CPU number is
never reported under the device metric's name.  Any failing phase fails the
run.

Baseline: 2000 decode tok/s/chip (BASELINE.md north star, stated for
Llama-3-8B TP=8 on v5e-8).  This round measures the TinyLlama-1.1B
architecture (BASELINE configs 2/3: the provider-swap model) under
continuous batching on however many chips are visible; the metric name
carries the exact config so rounds stay comparable.

The engine places the persistent XLA compile cache by the one rule in
calfkit_tpu/inference/compile_cache.py.
"""

from __future__ import annotations

from calfkit_tpu.effects import no_wallclock

import asyncio
import contextlib
import json
import os
import sys
import time


def _bench_config():
    import jax

    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    choice = os.environ.get("CALFKIT_BENCH_CONFIG", "auto")
    if choice not in ("auto", "smoke", "tinyllama", "tinyllama_cpu",
                      "llama8b", "llama8b_int4"):
        raise ValueError(
            f"CALFKIT_BENCH_CONFIG={choice!r} "
            "(want auto | smoke | tinyllama | tinyllama_cpu | llama8b | "
            "llama8b_int4)"
        )
    if choice == "auto":
        choice = "smoke" if platform == "cpu" else "tinyllama"
    if choice == "smoke":
        # offline smoke mode: tiny model, small workload (requests = 4x bs
        # so even the fallback number reflects steady-state batching)
        return dict(
            preset="debug", bs=8, max_seq=256, prefill_chunk=32,
            steps=8, requests=32, new_tokens=32, prompt_len=16,
        )
    if choice == "tinyllama_cpu":
        # CPU-replay shape (VERDICT r3 item 3): the REAL tinyllama
        # architecture with a workload small enough for CPU.  Same engine
        # code path as the tinyllama config; only batch/requests/token
        # counts shrink.
        return dict(
            preset="tinyllama-1.1b", bs=8, max_seq=256, prefill_chunk=32,
            steps=8, requests=32, new_tokens=16, prompt_len=16,
            quantization="int8",
        )
    if choice == "llama8b":
        # BASELINE north star shape: Llama-3-8B, int8 weights (~8 GB),
        # paged KV (dense at this batch would not fit 16 GB), random
        # int8-shaped params built host-side (no checkpoint in image)
        return dict(
            preset="llama-3-8b", bs=32, max_seq=1024, prefill_chunk=128,
            steps=32, requests=128, new_tokens=128, prompt_len=64,
            quantization="int8", kv_layout="paged", random_quantized=True,
            # 32 slots x 4 pages reserve (64+128+1 tokens) + headroom
            num_kv_pages=32 * 4 + 65,
        )
    if choice == "llama8b_int4":
        # int4 weights (~4 GB): half the int8 weight stream — the freed
        # HBM funds a 2x batch (64 slots) for even better occupancy
        return dict(
            preset="llama-3-8b", bs=64, max_seq=1024, prefill_chunk=128,
            steps=32, requests=256, new_tokens=128, prompt_len=64,
            quantization="int4", kv_layout="paged", random_quantized=True,
            num_kv_pages=64 * 4 + 65,
        )
    return dict(
        # requests = 4x bs so the measured region is steady-state-dominated
        # real continuous batching (admission churn + slot reuse).  The
        # round-2 number used requests=72 at bs=64: the 8-request tail plus
        # ramp put a third of the dispatches in the bottom occupancy
        # quartile (mean occupancy 0.365 on TPU, 0.68 in the CPU replay) —
        # a measurement-window artifact, not engine starvation.  At 4x bs
        # the same engine measures occupancy 1.0 and ~3x the wall tok/s.
        preset="tinyllama-1.1b", bs=64, max_seq=1024, prefill_chunk=128,
        steps=32, requests=256, new_tokens=128, prompt_len=64,
        quantization="int8",  # weight-only: halves the decode HBM stream
    )


# Published per-chip peaks (bf16 TFLOP/s, HBM GB/s) keyed by device_kind
# substring (source: Google Cloud TPU documentation, per-generation system
# architecture pages) — used ONLY to normalize measured throughput into an
# end-to-end utilization.  A device that is not in the table is an error.
_TPU_PEAKS = {
    "v2": (45.0, 700.0),
    "v3": (123.0, 900.0),
    "v4": (275.0, 1228.0),
    "v5 lite": (197.0, 819.0),
    "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v6 lite": (918.0, 1640.0),
    "v6e": (918.0, 1640.0),
}


def _device_peaks() -> "tuple[float, float] | None":
    """(bf16 TFLOP/s, HBM GB/s) for the live chip from the published table.
    None only on an explicit CPU run (no device utilization is reported
    there); an accelerator the table does not know raises."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = str(device.device_kind).lower()
    for key, peaks in _TPU_PEAKS.items():
        if key in kind:
            return peaks
    raise KeyError(
        f"no published peaks for device_kind {device.device_kind!r}: add it "
        "to bench._TPU_PEAKS with its source"
    )


@no_wallclock
def _perf_model(
    model, cfg, wall_tps: float, occupancy: float,
    wave_stats: "dict | None" = None,
) -> dict:
    """Model-FLOPs and HBM-traffic per decoded token AND per ragged wave
    (dispatch), and — on an accelerator, against the published peaks —
    end-to-end MFU and HBM-bandwidth utilization (VERDICT r4 item 6: tok/s
    alone flatters small models; MFU is the honest cross-config metric).

    Decode FLOPs/token ≈ 2·params (every weight participates in one MAC)
    + 4·n_layers·d_model·ctx attention score/value FLOPs at mean context.
    Decode HBM bytes/token ≈ weight stream amortized over the effective
    batch + the sequence's own KV read.  ``wave_stats`` (tokens per
    dispatch incl. absorbed prefill, dispatch rate) turns those into the
    analytic per-WAVE numbers the ragged scheduler is judged by: one
    fused dispatch reads the weights once for every token it carries, so
    absorbed prefill tokens amortize the same stream a bifurcated
    schedule paid a second dispatch for."""
    import jax

    kind = str(getattr(jax.devices()[0], "device_kind", "") or "").lower()
    peaks = _device_peaks()
    params = model.param_count
    ctx = cfg["prompt_len"] + cfg["new_tokens"] / 2.0
    attn_flops = 4.0 * model.n_layers * model.d_model * ctx
    flops_per_token = 2.0 * params + attn_flops
    weight_bytes = params * {
        "int8": 1.0, "int4": 0.5,
    }.get(cfg.get("quantization"), 2.0)
    kv_bytes = 2.0 * model.n_layers * model.n_kv_heads * model.head_dim * ctx * 2
    effective_bs = max(cfg["bs"] * max(occupancy, 0.0), 1e-9)
    bytes_per_token = weight_bytes / effective_bs + kv_bytes
    out = {
        "model_params_b": round(params / 1e9, 3),
        "decode_flops_per_token_g": round(flops_per_token / 1e9, 3),
        "decode_hbm_bytes_per_token_m": round(bytes_per_token / 1e6, 3),
        "device_kind": kind or None,
        "mfu": None,
        "hbm_bw_util": None,
    }
    if wave_stats:
        # per-ragged-wave roofline: tokens carried per dispatch (decode +
        # absorbed prefill) × per-token FLOPs, against ONE weight stream
        # per dispatch — the fused wave's arithmetic intensity
        tokens_per_wave = wave_stats.get("tokens_per_dispatch", 0.0)
        if tokens_per_wave:
            wave_flops = tokens_per_wave * flops_per_token
            wave_bytes = weight_bytes + tokens_per_wave * kv_bytes
            out["per_wave"] = {
                "tokens_per_dispatch": round(tokens_per_wave, 2),
                "flops_per_wave_g": round(wave_flops / 1e9, 3),
                "hbm_bytes_per_wave_m": round(wave_bytes / 1e6, 3),
                "arith_intensity_flop_per_byte": round(
                    wave_flops / max(wave_bytes, 1e-9), 2
                ),
                "prefill_absorbed_tokens": wave_stats.get(
                    "prefill_absorbed_tokens", 0
                ),
            }
    if peaks is not None:
        tflops, gb_s = peaks
        out["mfu"] = round(wall_tps * flops_per_token / (tflops * 1e12), 4)
        out["hbm_bw_util"] = round(
            wall_tps * bytes_per_token / (gb_s * 1e9), 4
        )
    return out


async def run() -> dict:
    import jax

    from calfkit_tpu.inference.config import RuntimeConfig, preset
    from calfkit_tpu.inference.engine import InferenceEngine

    cfg = _bench_config()
    n_dev = len(jax.devices())
    model = preset(cfg["preset"], max_seq_len=cfg["max_seq"])
    runtime = RuntimeConfig(
        max_batch_size=cfg["bs"],
        max_seq_len=cfg["max_seq"],
        prefill_chunk=cfg["prefill_chunk"],
        decode_steps_per_dispatch=cfg["steps"],
        tp=1,
        dp=1,
        quantization=cfg.get("quantization"),
        kv_layout=cfg.get("kv_layout", "dense"),
        num_kv_pages=cfg.get("num_kv_pages", 0),
        # chunked admission is the ragged unified lane's substrate
        # (ISSUE 6): the bench measures the default serving path —
        # prefill chunks absorbed into decode dispatches
        chunked_prefill=True,
    )
    params = None
    if cfg.get("random_quantized"):
        # big-model bench without a checkpoint: int8 params built on host
        # (a device-side random init would transiently need the full bf16
        # tree — the whole chip for 8B)
        from calfkit_tpu.inference.quant import random_quantized_params_host

        params = random_quantized_params_host(
            model, bits=4 if cfg.get("quantization") == "int4" else 8
        )
    engine = InferenceEngine(model, runtime, params=params)
    await engine.start()

    # warm every specialization the measured run will touch: each power-of-
    # two prefill-wave size (deterministic sequential batches) + the decode
    # window
    async def _warm(i: int) -> int:
        n = 0
        async for _ in engine.generate(
            [5 + i, *range(6, 5 + cfg["prompt_len"])],
            max_new_tokens=cfg["new_tokens"],
        ):
            n += 1
        return n

    for size in (1, 2, 4, 8):
        if size > cfg["bs"]:
            break
        warm = await asyncio.gather(*[_warm(i) for i in range(size)])
        assert all(warm), "warmup produced no tokens"
    # oversubscribe with SHORT generations: waiting admissions + imminent
    # retirements trigger the short decode variant, compiling it outside the
    # measured region at minimal token cost
    async def _warm_short(i: int) -> int:
        n = 0
        async for _ in engine.generate(
            [9 + i, *range(6, 5 + cfg["prompt_len"])], max_new_tokens=8
        ):
            n += 1
        return n

    warm = await asyncio.gather(*[_warm_short(i) for i in range(cfg["bs"] + 2)])
    assert all(warm), "oversubscribed warmup produced no tokens"

    stats = engine.stats
    stats.decode_tokens = 0
    stats.decode_time_s = 0.0
    stats.decode_dispatches = 0
    stats.occupancy_sum = 0.0
    stats.occupancy_hist = [0, 0, 0, 0]
    stats.short_dispatches = 0
    # ragged-wave counters reset with the dispatch counters they are
    # divided by — warmup absorption must not inflate the measured
    # tokens_per_dispatch / per_wave roofline
    stats.prefill_absorbed_tokens = 0
    stats.unified_dispatches = 0

    async def one(i: int) -> int:
        n = 0
        async for _ in engine.generate(
            [3 + (i % 41), *range(7, 6 + cfg["prompt_len"])],
            max_new_tokens=cfg["new_tokens"],
        ):
            n += 1
        return n

    started = time.perf_counter()
    counts = await asyncio.gather(*[one(i) for i in range(cfg["requests"])])
    wall = time.perf_counter() - started
    # snapshot throughput-phase stats NOW: the TTFT phase below pushes 12
    # deliberately single-stream requests through the same engine, and its
    # occ=1/bs dispatches must not pollute the batching metrics (this was
    # a third of the round-2 "0.365 mean occupancy" mystery)
    decode_tps = stats.tokens_per_second / n_dev
    mean_occupancy = stats.mean_occupancy
    occupancy_hist = list(stats.occupancy_hist)
    short_dispatches = stats.short_dispatches
    wave_stats = {
        "tokens_per_dispatch": stats.mean_tokens_per_dispatch,
        "prefill_absorbed_tokens": stats.prefill_absorbed_tokens,
        "unified_dispatches": stats.unified_dispatches,
        "ragged_waves": engine._ragged,
    }

    # ---- TTFT phase: p50 mesh-msg -> first streamed token through the FULL
    # agent path (client -> mesh -> agent -> engine -> token step -> client)
    ttft_p50_ms, ttft_transport = await _ttft_phase(engine)
    await engine.stop()

    spec_row = await _spec_phase(model, cfg)

    total = sum(counts)
    wall_tps = total / wall / n_dev
    # the 2,000 tok/s/chip bar is STATED for Llama-3-8B TP=8 — comparing a
    # smaller model's throughput against it flatters the number, so any
    # other config reports vs_baseline: null with an explicit note
    is_baseline_model = model.name == "llama-3-8b"
    device = jax.devices()[0]
    on_cpu = device.platform == "cpu"
    return {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "devices": n_dev,
        "metric": (
            f"{'cpu_decode_tok_s' if on_cpu else 'decode_tok_s_per_chip'}"
            f"[{model.name} bs={cfg['bs']}"
            f"{' ' + cfg['quantization'] if cfg.get('quantization') else ''}"
            f"{' paged-kv' if cfg.get('kv_layout') == 'paged' else ''}"
            f"{' ragged-waves' if wave_stats['ragged_waves'] else ''} "
            f"continuous-batching wall]"
        ),
        "value": round(wall_tps, 1),
        "unit": "tok/s (cpu, not a device rate)" if on_cpu else "tok/s/chip",
        "vs_baseline": (
            round(wall_tps / 2000.0, 3)
            if is_baseline_model and not on_cpu else None
        ),
        **(
            {}
            if is_baseline_model
            else {"vs_baseline_note": "baseline_model_mismatch"}
        ),
        "detail": {
            **({"speculative": spec_row} if spec_row else {}),
            "decode_only_tok_s_per_chip": round(decode_tps, 1),
            "mean_batch_occupancy": round(mean_occupancy, 3),
            # dispatch counts per occupancy quartile [0-25%, .., 75-100%]
            "occupancy_hist": occupancy_hist,
            "short_dispatches": short_dispatches,
            # ragged unified waves (ISSUE 6): whether the fused lane ran,
            # and what each dispatch actually carried
            "ragged_waves": wave_stats["ragged_waves"],
            "prefill_absorbed_tokens": wave_stats["prefill_absorbed_tokens"],
            "unified_dispatches": wave_stats["unified_dispatches"],
            "tokens_per_dispatch": round(
                wave_stats["tokens_per_dispatch"], 2
            ),
            "p50_mesh_to_first_token_ms": ttft_p50_ms,
            "ttft_transport": ttft_transport,
            "requests": cfg["requests"],
            "new_tokens_per_request": cfg["new_tokens"],
            **_perf_model(model, cfg, wall_tps, mean_occupancy, wave_stats),
        },
    }


async def _spec_phase(model, cfg) -> dict | None:
    """Speculative-decoding row: a fresh engine at the same model config
    with the n-gram drafter on, driven by agent-shaped (self-repetitive)
    prompts.  Reports measured tokens_per_dispatch and acceptance_rate —
    the speculation win is measured here, never asserted (SPEC_DECODE.json
    carries the host-stub scheduler-level artifact)."""
    import time as _time

    from calfkit_tpu.inference.config import RuntimeConfig, SpecConfig
    from calfkit_tpu.inference.engine import InferenceEngine

    if model.param_count > 2e9:
        # the spec row builds a SECOND engine with fresh random params; at
        # 8B that doubles HBM for an auxiliary detail row — skip, and say so
        return {"skipped": "model too large for the auxiliary spec row"}
    engine = None
    try:
        runtime = RuntimeConfig(
            max_batch_size=min(8, cfg["bs"]),
            max_seq_len=cfg["max_seq"],
            prefill_chunk=cfg["prefill_chunk"],
            decode_steps_per_dispatch=cfg["steps"],
            quantization=cfg.get("quantization"),
            kv_layout=cfg.get("kv_layout", "dense"),
            num_kv_pages=cfg.get("num_kv_pages", 0),
            speculative=SpecConfig(k=4),
        )
        engine = InferenceEngine(model, runtime)
        await engine.start()
        pattern = [11, 7, 23, 5, 17, 9, 13, 3]
        new_tokens = min(cfg["new_tokens"], 32)

        async def one(i: int) -> int:
            # repeated structure = the n-gram drafter's home turf
            prompt = ([31 + i] + pattern * 3)[: cfg["max_seq"] // 4]
            n = 0
            async for _ in engine.generate(prompt, max_new_tokens=new_tokens):
                n += 1
            return n

        await asyncio.gather(*[one(i) for i in range(4)])  # warm compiles
        from calfkit_tpu.inference.engine import EngineStats

        stats = engine.stats = EngineStats()
        started = _time.perf_counter()
        counts = await asyncio.gather(*[one(i) for i in range(16)])
        wall = _time.perf_counter() - started
        return {
            "drafter": "ngram",
            "k": 4,
            "requests": len(counts),
            "tokens_per_dispatch": round(stats.tokens_per_dispatch, 3),
            "acceptance_rate": round(stats.acceptance_rate, 4),
            "spec_proposed": stats.spec_proposed,
            "spec_accepted": stats.spec_accepted,
            "wall_tok_s": round(sum(counts) / wall, 1),
        }
    finally:
        # a leaked engine would keep its scheduler task + a whole second
        # model's HBM alive through the remaining bench phases
        if engine is not None:
            await engine.stop()


async def _ttft_phase(engine) -> tuple[float, str]:
    """Median client-publish -> first-token latency over the live mesh.

    BASELINE phrases the north star as "Kafka-msg -> first-token": the lane
    is the in-repo ``kafkad`` broker over the REAL Kafka wire protocol
    (worker and client as separate wire clients).  A broker that cannot be
    found or spawned fails the run — no other transport stands in."""
    from calfkit_tpu.mesh.kafka_wire import KafkaWireMesh, spawn_kafkad

    proc = spawn_kafkad(0)
    port = proc.kafkad_port
    try:
        mesh = KafkaWireMesh(f"127.0.0.1:{port}")
        client_mesh = KafkaWireMesh(f"127.0.0.1:{port}")
        await client_mesh.start()
        try:
            return await _ttft_runs(engine, mesh, client_mesh), "kafkad-wire"
        finally:
            await client_mesh.stop()
    finally:
        proc.terminate()
        with contextlib.suppress(Exception):
            proc.wait(timeout=5)


async def _ttft_runs(engine, mesh, client_mesh) -> float:
    """Drive 12 single-turn runs (2 warmup) and return the p50 in ms."""
    from calfkit_tpu.client import Client
    from calfkit_tpu.inference.client import JaxLocalModelClient
    from calfkit_tpu.inference.tokenizer import IdTokenizer
    from calfkit_tpu.nodes import Agent
    from calfkit_tpu.worker import Worker

    # TTFT measures pipeline latency, not tokenizer quality: every id must
    # render, or a random-weights model streams no token step at all
    model = JaxLocalModelClient(
        engine=engine, max_new_tokens=8, tokenizer=IdTokenizer()
    )
    await model.start()
    agent = Agent("bench_agent", model=model, stream_tokens=True)
    samples: list[float] = []
    async with Worker([agent], mesh=mesh, owns_transport=True):
        client = Client.connect(client_mesh)
        # 2 unmeasured warmup runs absorb the agent-path jit variants
        # (prompt-length buckets the throughput phase never touched)
        for i in range(12):
            t0 = time.perf_counter()
            handle = await client.agent("bench_agent").start(
                f"ping {i}", timeout=120
            )
            got = False
            async for event in handle.stream():
                if getattr(getattr(event, "step", None), "kind", "") == "token":
                    if i >= 2:
                        samples.append((time.perf_counter() - t0) * 1000.0)
                    got = True
                    break
            # drain the rest of the run
            if got:
                await handle.result(timeout=120)
        await client.close()
    samples.sort()
    if not samples:
        raise RuntimeError("no token step observed in any TTFT run")
    return round(samples[len(samples) // 2], 1)


def main() -> None:
    """One in-process run: no probe child, no cached row, no CPU stand-in.
    Any failure is a non-zero exit."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(
            "bench: JAX found no accelerator (platform cpu) and "
            "JAX_PLATFORMS=cpu was not asked for — refusing to report a "
            "CPU number", file=sys.stderr,
        )
        sys.exit(2)
    print(json.dumps(asyncio.run(run())))


if __name__ == "__main__":
    main()
