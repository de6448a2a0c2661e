"""Profile XLA vs Pallas decode attention on the current backend.

Times a FULL decode dispatch (the engine's scheduler unit: ``steps``
decode_step_ring iterations under lax.scan + one ring consolidation) for
each attention implementation, at the bench's TinyLlama shapes and the
Llama-3-8B paged shapes.  This is the measurement that decides what
``RuntimeConfig(attention_impl="auto")`` resolves to on hardware
(VERDICT round-1 "weak" #3).

Usage:  python scripts/profile_attention.py [--config tinyllama|llama8b|both]
Prints one JSON line per (config, impl) with ms/dispatch and tok/s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def profile_dense(preset_name: str, B: int, W: int, steps: int, impls,
                  rows=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference.config import preset

    cfg = preset(preset_name)
    dtype = jnp.bfloat16
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0)),
    )
    k = jnp.zeros((cfg.n_layers, B, cfg.n_kv_heads, W, cfg.head_dim), dtype)
    v = jnp.zeros_like(k)
    last = jnp.ones((B,), jnp.int32)
    lens = jnp.full((B,), W // 2, jnp.int32)

    for impl in impls:
        def dispatch(params, k, v, last, lens):
            ring = (
                jnp.zeros((cfg.n_layers, steps, B, cfg.n_kv_heads, cfg.head_dim), dtype),
                jnp.zeros((cfg.n_layers, steps, B, cfg.n_kv_heads, cfg.head_dim), dtype),
            )

            def step(carry, t):
                ring, last = carry
                lg, ring = M.decode_step_ring(
                    params, cfg, last[:, None], (k, v), ring, t, lens,
                    attn_impl=impl,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                return (ring, nxt), nxt

            (ring, last), toks = lax.scan(step, (ring, last), jnp.arange(steps))
            k2, v2 = M.consolidate_ring((k, v), ring, lens)
            return k2, v2, toks

        fn = jax.jit(dispatch, donate_argnums=(1, 2))
        k2, v2, toks = fn(params, k, v, last, lens)
        toks.block_until_ready()
        k, v = k2, v2
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            k2, v2, toks = fn(params, k, v, last, lens)
            toks.block_until_ready()
            times.append(time.perf_counter() - t0)
            k, v = k2, v2
        ms = min(times) * 1000.0
        row = {
            "path": "decode",
            "config": f"{preset_name} dense B={B} W={W} steps={steps}",
            "impl": impl,
            "ms_per_dispatch": round(ms, 2),
            "tok_s": round(B * steps / (ms / 1000.0), 1),
        }
        print(json.dumps(row))
        if rows is not None:
            rows.append(row)


def profile_prefill(preset_name: str, R: int, S: int, impls,
                    rows=None) -> None:
    """Time one prefill-wave forward ([R, S] into a fresh scratch cache)
    per attention impl — the flash kernel's shape of interest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference.config import preset

    cfg = preset(preset_name)
    dtype = jnp.bfloat16
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0)),
    )
    tokens = jnp.ones((R, S), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (R, S))
    lens = jnp.full((R,), S, jnp.int32)

    for impl in impls:
        def prefill(params, tokens):
            scratch = (
                jnp.zeros((cfg.n_layers, R, cfg.n_kv_heads, S, cfg.head_dim), dtype),
                jnp.zeros((cfg.n_layers, R, cfg.n_kv_heads, S, cfg.head_dim), dtype),
            )
            logits, _ = M.forward(
                params, cfg, tokens, pos, scratch, lens, attn_impl=impl
            )
            return logits[:, -1]

        fn = jax.jit(prefill)
        out = fn(params, tokens)
        np.asarray(jnp.float32(out)).sum()  # force a real fetch
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            out = fn(params, tokens)
            np.asarray(jnp.float32(out)).sum()
            times.append(time.perf_counter() - t0)
        ms = min(times) * 1000.0
        row = {
            "path": "prefill",
            "config": f"{preset_name} prefill R={R} S={S}",
            "impl": impl,
            "ms_per_dispatch": round(ms, 2),
            "prefill_tok_s": round(R * S / (ms / 1000.0), 1),
        }
        print(json.dumps(row))
        if rows is not None:
            rows.append(row)


def profile_paged(preset_name: str, B: int, wpages: int, steps: int,
                  page: int, impls, n_layers: int | None = None,
                  rows=None) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference.config import preset

    cfg = preset(preset_name, **({"n_layers": n_layers} if n_layers else {}))
    dtype = jnp.bfloat16
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0)),
    )
    N = B * wpages + 1
    pool_k = jnp.zeros((cfg.n_layers, N, cfg.n_kv_heads, page, cfg.head_dim), dtype)
    pool_v = jnp.zeros_like(pool_k)
    tables = (jnp.arange(B * wpages, dtype=jnp.int32).reshape(B, wpages) + 1)
    last = jnp.ones((B,), jnp.int32)
    lens = jnp.full((B,), wpages * page // 2, jnp.int32)
    active = jnp.ones((B,), bool)

    for impl in impls:
        def dispatch(params, pool_k, pool_v, tables, last, lens):
            ring = (
                jnp.zeros((cfg.n_layers, steps, B, cfg.n_kv_heads, cfg.head_dim), dtype),
                jnp.zeros((cfg.n_layers, steps, B, cfg.n_kv_heads, cfg.head_dim), dtype),
            )

            def step(carry, t):
                ring, last = carry
                lg, ring = M.decode_step_ring_paged(
                    params, cfg, last[:, None], (pool_k, pool_v), tables,
                    ring, t, lens, wpages=wpages, attn_impl=impl,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                return (ring, nxt), nxt

            (ring, last), toks = lax.scan(step, (ring, last), jnp.arange(steps))
            pk, pv = M.consolidate_ring_paged(
                (pool_k, pool_v), ring, tables, lens, active
            )
            return pk, pv, toks

        fn = jax.jit(dispatch, donate_argnums=(1, 2))
        pk, pv, toks = fn(params, pool_k, pool_v, tables, last, lens)
        toks.block_until_ready()
        pool_k, pool_v = pk, pv
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            pk, pv, toks = fn(params, pool_k, pool_v, tables, last, lens)
            toks.block_until_ready()
            times.append(time.perf_counter() - t0)
            pool_k, pool_v = pk, pv
        ms = min(times) * 1000.0
        row = {
            "path": "paged_decode",
            "config": f"{preset_name} paged B={B} wpages={wpages} page={page} steps={steps}",
            "impl": impl,
            "ms_per_dispatch": round(ms, 2),
            "tok_s": round(B * steps / (ms / 1000.0), 1),
        }
        print(json.dumps(row))
        if rows is not None:
            rows.append(row)


def _time_min(fn, *args) -> float:
    """THE timing law shared by the ragged profilers: warm once (jit
    build outside the window), then min of 5 synced reps, in ms — one
    copy, so the cross-path comparison that steers
    ``attention_impl="auto"`` cannot drift between paths."""
    out = fn(*args)
    out.block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(*args)
        out.block_until_ready()
        times.append(time.perf_counter() - t0)
    return min(times) * 1000.0


def _ragged_rows(B: int, S: int, W: int):
    """Mixed ragged row kinds at wave shape [B, S]: one third decode
    (q_len=1, start=kv_len=lens), one third prefill-chunk (q_len=S,
    start=offset, kv_len=offset+S), one third spec-verify (q_len=k+1,
    start=kv_len=base_lens) — the three row kinds the unified wave and
    the verify dispatch actually serve (calfkit_tpu/inference/ragged.py
    descriptor vocabulary).  Queries past a row's true q_len are padding
    the kernel computes-and-ignores, exactly as in production."""
    import numpy as np

    lens0 = W // 2
    offset = W // 4
    starts = np.zeros((B,), np.int32)
    kv_lens = np.zeros((B,), np.int32)
    for b in range(B):
        kind = b % 3
        if kind == 0:  # decode row
            starts[b] = lens0
            kv_lens[b] = lens0
        elif kind == 1:  # prefill-chunk row
            starts[b] = offset
            kv_lens[b] = offset + S
        else:  # verify row (k+1 queries against the settled cache)
            starts[b] = lens0
            kv_lens[b] = lens0
    return starts, kv_lens


def profile_ragged(preset_name: str, B: int, W: int, S: int, impls,
                   rows=None) -> None:
    """Time the ragged multi-query attention kernel (dense window) on a
    mixed decode/chunk/verify wave — the shape ``attention_impl="auto"``
    resolves the VERIFY dispatch (and any ragged consumer) with (path
    ``ragged``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference import pallas_attention as P
    from calfkit_tpu.inference.config import preset

    cfg = preset(preset_name)
    dtype = jnp.bfloat16
    K, hd = cfg.n_kv_heads, cfg.head_dim
    H = cfg.n_heads
    G = H // K
    starts_np, kv_np = _ragged_rows(B, S, W)
    q = jnp.ones((B, S, H, hd), dtype)
    k = jnp.zeros((cfg.n_layers, B, K, W, hd), dtype)
    v = jnp.zeros_like(k)
    starts = jnp.asarray(starts_np)
    kv_lens = jnp.asarray(kv_np)

    for impl in impls:
        # EVERY operand is a traced jit argument (q, caches, starts,
        # kv_lens) in BOTH branches — a baked-in constant query would
        # let XLA fold/specialize asymmetrically and skew the winner
        # artifact that steers production attention_impl="auto"
        if impl.startswith("pallas"):
            interpret = impl == "pallas_interpret"

            def dispatch(q_in, k, v, st, kv, interpret=interpret):
                qg = q_in.reshape(B, S, K, G, hd).transpose(0, 2, 1, 3, 4)

                def one_layer(_, kv_layer):
                    lk, lv = kv_layer
                    o, m, z = P.ragged_attention_pallas(
                        qg, lk, lv, st, kv, interpret=interpret
                    )
                    out = o / jnp.maximum(z[..., None], 1e-30)
                    return None, out.astype(qg.dtype)

                _, outs = lax.scan(one_layer, None, (k, v))
                return outs
        else:

            def dispatch(q_in, k, v, st, kv):
                def one_layer(_, kv_layer):
                    lk, lv = kv_layer
                    return None, M.ragged_attention_xla(
                        q_in, lk, lv, st, kv
                    )

                _, outs = lax.scan(one_layer, None, (k, v))
                return outs

        ms = _time_min(jax.jit(dispatch), q, k, v, starts, kv_lens)
        row = {
            "path": "ragged",
            "config": f"{preset_name} ragged B={B} S={S} W={W}",
            "impl": impl,
            "ms_per_dispatch": round(ms, 2),
            "ragged_q_tok_s": round(B * S / (ms / 1000.0), 1),
        }
        print(json.dumps(row))
        if rows is not None:
            rows.append(row)


def profile_ragged_paged(preset_name: str, B: int, wpages: int, S: int,
                         page: int, impls, n_layers: int | None = None,
                         rows=None) -> None:
    """Paged analog of :func:`profile_ragged`: the ragged kernel reading
    through block tables (path ``paged_ragged`` — resolves the paged
    verify dispatch under ``attention_impl="auto"``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference import pallas_attention as P
    from calfkit_tpu.inference.config import preset

    cfg = preset(preset_name, **({"n_layers": n_layers} if n_layers else {}))
    dtype = jnp.bfloat16
    K, hd = cfg.n_kv_heads, cfg.head_dim
    H = cfg.n_heads
    G = H // K
    W = wpages * page
    N = B * wpages + 1
    pool_k = jnp.zeros((cfg.n_layers, N, K, page, hd), dtype)
    pool_v = jnp.zeros_like(pool_k)
    tables = (jnp.arange(B * wpages, dtype=jnp.int32).reshape(B, wpages) + 1)
    starts_np, kv_np = _ragged_rows(B, S, W)
    q = jnp.ones((B, S, H, hd), dtype)
    starts = jnp.asarray(starts_np)
    kv_lens = jnp.asarray(kv_np)

    for impl in impls:
        # all operands traced, both branches (see profile_ragged)
        if impl.startswith("pallas"):
            interpret = impl == "pallas_interpret"

            def dispatch(q_in, pool_k, pool_v, tb, st, kv,
                         interpret=interpret):
                qg = q_in.reshape(B, S, K, G, hd).transpose(0, 2, 1, 3, 4)

                def one_layer(_, layer):
                    o, m, z = P.ragged_attention_paged_pallas(
                        qg, pool_k, pool_v, layer, tb, st, kv,
                        wpages=wpages, interpret=interpret,
                    )
                    out = o / jnp.maximum(z[..., None], 1e-30)
                    return None, out.astype(qg.dtype)

                _, outs = lax.scan(
                    one_layer, None,
                    jnp.arange(pool_k.shape[0], dtype=jnp.int32),
                )
                return outs
        else:

            def dispatch(q_in, pool_k, pool_v, tb, st, kv):
                def one_layer(_, kv_layer):
                    lk, lv = kv_layer
                    return None, M.ragged_attention_paged_xla(
                        q_in, lk, lv, tb, st, kv, wpages=wpages,
                    )

                _, outs = lax.scan(one_layer, None, (pool_k, pool_v))
                return outs

        ms = _time_min(
            jax.jit(dispatch), q, pool_k, pool_v, tables, starts, kv_lens
        )
        row = {
            "path": "paged_ragged",
            "config": (
                f"{preset_name} paged-ragged B={B} S={S} "
                f"wpages={wpages} page={page}"
            ),
            "impl": impl,
            "ms_per_dispatch": round(ms, 2),
            "ragged_q_tok_s": round(B * S / (ms / 1000.0), 1),
        }
        print(json.dumps(row))
        if rows is not None:
            rows.append(row)


def compute_winners(rows: list[dict], margin: float = 0.97) -> dict:
    """Per-path winner for the auto-resolution artifact.

    Conservative rule: "pallas" wins a path only when it beat XLA by
    >= (1 - margin) on EVERY config measured for that path — a single
    losing shape keeps the safe XLA default (the engine serves all shapes
    with one setting per path, so the winner must generalize)."""
    by_path: dict[str, dict[str, dict[str, float]]] = {}
    for row in rows:
        by_path.setdefault(row["path"], {}).setdefault(
            row["config"], {}
        )[row["impl"]] = row["ms_per_dispatch"]
    winners: dict[str, str] = {}
    for path, configs in by_path.items():
        comparable = [
            c for c in configs.values() if "xla" in c and "pallas" in c
        ]
        if comparable and all(
            c["pallas"] < margin * c["xla"] for c in comparable
        ):
            winners[path] = "pallas"
        elif comparable:
            winners[path] = "xla"
    return winners


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="both",
                    choices=("tinyllama", "llama8b", "both"))
    ap.add_argument("--impls", default="xla,pallas")
    ap.add_argument("--out", default=None, help=(
        "write the per-path winner artifact here (the engine's "
        "attention_impl='auto' reads it via $CALFKIT_ATTN_PROFILE)"
    ))
    args = ap.parse_args()
    impls = args.impls.split(",")

    import jax

    from calfkit_tpu.inference.compile_cache import enable_compile_cache

    enable_compile_cache()

    platform = jax.devices()[0].platform
    print(f"# platform={platform} devices={len(jax.devices())}",
          file=sys.stderr)
    rows: list[dict] = []
    if args.config in ("tinyllama", "both"):
        # bench tinyllama shape: bs=64, window bucket 1024, 32-step dispatch
        profile_dense("tinyllama-1.1b", B=64, W=1024, steps=32, impls=impls,
                      rows=rows)
        profile_paged("tinyllama-1.1b", B=64, wpages=16, steps=32, page=64,
                      impls=impls, rows=rows)
        profile_prefill("tinyllama-1.1b", R=8, S=512, impls=impls, rows=rows)
        # ragged multi-query shapes (ISSUE 10 satellite): mixed
        # decode/chunk/verify waves, so attention_impl="auto" resolves
        # the ragged kernels (verify dispatch, unified-wave consumers)
        # from measured winners instead of riding the legacy paths
        profile_ragged("tinyllama-1.1b", B=64, W=1024, S=16, impls=impls,
                       rows=rows)
        # spec-verify width (k+1 = 5): the other production ragged shape
        profile_ragged("tinyllama-1.1b", B=64, W=1024, S=5, impls=impls,
                       rows=rows)
        profile_ragged_paged("tinyllama-1.1b", B=64, wpages=16, S=16,
                             page=64, impls=impls, rows=rows)
    if args.config in ("llama8b", "both"):
        # bench llama8b ATTENTION shapes (bs=32, 4 pages/row reserve) on a
        # 4-layer slice: bf16 zero-params at full depth would not fit 16 GB
        # next to the pool, and the impl comparison is per-layer anyway
        profile_paged("llama-3-8b", B=32, wpages=4, steps=32, page=64,
                      impls=impls, n_layers=4, rows=rows)
        profile_ragged_paged("llama-3-8b", B=32, wpages=4, S=5, page=64,
                             impls=impls, n_layers=4, rows=rows)

    if args.out:
        verdict = {
            "platform": platform,
            "captured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
            "winners": compute_winners(rows),
            "rows": rows,
        }
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(json.dumps(verdict, indent=1))
        print(json.dumps({"winners": verdict["winners"],
                          "written": [args.out]}))


if __name__ == "__main__":
    main()
