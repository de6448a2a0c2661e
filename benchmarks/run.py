#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of BENCHMARK.json.  Lines of JSON on
stdout say what happened (agreement check, warm-up, the window's realised
token distributions, generator lateness, set-up split); the LAST line is
the result object the driver reads.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.

``--rehearse`` runs the same command end to end on the CPU at toy widths
with every length divided by the configuration's rehearsal scale; its last
line names ``platform: cpu`` and carries no metric at all.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def die(code: int, message: str):
    print(f"benchmarks/run.py: {message}", file=sys.stderr, flush=True)
    sys.exit(code)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy widths, scaled lengths; prints no metric")
    args = ap.parse_args()

    from benchmarks.manifest import ManifestError, load_manifest, load_peaks, resolve_cell

    try:
        manifest = load_manifest(ROOT)
        cell = resolve_cell(manifest, args.workload, ROOT)
    except ManifestError as e:
        die(4, str(e))
    seconds = args.seconds if args.seconds is not None else manifest["run_seconds"]

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if cell.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell.chips}"
            ).strip()
    try:
        import jax

        import calfkit_tpu  # noqa: F401 - the system under test must be around
    except ImportError as e:
        die(3, f"cannot import the system under test: {e}")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        die(2, f"JAX found no backend: {e}")
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        die(2, f"no TPU: jax.devices()[0].platform is {platform!r}")
    if len(devices) < cell.chips:
        die(2, f"cell {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
    if not args.rehearse:
        try:
            load_peaks(devices[0].device_kind)
        except ManifestError as e:
            die(4, str(e))
        # the program's own rule for the cache's place ($JAX_COMPILATION_CACHE_DIR,
        # else <checkout>/.jax_cache), applied before the first program compiles:
        # the engine applies it only when it is built, after the weights are made
        from calfkit_tpu.inference.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program goes to the persistent cache, however quick its compile,
        # and none is evicted: the cells' programs together pass a cap set in the
        # environment (192 MiB on the builder's chip machine; one cell's are 180-200
        # MB), and a run that finds half of them gone compiles inside its ramp-in
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_compilation_cache_max_size", -1)

    from benchmarks.harness import Run

    run = Run(cell, args.seed, seconds, bool(args.trace), args.rehearse, _T_PROCESS)
    result = asyncio.run(run.run())
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
