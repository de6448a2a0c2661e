"""Kernels: least time of the chunkwise delta rule for the prompt tokens the
traced interval prefilled (the engine's ``prefill_tokens``; the architecture
file's ``recurrent_chunk``: FLOPs over the chip's published bf16 peak) over
the device time under ``chunk_loop/.../gdn/state``, the scope that holds the
pass whatever implements it.  An architecture without such a count, an
interval that prefilled nothing, or a trace without that scope, reads
nothing."""

from benchmarks.opcount import least_seconds as roofline


def read(ctx):
    r, c = ctx.trace_reduced, ctx.trace_counters
    count = getattr(ctx.arch, "recurrent_chunk", None)
    if not r or not c or count is None:
        return None
    measured = 0.0
    for path, seconds in (r.get("by_scope") or {}).items():
        parts = path.split("/")
        if "chunk_loop" in parts and "gdn" in parts and "state" in parts[parts.index("gdn"):]:
            measured += seconds
    tokens = c.get("prefill_tokens", 0)
    if measured <= 0 or tokens <= 0:
        return None
    least, _ = roofline(count(ctx.config, tokens, ctx.chips), ctx.peaks)
    return 100.0 * least / measured
