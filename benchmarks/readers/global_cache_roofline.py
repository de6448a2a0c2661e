"""Kernels: least time to read the keys and values of the tokens the rows of
the traced decode steps really had, a global layer (the engine's
``decode_global_tokens_read``: rows x len x global layers, summed over the
steps run, through the architecture file's ``global_layers_step``: bytes or
FLOPs over the chip's published peak, whichever is larger) over the device
time under ``decode_loop/.../attention/global``, whatever implements the
read.  An architecture without such a count, an engine without the counter,
or a trace without that scope, reads nothing."""

from benchmarks.opcount import least_seconds as roofline


def under_global(path: str) -> bool:
    parts = path.split("/")
    return "decode_loop" in parts and any(
        a == "attention" and b == "global" for a, b in zip(parts, parts[1:]))


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "global_layers_step", None)
    c = ctx.trace_counters
    if not r or not c or count is None or not c.get("decode_global_tokens_read"):
        return None
    measured = sum(s for path, s in (r.get("by_scope") or {}).items() if under_global(path))
    if measured <= 0:
        return None
    least, _ = roofline(
        count(ctx.config, c.get("decode_tokens", 0), c["decode_global_tokens_read"], ctx.chips),
        ctx.peaks)
    return 100.0 * least / measured
