"""Kernels: least time for the gated short convolutions of the traced
decode steps (the architecture file's ``shortconv_step``: every conv
mixer's ``W_in``, taps and ``W_out`` read once a step, the rows' tails read
and written, the two products; bytes or FLOPs over the chip's published
peak, whichever is larger) over the device time under
``decode_loop/.../shortconv``, the scope that holds the whole mixer whatever
implements it.  An architecture without such a count, or a trace without
that scope, reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers._trace import decode_steps


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "shortconv_step", None)
    if not r or not ctx.trace_counters or count is None:
        return None
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items()
        if {"decode_loop", "shortconv"} <= set(path.split("/"))
    )
    steps = decode_steps(ctx)
    if measured <= 0 or steps <= 0:
        return None
    rows = ctx.trace_counters["decode_tokens"] / steps
    least, _ = roofline(count(ctx.config, rows, ctx.chips), ctx.peaks)
    return 100.0 * least * steps / measured
