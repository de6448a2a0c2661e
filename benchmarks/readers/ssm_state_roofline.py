"""Kernels: least time to read and write the recurrent state of the rows
the traced decode steps really had (the architecture file's
``recurrent_state_step``: bytes or FLOPs over the chip's published peak,
whichever is larger) over the device time under ``decode_loop/mamba/ssm``
and ``decode_loop/mamba/conv``, the two scopes that touch that state.
An architecture without such a count, or a trace without those scopes,
reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers._trace import decode_steps


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "recurrent_state_step", None)
    if not r or not ctx.trace_counters or count is None:
        return None
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items()
        if {"decode_loop", "mamba"} <= set(path.split("/")) and path.split("/")[-1] in ("ssm", "conv")
    )
    steps = decode_steps(ctx)
    if measured <= 0 or steps <= 0:
        return None
    rows = ctx.trace_counters["decode_tokens"] / steps
    least, _ = roofline(count(ctx.config, rows, ctx.chips), ctx.peaks)
    return 100.0 * least * steps / measured
