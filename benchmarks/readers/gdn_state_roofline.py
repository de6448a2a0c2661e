"""Kernels: least time to read and write the delta-rule state ``S`` and the
conv tail of the rows the traced decode steps really had (the architecture
file's ``recurrent_state_step``: bytes or FLOPs over the chip's published
peak, whichever is larger) over the device time under
``decode_loop/gdn/state`` and ``decode_loop/gdn/conv``, the two scopes that
touch that state, whatever implements the pass.  An architecture without
such a count, or a trace without those scopes, reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers._trace import decode_steps


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "recurrent_state_step", None)
    if not r or not ctx.trace_counters or count is None:
        return None
    measured = 0.0
    for path, seconds in (r.get("by_scope") or {}).items():
        parts = path.split("/")
        if "decode_loop" in parts and "gdn" in parts and (
                {"state", "conv"} & set(parts[parts.index("gdn"):])):
            measured += seconds
    steps = decode_steps(ctx)
    if measured <= 0 or steps <= 0:
        return None
    rows = ctx.trace_counters["decode_tokens"] / steps
    least, _ = roofline(count(ctx.config, rows, ctx.chips), ctx.peaks)
    return 100.0 * least * steps / measured
