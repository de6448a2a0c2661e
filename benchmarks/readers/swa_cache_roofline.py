"""Kernels: least time to read the keys and values of ``min(len, W)``
tokens of the rows the traced decode steps really had, a window layer (the
engine's ``decode_window_tokens_read``: rows x min(len, W) x window layers,
summed over the steps run, through the architecture file's
``window_layers_step``: bytes or FLOPs over the chip's published peak,
whichever is larger) over the device time under
``decode_loop/.../attention/window``, whatever implements the read.  An
architecture without such a count, an engine without the counter, or a
trace without that scope, reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers.swa_device_pct import under_window


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "window_layers_step", None)
    c = ctx.trace_counters
    if not r or not c or count is None or not c.get("decode_window_tokens_read"):
        return None
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items()
        if "decode_loop" in path.split("/") and under_window(path))
    if measured <= 0:
        return None
    rows = c.get("decode_tokens", 0)
    least, _ = roofline(
        count(ctx.config, rows, c["decode_window_tokens_read"], ctx.chips), ctx.peaks)
    return 100.0 * least / measured
