"""Kernels: least time of the work the traced dispatches did (decode steps
and prefilled prompt tokens, each by bytes or FLOPs over the chip's
published peak, whichever is larger) over the device time of the model
programs that did it."""

from benchmarks.readers._trace import least_seconds, model_seconds


def read(ctx):
    if not ctx.trace_reduced:
        return None
    measured = model_seconds(ctx)
    least = least_seconds(ctx) if measured > 0 else None
    if not least:
        return None
    return 100.0 * least / measured
