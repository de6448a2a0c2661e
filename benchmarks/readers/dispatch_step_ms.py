"""Model step: device time of the engine's model programs a decode step."""

from benchmarks.readers._trace import dispatch_step_ms


def read(ctx):
    return dispatch_step_ms(ctx)
