"""Node and agent: what a text delta costs to make (detokenize, stop
search, the delta), from the engine's ``stream_emit_s`` and
``stream_events`` by difference over the traced interval."""


def read(ctx):
    counters = ctx.trace_counters
    if not counters or "stream_emit_s" not in counters or "stream_events" not in counters:
        return None  # a program that keeps no such counters
    events = counters["stream_events"]
    return 1e3 * counters["stream_emit_s"] / events if events else 0.0
