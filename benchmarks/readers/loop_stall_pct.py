"""Admission and batching: the share of the traced interval the engine's
event loop was held past its 20 ms heartbeat (``loop_stall_s`` by
difference: the lateness of every beat that came more than a period late).
A stall with no sync in it, which no phase and no gap class names."""


def read(ctx):
    counters, reduced = ctx.trace_counters, ctx.trace_reduced
    if not counters or not reduced or "loop_stall_s" not in counters:
        return None  # a program that keeps no such counter
    return 100.0 * counters["loop_stall_s"] / reduced["window_s"]
