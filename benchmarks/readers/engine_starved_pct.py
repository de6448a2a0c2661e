"""Admission and batching: the share of the traced interval in which rows
were active and nothing was in flight, by the engine's own clock
(``starved_s``: from a landing to the next enqueue).  The program's
account of ``device_idle_closed_pct``, which also holds launch latency
and the gaps between the programs of one dispatch."""


def read(ctx):
    counters, reduced = ctx.trace_counters, ctx.trace_reduced
    if not counters or not reduced or "starved_s" not in counters:
        return None  # a program that keeps no such counter
    return 100.0 * counters["starved_s"] / reduced["window_s"]
