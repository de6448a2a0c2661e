"""Admission and batching: slots standing free while a request is queued,
as a share of all slot-seconds of the traced interval: the occupancy the
cell loses while a caller waits."""


def read(ctx):
    counters, reduced = ctx.trace_counters, ctx.trace_reduced
    if not counters or not reduced or "empty_slot_queued_s" not in counters:
        return None  # a program that keeps no admission ledger
    slots = ctx.runtime.max_batch_size
    return 100.0 * counters["empty_slot_queued_s"] / (slots * reduced["window_s"])
