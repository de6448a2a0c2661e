"""Admission and batching: of the dispatches landed in the traced interval,
the share whose pipeline was drained: host syncs that left the device KNOWN
EMPTY (every program the engine had enqueued proved complete, by the
engine's own numbering: ``pipeline_drains``) while rows were active or a
wave was in flight.  After each of them the device idles until the host's
next enqueue, which is what ``engine_starved_pct`` adds up in seconds.  A
program without the counter reads nothing."""


def read(ctx):
    c = ctx.trace_counters
    if not c or "pipeline_drains" not in c or not c.get("decode_dispatches"):
        return None
    return 100.0 * c["pipeline_drains"] / c["decode_dispatches"]
