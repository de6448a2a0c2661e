"""Admission: submit-to-slot-grant wait of the requests due in the window,
from the ``engine.queue`` spans the engine measures where the wait
happens, exact (the bucketed reading of the engine's histogram, 18-23% high,
went with PR 26)."""

from benchmarks.metrics import percentile


def read(ctx):
    due = {s.correlation_id for s in ctx.samples if s.correlation_id}
    waits = [s.duration_ms for s in ctx.spans
             if s.name == "engine.queue" and s.status == "ok" and s.trace_id in due]
    return percentile(waits, 95)  # None where the program ends no such span
