"""Shared by the readers of a token's road from its dispatch's landing to the
caller (ISSUE 52, as PR 50 built it): the stage account a request's ``engine.decode`` span ends
with (``blocks``, ``events``, ``first_landed_ms`` / ``last_landed_ms``,
``deliver_wait_ms``, ``block_wait_ms``, ``emit_ms``, ``backpressure_ms``) and
its ``agent.turn``'s (``token_events``, ``publish_ms``), over the requests due
in the window, joined by correlation id as ``mesh_stream_overhead_p95_ms``
joins them.

A program without the account (the parent) ends its spans without these
attributes: ``accounts`` is then None and every reader returns None.  With
the account and nothing booked (no request due finished) a reader returns
0.0: the result line holds a number for every metric its cell registers.
"""

import json
import time

from benchmarks.metrics import percentile


def accounts(ctx, name="engine.decode", key="last_landed_ms"):
    """{correlation id: [span, ...]} of the finished ``name`` spans of the
    requests due in the window that carry ``key``; None where no span of
    that name carries it at all (a program without the account)."""
    spans = [s for s in ctx.spans if s.name == name and key in s.attrs]
    if not spans:
        return None
    due = {s.correlation_id for s in ctx.samples if s.correlation_id}
    out = {}
    for s in spans:
        if s.status == "ok" and s.trace_id in due:
            out.setdefault(s.trace_id, []).append(s)
    return out


def p95_per_request(ctx, metric, value, name="engine.decode", key="last_landed_ms", **log):
    """The 95th percentile of ``value(span)`` over the requests' spans (None
    from ``value``: the span has nothing to divide by and is left out), with
    a line of the run's log beside it."""
    by_request = accounts(ctx, name, key)
    if by_request is None:
        return None
    spans = [s for group in by_request.values() for s in group]
    values = [v for v in map(value, spans) if v is not None]
    p95 = percentile(values, 95) or 0.0
    print(json.dumps({"phase": "reader", "metric": metric, "requests": len(by_request),
                      "values": len(values), "p50_ms": percentile(values, 50), "p95_ms": p95,
                      "max_ms": max(values, default=None),
                      **{k: f(spans) for k, f in log.items()}}), flush=True)
    return p95


def to_monotonic():
    """Seconds to add to a span's ``start_s`` (the wall clock, taken with
    the span's ``perf_counter`` start in one breath) to stand on the
    harness's ``perf_counter``: the two clocks' distance now, as
    ``dispatch_step_span_p95_ms`` takes it.  Good to the wall clock's slew
    over the run (microseconds against a road of milliseconds)."""
    return time.perf_counter() - time.time()
