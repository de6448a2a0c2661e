"""Client and mesh: the whole outer road of a request's LAST block, on one
clock: the caller's last token event (the harness's ``perf_counter``) less
the landing of the dispatch that carried the request's last tokens (the
``engine.decode`` span's ``start_s`` + ``last_landed_ms``, moved onto the
harness's clock by ``_stream.to_monotonic``: both are one process's).  Per
request due in the window; 95th percentile.  The log line holds what shows a
wrong join and an account that does not close: the least road of a first
and of a last block (negative = a wrong join), and the median request's
``engine.decode`` self time (duration less ``block_wait_ms``, ``emit_ms``
and ``backpressure_ms``) as a share of its duration, with the median shares
of the three stages beside it."""

import json

from benchmarks.metrics import percentile
from benchmarks.readers._stream import accounts, to_monotonic

STAGES = ("block_wait_ms", "emit_ms", "backpressure_ms")


def read(ctx):
    by_request = accounts(ctx)
    if by_request is None:
        return None
    shift = to_monotonic()
    first, last, own, shares = [], [], [], {k: [] for k in STAGES}
    for sample in ctx.samples:
        spans = by_request.get(sample.correlation_id)
        if not spans or not sample.events:
            continue
        span = spans[-1]  # (a request is one model turn here)
        began = span.start_s + shift
        first.append((sample.events[0][0] - began) * 1e3 - span.attrs["first_landed_ms"])
        last.append((sample.events[-1][0] - began) * 1e3 - span.attrs["last_landed_ms"])
        if span.duration_ms > 0:
            staged = [span.attrs.get(k, 0.0) for k in STAGES]
            own.append(100.0 * (span.duration_ms - sum(staged)) / span.duration_ms)
            for k, v in zip(STAGES, staged):
                shares[k].append(100.0 * v / span.duration_ms)
    p95 = percentile(last, 95) or 0.0
    print(json.dumps({
        "phase": "reader", "metric": "stream_path_p95_ms", "requests": len(last),
        "p50_ms": percentile(last, 50), "p95_ms": p95, "max_ms": max(last, default=None),
        "least_first_block_road_ms": min(first, default=None),
        "least_last_block_road_ms": min(last, default=None),
        "first_block_road_p50_ms": percentile(first, 50),
        "decode_self_time_p50_pct": percentile(own, 50),
        "decode_self_time_max_pct": max(own, default=None),
        **{k[:-3] + "_p50_pct": percentile(v, 50) for k, v in shares.items()}}), flush=True)
    return p95
