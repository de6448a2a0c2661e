"""Admission and batching: from a dispatch's landing to the request's
consumer taking its block (the hop from the tick thread, the loop's turn,
the task's wake-up).  Per request due in the window ``deliver_wait_ms`` /
``blocks`` of its ``engine.decode`` span; 95th percentile.  The log line
gives the longest single wait (``deliver_wait_max_ms``) too: a stall of
the loop shows there first."""

from benchmarks.readers._stream import p95_per_request


def read(ctx):
    return p95_per_request(
        ctx, "stream_deliver_wait_p95_ms",
        lambda s: s.attrs["deliver_wait_ms"] / s.attrs["blocks"] if s.attrs.get("blocks") else None,
        key="deliver_wait_ms",
        longest_wait_ms=lambda spans: max(
            (s.attrs.get("deliver_wait_max_ms", 0.0) for s in spans), default=None))
