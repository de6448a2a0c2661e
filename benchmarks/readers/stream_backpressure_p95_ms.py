"""Node and agent: what the stream's consumer holds it an event: the time
``request_stream`` stood suspended in its ``yield`` of a text delta (the
token tap, the step's wire message, the publish and its acknowledgement).
Per request due in the window ``backpressure_ms`` / ``events`` of its
``engine.decode`` span; 95th percentile."""

from benchmarks.readers._stream import p95_per_request


def read(ctx):
    return p95_per_request(
        ctx, "stream_backpressure_p95_ms",
        lambda s: s.attrs["backpressure_ms"] / s.attrs["events"] if s.attrs.get("events") else None,
        key="backpressure_ms")
