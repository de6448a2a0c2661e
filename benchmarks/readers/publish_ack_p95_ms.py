"""Client and mesh: a token event's publish, to the broker's
acknowledgement, from inside: per request due in the window ``publish_ms`` /
``token_events`` of its ``agent.turn`` span(s); 95th percentile.  The log
line gives the longest single publish (``publish_max_ms``) and what building
an event's wire message cost beside it (``step_build_ms`` / ``token_events``,
the median request)."""

from benchmarks.metrics import percentile
from benchmarks.readers._stream import p95_per_request


def read(ctx):
    def an_event(key):
        return lambda s: s.attrs[key] / s.attrs["token_events"] if s.attrs.get("token_events") else None

    return p95_per_request(
        ctx, "publish_ack_p95_ms", an_event("publish_ms"), name="agent.turn", key="publish_ms",
        longest_publish_ms=lambda spans: max(
            (s.attrs.get("publish_max_ms", 0.0) for s in spans), default=None),
        step_build_p50_ms=lambda spans: percentile(
            [v for v in map(an_event("step_build_ms"), spans) if v is not None], 50))
