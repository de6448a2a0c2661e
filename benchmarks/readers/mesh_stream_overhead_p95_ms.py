"""Client and mesh (the node's and the agent's share too): what the path
outside the engine adds to a request's time per output token.  Per request
due in the window, joined by correlation id: the client's (last - first
token event) / (tokens - 1), which is ``tpot_p95_ms``'s own quantity, less
the request's ``engine.decode`` span (first token consumed from the engine
to the end of its stream) / (``generated_tokens`` - 1).  95th percentile.
Needs a span that ends where the stream ended (``last_seq`` marks it): one
that ends after the stream was closed holds a scheduler pass more."""

from benchmarks.metrics import percentile


def read(ctx):
    engine = {
        s.trace_id: s.duration_ms / (s.attrs["generated_tokens"] - 1)
        for s in ctx.spans
        if s.name == "engine.decode" and s.status == "ok" and "last_seq" in s.attrs
        and s.attrs.get("generated_tokens", 0) > 1
    }
    over = [s.tpot_ms - engine[s.correlation_id] for s in ctx.samples
            if s.tpot_ms is not None and s.correlation_id in engine]
    return percentile(over, 95)
