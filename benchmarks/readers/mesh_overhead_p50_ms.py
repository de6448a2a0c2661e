"""Client and mesh: what the path outside the engine adds to first token."""

from benchmarks.metrics import percentile


def read(ctx):
    engine_ttft = {
        s.trace_id: s.attrs.get("ttft_ms") for s in ctx.spans
        if s.name == "engine.prefill" and s.attrs.get("ttft_ms") is not None
    }
    over = [
        (s.events[0][0] - s.sent) * 1e3 - engine_ttft[s.correlation_id]
        for s in ctx.samples
        if s.events and s.correlation_id in engine_ttft
    ]
    return percentile(over, 50)
