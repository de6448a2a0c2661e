"""Node and agent: the agent turn's own time around its engine call."""

from benchmarks.metrics import percentile


def read(ctx):
    child = {s.parent_span_id: s.duration_ms for s in ctx.spans if s.name == "engine.generate"}
    over = [s.duration_ms - child[s.span_id] for s in ctx.spans
            if s.name == "agent.turn" and s.span_id in child]
    return percentile(over, 50)
