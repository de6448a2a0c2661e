"""KV pages: of the token-layers the live rows would hold if EVERY layer kept
every token, the share an EVA stack's two caches hold, over the measured
window's decode steps: from the engine's ``decode_eva_window_tokens_read``
(rows x the exact entries of the query's own window x layers a step) and
``decode_eva_summaries_read`` (rows x the pooled entries behind it x layers).
A query at ``q`` has ``q mod W + 1`` exact entries and ``(W / c) (q // W)``
pooled ones where a global layer would hold ``q + 1``: the uniform count is
recovered from the two (``exact + pooled x c``).  A model without EVA layers
counts neither and reads nothing."""


def read(ctx):
    c = ctx.counters.get("window") or {}
    exact, pooled = c.get("decode_eva_window_tokens_read", 0), c.get("decode_eva_summaries_read", 0)
    chunk = getattr(ctx.model_config, "chunk_size", 0)
    if not exact or not chunk:
        return None
    return 100.0 * (exact + pooled) / (exact + pooled * chunk)
