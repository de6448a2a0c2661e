"""KV pages: of the token-layers the live rows would hold if EVERY layer
kept every token, the share the window layers have given back, over the
measured window's decode steps: from the engine's
``decode_global_tokens_read`` (rows x len x global layers a step) and
``decode_window_tokens_read`` (rows x min(len, W) x window layers).  A model
without window layers counts neither and reads nothing."""


def read(ctx):
    c = ctx.counters.get("window") or {}
    kept_global = c.get("decode_global_tokens_read", 0)
    kept_window = c.get("decode_window_tokens_read", 0)
    m = ctx.model_config
    n_global = getattr(m, "n_global_layers", 0)
    if not kept_global or not kept_window or not n_global:
        return None
    uniform = kept_global / n_global * m.n_kv_layers
    return 100.0 * (uniform - kept_global - kept_window) / uniform
