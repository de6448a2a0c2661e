"""Kernels: least time to multiply the (query, key) pairs the traced prefill
chunks' OWN positions had to attend (the engine's ``chunk_attn_pairs_window``
and ``chunk_attn_pairs_global``: ``sum_q min(q + 1, W)`` a window layer,
``sum_q (q + 1)`` a global one; ``4 x query heads x head_dim`` FLOPs a pair,
the QK and the PV product, over the chip's published bf16 peak) over the
device time under ``chunk_loop/.../attention/window`` and
``.../attention/global``, whatever implements the attention.  The pairs are
the needed ones, never the visited.  An engine without the counters, a
program without chunks in the interval, or a trace without those scopes,
reads nothing."""


def under_chunk_attention(path: str) -> bool:
    parts = path.split("/")
    return "chunk_loop" in parts and any(
        a == "attention" and b in ("window", "global") for a, b in zip(parts, parts[1:]))


def read(ctx):
    r, c = ctx.trace_reduced, ctx.trace_counters
    if not r or not c:
        return None
    pairs = c.get("chunk_attn_pairs_window", 0) + c.get("chunk_attn_pairs_global", 0)
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items() if under_chunk_attention(path))
    if pairs <= 0 or measured <= 0:
        return None
    flops = 4.0 * ctx.config["num_attention_heads"] * ctx.config["head_dim"] * pairs
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / measured
