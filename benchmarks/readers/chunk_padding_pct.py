"""Admission and batching: of the positions the prefill chunks launched in
the measured window computed (the engine's ``chunk_tokens``: rows x chunk),
the share that held no prompt token (``chunk_tokens_padding``).  An engine
without the counters, or a window without a chunk, reads nothing."""


def read(ctx):
    w = ctx.counters.get("window") or {}
    if not w.get("chunk_tokens"):
        return None
    return 100.0 * w.get("chunk_tokens_padding", 0) / w["chunk_tokens"]
