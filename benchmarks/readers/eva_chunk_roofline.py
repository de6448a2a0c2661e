"""Kernels: least time for the (query, key) pairs the traced prefill chunks'
own positions had to attend in their EVA layers, exact keys of their window
and pooled entries of the windows before it, and for the pooling of their
chunks (the engine's ``chunk_attn_pairs_eva_window``,
``chunk_attn_pairs_eva_summary`` and ``eva_chunks_pooled`` through the
architecture file's ``eva_chunk``: FLOPs or bytes over the chip's published
peak, whichever is larger) over the device time under
``chunk_loop/.../eva/attention`` and ``chunk_loop/.../eva/pool``, whatever
implements them.  An architecture without such a count, an engine without the
counters, or a trace without those scopes, reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers.eva_cache_roofline import under


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "eva_chunk", None)
    c = ctx.trace_counters
    if not r or not c or count is None or not c.get("chunk_attn_pairs_eva_window"):
        return None
    measured = sum(s for path, s in (r.get("by_scope") or {}).items()
                   if under(path, "chunk_loop", ("attention", "pool")))
    if measured <= 0:
        return None
    least, _ = roofline(
        count(ctx.config, c["chunk_attn_pairs_eva_window"],
              c.get("chunk_attn_pairs_eva_summary", 0), c.get("eva_chunks_pooled", 0), ctx.chips),
        ctx.peaks)
    return 100.0 * least / measured
