"""Kernels: least time to read the keys and values of the entries the traced
decode steps' rows really had in their EVA layers, the exact ones of their own
window and the pooled ones behind it (the engine's
``decode_eva_window_tokens_read`` and ``decode_eva_summaries_read``: rows x
live entries x layers, summed over the steps run, through the architecture
file's ``eva_cache_step``: bytes or FLOPs over the chip's published peak,
whichever is larger) over the device time under
``decode_loop/.../eva/attention`` and ``decode_loop/.../eva/merge``, whatever
implements the read.  An architecture without such a count, an engine without
the counters, or a trace without those scopes, reads nothing."""

from benchmarks.opcount import least_seconds as roofline


def under(path: str, loop: str, inner: tuple) -> bool:
    """Is ``path`` under ``loop/.../eva/<one of inner>``?"""
    parts = path.split("/")
    return loop in parts and any(
        a == "eva" and b in inner for a, b in zip(parts, parts[1:]))


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "eva_cache_step", None)
    c = ctx.trace_counters
    if not r or not c or count is None:
        return None
    exact, pooled = c.get("decode_eva_window_tokens_read"), c.get("decode_eva_summaries_read")
    if not exact or pooled is None:
        return None
    measured = sum(s for path, s in (r.get("by_scope") or {}).items()
                   if under(path, "decode_loop", ("attention", "merge")))
    if measured <= 0:
        return None
    least, _ = roofline(count(ctx.config, exact, pooled, ctx.chips), ctx.peaks)
    return 100.0 * least / measured
