"""Kernels: least time to read the live latent tokens of the traced decode
steps' rows once a layer and to score them (the architecture file's
``latent_read_step``; the tokens from the engine's ``decode_pages_live``,
the pages the active rows held, summed over steps, times the page size, so
a row's last page counts whole) over the device time under
``decode_loop/.../mla`` in the ``attention``, ``absorb`` and
``gather_window`` scopes: the absorbed read and what it costs to get the
window in front of it.  An architecture without such a count, or a trace
without those scopes, reads nothing."""

from benchmarks.opcount import least_seconds as roofline

PARTS = {"attention", "absorb", "gather_window"}


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "latent_read_step", None)
    c = ctx.trace_counters
    if not r or not c or count is None or not c.get("decode_pages_live"):
        return None
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items()
        if {"decode_loop", "mla"} <= set(path.split("/")) and PARTS & set(path.split("/"))
    )
    if measured <= 0:
        return None
    tokens = c["decode_pages_live"] * ctx.runtime.page_size  # summed over the steps run
    least, _ = roofline(count(ctx.config, tokens, ctx.chips), ctx.peaks)
    return 100.0 * least * ctx.model_config.n_layers / measured
