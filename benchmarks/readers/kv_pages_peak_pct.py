"""KV pages: the most pages in use at once, against the pool."""


def read(ctx):
    if not ctx.pages_total:
        return None
    return 100.0 * ctx.pages_peak / ctx.pages_total
