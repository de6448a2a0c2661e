"""Device: share of the traced window in which no operation ran."""


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("window_s") or not r.get("busy_s"):
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
