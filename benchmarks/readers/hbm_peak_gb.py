"""Device: peak HBM in use on the fullest chip."""


def read(ctx):
    peak = ctx.device().get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
