"""Model step: share of device busy time in the sliding-window layers'
attention cores: operations under ``.../attention/window`` (the decode
steps' read of a row's ring and its merge with the fresh tokens, and the
prefill chunks' key-block loop, alike).  A program that names no such scope
(a model without window layers, a build before them) reads nothing."""


def under_window(path: str) -> bool:
    parts = path.split("/")
    return any(a == "attention" and b == "window" for a, b in zip(parts, parts[1:]))


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("busy_s"):
        return None
    under = [s for path, s in (r.get("by_scope") or {}).items() if under_window(path)]
    if not under:
        return None
    return 100.0 * sum(under) / r["busy_s"]
