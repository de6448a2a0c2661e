"""Model step: share of device busy time in an EVA layer's mixer: operations
under an ``eva`` scope (the projections, the read of the row's ring and of
its summary pages, their merge, the pooling and the output projection, in
decode steps and prefill chunks alike).  A program that names no such scope
(a model without EVA layers, a build before them) reads nothing."""


def under_eva(path: str) -> bool:
    return "eva" in path.split("/")


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("busy_s"):
        return None
    under = [s for path, s in (r.get("by_scope") or {}).items() if under_eva(path)]
    if not under:
        return None
    return 100.0 * sum(under) / r["busy_s"]
