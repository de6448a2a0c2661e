"""Model step: share of device busy time in operations under a
``shortconv`` scope (the gated short convolutions' in_proj, conv and
out_proj, in decode steps and prefill chunks alike).  A program that names
no such scope (a model without those layers, a build before them) reads
nothing."""


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("busy_s"):
        return None
    under = [s for path, s in (r.get("by_scope") or {}).items() if "shortconv" in path.split("/")]
    if not under:
        return None
    return 100.0 * sum(under) / r["busy_s"]
