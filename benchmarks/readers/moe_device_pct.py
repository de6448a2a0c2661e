"""Model step: share of device busy time in the expert layers: operations
under a ``moe`` scope (router, group, experts, combine and shared expert, in
decode steps and prefill chunks alike) and the grouped expert products of
the wide chunks, which the TPU compiler runs as kernels of its own name
(``ragged-dot...``) and the trace shows under NO scope: they are counted by
that name, from the trace's per-operation table.  A program that names no
such scope (a model without routed experts, a build before them) reads
nothing."""


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("busy_s"):
        return None
    under = [s for path, s in (r.get("by_scope") or {}).items() if "moe" in path.split("/")]
    if not under:
        return None
    grouped = [  # "<scope path> <operation>": the kernels a scope did not survive on
        s for label, s in (r.get("own_by_op") or {}).items()
        if label.rsplit(" ", 1)[-1].startswith("ragged-dot")
        and "moe" not in label.rsplit(" ", 1)[0].split("/")
    ]
    return 100.0 * (sum(under) + sum(grouped)) / r["busy_s"]
