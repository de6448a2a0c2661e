"""Kernels: least time to read the experts the traced decode steps really
hit (the engine's ``moe_experts_hit``: distinct experts a step read, summed
over expert layers and steps), the shared expert and the gate, and to
multiply the rows' chosen and shared experts (the architecture file's
``expert_layer_step``: bytes or FLOPs over the chip's published peak,
whichever is larger) over the device time under ``decode_loop/.../moe``.
An architecture without such a count, a program without the counter, or a
trace without those scopes, reads nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers._trace import decode_steps


def read(ctx):
    r = ctx.trace_reduced
    count = getattr(ctx.arch, "expert_layer_step", None)
    c = ctx.trace_counters
    if not r or not c or count is None or not c.get("moe_experts_hit"):
        return None
    measured = sum(
        s for path, s in (r.get("by_scope") or {}).items()
        if {"decode_loop", "moe"} <= set(path.split("/"))
    )
    steps = decode_steps(ctx)
    layers = getattr(ctx.model_config, "n_moe_layers", 0)
    if measured <= 0 or steps <= 0 or layers <= 0:
        return None
    rows = c["decode_tokens"] / steps
    hit = c["moe_experts_hit"] / (steps * layers)  # distinct experts a layer a step
    least, _ = roofline(count(ctx.config, rows, hit, ctx.chips), ctx.peaks)
    return 100.0 * least * steps * layers / measured
