"""Kernels: least time for the GROUPED expert products the traced interval
ran over the device time of the kernels the TPU compiler runs them as
(``ragged-dot...``, which the trace shows under NO scope: taken by that
name from the trace's per-operation table, as ``moe_device_pct`` takes
them).  The work is the architecture file's ``expert_layer_step``, bytes or
FLOPs over the chip's published peak, whichever is larger: for every decode
step, where the program's own rule (``moe.dense_form`` at the slots'
count) runs the steps' products grouped, the experts the step really hit
(the engine's ``moe_experts_hit``) and the rows' chosen; for every chunk
that ran grouped (``moe_grouped_chunks``), the experts its tokens hit under
even routing (``experts_hit``) and the tokens' chosen.  The count holds the
gate beside the experts (a few thousandths of a layer's bytes), which the
kernel does not run.  An architecture without those counts, a program
without the rule or the counters, or a trace without such a kernel, reads
nothing."""

from benchmarks.opcount import least_seconds as roofline
from benchmarks.readers._trace import decode_steps


def read(ctx):
    r, c = ctx.trace_reduced, ctx.trace_counters
    count = getattr(ctx.arch, "expert_layer_step", None)
    hit_evenly = getattr(ctx.arch, "experts_hit", None)
    layers = getattr(ctx.model_config, "n_moe_layers", 0)
    if not r or not c or count is None or hit_evenly is None or layers <= 0:
        return None
    try:
        from calfkit_tpu.inference.moe import dense_form
    except ImportError:
        return None
    measured = sum(
        s for label, s in (r.get("own_by_op") or {}).items()
        if label.rsplit(" ", 1)[-1].startswith("ragged-dot")
    )
    if measured <= 0:
        return None
    least = 0.0
    steps = decode_steps(ctx)
    if steps > 0 and c.get("moe_experts_hit") and not dense_form(
            ctx.runtime.max_batch_size, ctx.model_config):
        rows = c["decode_tokens"] / steps
        hit = c["moe_experts_hit"] / (steps * layers)  # distinct experts a layer a step
        least += steps * layers * roofline(count(ctx.config, rows, hit, ctx.chips), ctx.peaks)[0]
    chunks = c.get("moe_grouped_chunks", 0) + c.get("moe_dense_chunks", 0)
    if c.get("moe_grouped_chunks"):
        tokens = c.get("prefill_tokens", 0) / chunks  # a chunk's real tokens, its rows together
        work = count(ctx.config, tokens, hit_evenly(ctx.config, tokens), ctx.chips)
        least += c["moe_grouped_chunks"] * layers * roofline(work, ctx.peaks)[0]
    return 100.0 * least / measured if least > 0 else None
