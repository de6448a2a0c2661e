"""Shared by the readers that take a number from the device trace.

The engine's model programs, by the names the trace gives their XLA
modules: a dispatch is ``jit_decode`` (decode steps only) or
``jit_ragged_*`` (the same decode steps with one prefill chunk of the
wave being admitted riding along); the others carry prefill alone.  Until
the program names its scopes the trace cannot split a ragged program into
its decode and its chunk part, so the step time below holds both.

What work a step is comes from the cell's architecture (``ctx.arch``:
``decode_step``, ``prefill_chunk``, ``weight_bytes``); the roofline that
turns work into least seconds is shared.
"""

from benchmarks import trace_reduce
from benchmarks.opcount import least_seconds as roofline

DISPATCH_MODULES = [r"^jit_decode$", r"^jit_ragged_"]
PREFILL_MODULES = [r"^jit_ragged_", r"^jit_chunk_step$", r"^jit_finalize$", r"^jit_seed$",
                   r"^jit_prefill$"]
MODEL_MODULES = sorted(set(DISPATCH_MODULES + PREFILL_MODULES))


def decode_steps(ctx) -> float:
    """Decode steps run inside the traced interval, from the engine's
    dispatch counters there (short dispatches run fewer steps)."""
    c = ctx.trace_counters
    full = ctx.runtime.decode_steps_per_dispatch
    short = min(full, max(4, full // 4))
    return (c["decode_dispatches"] - c["short_dispatches"]) * full + c["short_dispatches"] * short


def model_seconds(ctx) -> float:
    return trace_reduce.module_seconds(ctx.trace_reduced, MODEL_MODULES)


def dispatch_step_ms(ctx):
    """Device time of the model programs for each decode step run: what a
    decoding row waits for a token on the device, chunk work included."""
    if not ctx.trace_reduced or not ctx.trace_counters:
        return None
    steps = decode_steps(ctx)
    seconds = model_seconds(ctx)
    if steps <= 0 or seconds <= 0:
        return None
    return seconds * 1e3 / steps


def least_seconds(ctx):
    """The roofline of the work the traced interval did: its decode steps
    at the rows and contexts it really had (weights once a step and the KV
    attended) plus the prompt tokens it prefilled (their FLOPs; the weights
    once for each dispatch that carried a chunk)."""
    if not ctx.trace_counters:
        return None
    c = ctx.trace_counters
    steps = decode_steps(ctx)
    seen = [s for s in ctx.samples if s.tokens]
    if steps <= 0 or not seen:
        return None
    rows = c["decode_tokens"] / steps
    prompt = sum(s.prompt_tokens for s in seen) / len(seen)
    context = prompt + sum(s.tokens for s in seen) / len(seen) / 2.0
    step = ctx.arch.decode_step(ctx.config, rows, context, ctx.chips)
    total, _ = roofline(step, ctx.peaks)
    total *= steps
    tokens = c.get("prefill_tokens", 0)
    if tokens > 0:  # as tokens / prompt prompts of the mean length, weights once a chunk dispatch
        work = ctx.arch.prefill_chunk(ctx.config, tokens / prompt, prompt, 0, ctx.chips)
        work["bytes"] += ctx.arch.weight_bytes(ctx.config) / ctx.chips * max(
            c.get("unified_dispatches", 1) - 1, 0)
        total += roofline(work, ctx.peaks)[0]
    return total
