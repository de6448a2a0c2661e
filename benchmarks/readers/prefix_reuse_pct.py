"""KV pages: share of prompt tokens served from cached prefix pages."""


def read(ctx):
    w = ctx.counters.get("window")
    if not w or not w["prefill_tokens"]:
        return None
    return 100.0 * w["prefix_reused_tokens"] / w["prefill_tokens"]
