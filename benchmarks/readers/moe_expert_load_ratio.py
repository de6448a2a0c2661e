"""Model step: the busiest expert's tokens over the mean expert's, each
summed over expert layers and dispatches of the window (the engine's
``moe_expert_tokens_max`` / ``moe_expert_tokens_mean``): 1 is even routing,
and the busiest expert's group is what a grouped product waits for.  A
program without the counters reads nothing."""


def read(ctx):
    c = (ctx.counters or {}).get("window") or {}
    if not c.get("moe_expert_tokens_mean"):
        return None
    return c["moe_expert_tokens_max"] / c["moe_expert_tokens_mean"]
