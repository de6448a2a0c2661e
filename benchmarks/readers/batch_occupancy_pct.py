"""Batching: mean share of the slots that took part in a decode dispatch."""


def read(ctx):
    w = ctx.counters.get("window")
    if not w or not w["decode_dispatches"]:
        return None
    return 100.0 * w["occupancy_sum"] / w["decode_dispatches"]
