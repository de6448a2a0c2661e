"""Admission: submit-to-prefill-start wait, from the engine's own bucketed
observations inside the window (interpolated inside the bucket)."""


def read(ctx):
    window = ctx.counters.get("window")
    if not window:
        return None
    counts, edges = window["queue_counts"], window["queue_buckets"]
    total = sum(counts)
    if not total:
        return None
    rank, seen = 0.95 * total, 0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            lo = edges[i - 1] if i else 0.0
            hi = edges[i] if i < len(edges) else edges[-1]
            return lo + (hi - lo) * (rank - seen) / c
        seen += c
    return float(edges[-1])
