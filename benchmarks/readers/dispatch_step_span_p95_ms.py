"""Model step: the tail of what a decode step took, a dispatch, over the
whole measured window: the ``engine.dispatch`` spans the engine ends at the
host sync that proved each dispatch complete, ``exclusive_ms`` (its end less
the later of its enqueue and the previous dispatch's end) over ``steps``.
Dispatches proved by ONE sync (a wave's landing proves the dispatch it rode
and, often, the one before it) ended together and only their sum is known:
they count as one, their milliseconds over their steps.  The 95th
percentile; the median and the mean over steps are logged beside it (the
mean is the twin of ``dispatch_step_ms``, the device's MEAN over 8 traced
seconds, plus the idle share).  Nothing where fewer than 20 ended in
the window, or the program ends no such span."""

import json
import time
from collections import defaultdict

from benchmarks.metrics import percentile

MIN_SPANS = 20


def groups_in_window(ctx):
    """How many spans ended inside the window, and (ms, steps) of each
    group of them that one sync proved."""
    to_monotonic = time.perf_counter() - time.time()  # a span's start is on the wall clock
    groups = defaultdict(lambda: [0.0, 0])
    n = 0
    for s in ctx.spans:
        if s.name != "engine.dispatch" or not s.attrs.get("steps"):
            continue
        ended = s.start_s + s.duration_ms / 1e3 + to_monotonic
        if ctx.t0 <= ended < ctx.t_end:
            group = groups[s.attrs.get("proved_by", s.attrs.get("seq"))]
            group[0] += s.attrs["exclusive_ms"]
            group[1] += s.attrs["steps"]
            n += 1
    return n, list(groups.values())


def read(ctx):
    n, groups = groups_in_window(ctx)
    if n < MIN_SPANS:
        return None
    values = [ms / steps for ms, steps in groups]
    p95 = percentile(values, 95)
    print(json.dumps({"phase": "reader", "metric": "dispatch_step_span_p95_ms", "spans": n,
                      "syncs": len(values), "p50_ms": percentile(values, 50), "p95_ms": p95,
                      "max_ms": max(values),
                      "mean_ms": sum(ms for ms, _ in groups) / sum(steps for _, steps in groups)}),
          flush=True)
    return p95
