"""Model step: share of device busy time in programs that carried prefill."""

from benchmarks import trace_reduce
from benchmarks.readers._trace import PREFILL_MODULES


def read(ctx):
    r = ctx.trace_reduced
    if not r or not r.get("busy_s"):
        return None
    return 100.0 * trace_reduce.module_seconds(r, PREFILL_MODULES) / r["busy_s"]
