"""Admission and batching: what a dispatch costs the host outside the wait
for the device, from the engine's exclusive phase clock over the traced
interval.  It moves ``tpot_p95_ms`` once it nears the device time of a
dispatch: until then the host is hidden behind the sync."""

HOST_PHASES = ("phase_reap_s", "phase_admit_s", "phase_handoff_s", "phase_prep_s",
               "phase_enqueue_s", "phase_fanout_s")


def read(ctx):
    counters = ctx.trace_counters
    if not counters or any(p not in counters for p in HOST_PHASES):
        return None  # a program that keeps no phase clock
    if not counters.get("decode_dispatches"):
        return None
    return 1e3 * sum(counters[p] for p in HOST_PHASES) / counters["decode_dispatches"]
