"""Admission and batching: the tick's side of a stall.  The share of the
WHOLE window (not the traced interval: a stall is rare, and ``tpot_p95_ms``
is over the window) that lay in phases other than ``idle`` which outlasted
what the two-deep device queue hides, a host phase one dispatch's wall time
and a ``sync`` four (``phase_long_s`` by difference; the whole phase's
seconds, booked where it closes, an open one counted up to the snapshot).
The log line gives the count, the loop's side beside it (``loop_stall_s``
over the same window) and, where the engine's flight recorder is in reach
and still holds them, each ``PHASE_LONG`` and ``LOOP_STALL`` of the window:
the phase, the program it waited for, its milliseconds and where in the
window it ended."""

import json


def _journalled(ctx):
    """The window's ``PHASE_LONG`` / ``LOOP_STALL`` events from the engine's
    journal (its clock is the harness's ``perf_counter``), or None."""
    journal = getattr(getattr(ctx, "engine", None), "_journal", None)
    if journal is None:
        return None
    from calfkit_tpu.observability import flightrec

    # (a program that keeps the counter has the events)
    names = {flightrec.EV_PHASE_LONG: "PHASE_LONG", flightrec.EV_LOOP_STALL: "LOOP_STALL"}
    return [
        {"event": names[code], "at_s": round(t - ctx.t0, 3), "ms": a,
         **({"phase": note, "seq": b} if note else {})}
        for _, t, code, _, _, a, b, note in journal.snapshot()
        if code in names and ctx.t0 <= t < ctx.t_end
    ]


def read(ctx):
    window = ctx.counters.get("window")
    if not window or "phase_long_s" not in window:
        return None  # a program that keeps no such counter
    value = 100.0 * window["phase_long_s"] / ctx.seconds
    print(json.dumps({"phase": "reader", "metric": "phase_long_pct", "window_s": ctx.seconds,
                      "phase_longs": window.get("phase_longs"),
                      "phase_long_s": window["phase_long_s"],
                      "loop_stalls": window.get("loop_stalls"),
                      "loop_stall_s": window.get("loop_stall_s"),
                      "journal": _journalled(ctx)}), flush=True)
    return value
