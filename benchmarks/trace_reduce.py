"""From a profiler trace (.xplane.pb) to device numbers.

Two stages, so that the arithmetic is testable without a chip:

1. ``load_events(path)`` reads the trace with nothing but JAX
   (``jax.profiler.ProfileData``) into plain tuples
   ``(plane, line, name, start_ns, duration_ns)``.
2. ``reduce(events, window_s)`` is pure Python over those tuples: the
   union of the intervals in which an operation ran on each device, the
   idle gaps between them, time by XLA module and by operation, time in
   collectives.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` carries one event per executed operation and ``XLA Modules``
one per executed program (named ``jit_<function>(<fingerprint>)``).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

Event = tuple  # (plane, line, name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_ANNOTATION = "bench.traced_window"  # covers everything: says nothing
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I
)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str, host_prefixes: tuple[str, ...] = ("bench.",)) -> list[Event]:
    """Device events of every TPU plane, plus those host events whose name
    starts with one of ``host_prefixes`` (the harness's own annotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: list[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(host_prefixes):
                    out.append((plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)))
    return out


def describe(path: str, top: int = 12) -> dict:
    """What a trace holds, for looking at one by hand: planes, their lines,
    event counts and the commonest names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names: dict[str, int] = defaultdict(int)
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                n += 1
            lines.append({"line": line.name, "events": n,
                          "top": sorted(names.items(), key=lambda kv: -kv[1])[:top]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"planes": planes}


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list[tuple[int, int]]]:
    """Length of the union of [start, end) intervals (ns) in seconds, and
    the gaps between its parts."""
    busy, gaps = 0, []
    end = None
    for s, e in sorted(intervals):
        if end is None:
            busy, end = e - s, e
        elif s > end:
            gaps.append((end, s))
            busy, end = busy + (e - s), e
        elif e > end:
            busy, end = busy + (e - end), e
    return busy / 1e9, gaps


def module_family(name: str) -> str:
    """``jit_ragged_paged(1234)`` -> ``jit_ragged_paged``."""
    return name.split("(", 1)[0].strip()


def reduce(events: list[Event], window_s: float) -> dict:
    """Device numbers of one traced window of ``window_s`` seconds."""
    per_device: dict[str, dict] = {}
    for plane in sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])}):
        ops = [e for e in events if e[0] == plane and e[1] == OPS_LINE]
        mods = [e for e in events if e[0] == plane and e[1] == MODULES_LINE]
        busy_s, gaps = union_seconds([(e[3], e[3] + e[4]) for e in ops])
        by_op: dict[str, float] = defaultdict(float)
        for e in ops:
            by_op[e[2]] += e[4] / 1e9
        by_module: dict[str, float] = defaultdict(float)
        runs: dict[str, int] = defaultdict(int)
        for e in mods:
            by_module[module_family(e[2])] += e[4] / 1e9
            runs[module_family(e[2])] += 1
        per_device[plane] = {
            "busy_s": busy_s, "gaps": gaps, "by_op": dict(by_op),
            "by_module": dict(by_module), "module_runs": dict(runs),
            "collective_s": sum(s for n, s in by_op.items() if COLLECTIVE.search(n)),
        }
    n = len(per_device)
    if not n:
        return {"devices": 0, "busy_s": 0.0, "window_s": window_s}

    def mean_of(key: str) -> dict[str, float]:
        acc: dict[str, float] = defaultdict(float)
        for d in per_device.values():
            for name, value in d[key].items():
                acc[name] += value / n
        return dict(acc)

    first = per_device[sorted(per_device)[0]]
    host = [e for e in events if not DEVICE_PLANE.match(e[0])]
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per_device.values()) / n,
        "by_module": mean_of("by_module"),
        "module_runs": mean_of("module_runs"),
        "by_op": mean_of("by_op"),
        "idle_gaps": attribute_gaps(first["gaps"], host),
    }


def attribute_gaps(gaps: list[tuple[int, int]], host: list[Event], top: int = 10) -> list:
    """The longest idle gaps of the first device, summed by what the host was
    doing: the harness's own annotation that covers most of the gap, or
    ``unattributed`` (the program's spans are not on this clock yet)."""
    acc: dict[str, float] = defaultdict(float)
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, length = "unattributed", None
        for h in host:  # the most specific annotation that covers half the gap
            overlap = min(e, h[3] + h[4]) - max(s, h[3])
            if (overlap * 2 >= (e - s) and h[2] != WINDOW_ANNOTATION
                    and (length is None or h[4] < length)):
                best, length = h[2], h[4]
        acc[best] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(reduced: dict, top: int = 10) -> list:
    """The operations that took most device time, under the names the trace
    gives them (an HLO instruction's left-hand side; the rest of the text,
    its operands, is cut)."""
    acc: dict[str, float] = defaultdict(float)
    for name, seconds in reduced.get("by_op", {}).items():
        acc[name.split(" = ")[0][:80]] += seconds
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def module_seconds(reduced: dict, patterns: list[str]) -> float:
    """Device seconds in modules whose family name matches any pattern."""
    rx = [re.compile(p) for p in patterns]
    return sum(s for name, s in reduced.get("by_module", {}).items()
               if any(r.search(name) for r in rx))
