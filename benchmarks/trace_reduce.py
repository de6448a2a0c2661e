"""From a profiler trace (.xplane.pb) to device numbers.

Two stages, so that the arithmetic is testable without a chip:

1. ``load_events(path)`` reads the trace into plain tuples
   ``(plane, line, name, start_ns, duration_ns, scope)``.  The file is
   read as protobuf wire format with the standard library alone:
   ``jax.profiler.ProfileData`` hands out an event's own stats only, and
   an operation's scope path (the ``jax.named_scope`` names the program
   opened around it) is the ``tf_op`` stat of its event METADATA.  It is
   the benchmark's ONLY reader of the format, and a copy of the method of
   ``calfkit_tpu/observability/devtrace.read_trace`` on purpose: the
   yardstick may not move when a program PR edits the program's reader.
2. ``reduce(events, window_s)`` is pure Python over those tuples: the
   union of the intervals in which an operation ran on each device, the
   idle gaps between them by what the host was doing, time by XLA module,
   by operation and by scope, time in collectives.  Tuples without a
   scope (five long: traces recorded before PR 26) reduce as before.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` carries one event per executed operation and ``XLA Modules``
one per executed program (named ``jit_<function>(<fingerprint>)``).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Iterator

Event = tuple  # (plane, line, name, start_ns, duration_ns[, scope])

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_ANNOTATION = "bench.traced_window"  # covers everything: says nothing
# host annotations kept: the harness's own and the engine's exclusive phase
# clock (engine.sync, engine.handoff, engine.prep, ...)
HOST_PREFIXES = ("bench.", "engine.")
UNATTRIBUTED = "unattributed"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I
)
# parts of an operation's path that are JAX's own, not a scope the program
# named: transforms, control flow, and an einsum's own "bsd,df->bsf"
JAX_PART = re.compile(
    r"^(?:\w+\(.*\)|while|body|cond|branch_\d+_fun|closed_call|core_call|checkpoint|"
    r"remat\d*|scan|pallas_call|custom_[jv][vj]p_call\w*|.*->.*)$")
UNSCOPED = "(unscoped)"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


# ------------------------------------------------------------- reading
def _varint(buf: Any, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: Any) -> Iterator[tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for anything with a length or a fixed width."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _text(view: Any) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf: Any) -> tuple[int, Any]:
    key, value = 0, b""
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def scope_path(tf_op: str) -> str:
    """``jit(ragged_paged)/decode_loop/while/body/qkv/dot_general:`` ->
    ``decode_loop/qkv``: the names the program gave, in order.  The last
    part is the primitive, and ``jit(..)``, ``while``, ``body`` and their
    like are JAX's own."""
    parts = tf_op.rstrip(":").split("/")[:-1]
    return "/".join(p for p in parts if p and not JAX_PART.match(p))


def load_events(path: str, host_prefixes: tuple[str, ...] = HOST_PREFIXES) -> list[Event]:
    """Device events of every TPU plane, each operation with its scope
    path, plus those host events whose name starts with one of
    ``host_prefixes``.

    XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4
    stat_metadata=5; XLine: name=2 timestamp_ns=3 events=4; XEvent:
    metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata: name=2
    stats=5; XStat: metadata_id=1 str_value=5 ref_value=7;
    XStatMetadata: name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: list[Event] = []
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, event_md, stat_md = "", [], [], []
        for f2, v in _fields(plane):
            if f2 == 2:
                name = _text(v)
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                event_md.append(v)
            elif f2 == 5:
                stat_md.append(v)
        device = bool(DEVICE_PLANE.match(name))
        stat_names: dict[int, str] = {}
        for entry in stat_md:
            key, value = _map_entry(entry)
            stat_names[key] = next((_text(v) for f3, v in _fields(value) if f3 == 2), "")
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        known: dict[int, tuple[str, str]] = {}  # metadata id -> (name, scope path)
        for entry in event_md:
            key, value = _map_entry(entry)
            ev_name, scope = "", ""
            for f3, v in _fields(value):
                if f3 == 2:
                    ev_name = _text(v)
                elif f3 == 5 and device:
                    stat = dict(_fields(v))
                    if stat.get(1) in tf_op:
                        scope = scope_path(
                            _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), ""))
            if device or ev_name.startswith(host_prefixes):
                known[key] = (ev_name, scope)
        if not known:
            continue  # a plane with nothing of ours
        for line in lines:
            line_name, t0_ns, raw = "", 0, []
            for f3, v in _fields(line):
                if f3 == 2:
                    line_name = _text(v)
                elif f3 == 3:
                    t0_ns = v
                elif f3 == 4:
                    raw.append(v)
            if device and line_name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in raw:
                if ev[0] == 0x08 and _varint(ev, 1)[0] not in known:
                    continue  # (field 1 comes first: most host events end here)
                meta = dict(_fields(ev))
                hit = known.get(meta.get(1, 0))
                if hit is not None:
                    out.append((name, line_name, hit[0], t0_ns + meta.get(2, 0) // 1000,
                                meta.get(3, 0) // 1000, hit[1]))
    return out


# ------------------------------------------------------------ reducing
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


def scope_of(event: Event) -> str:
    """An operation's scope path; tuples five long (recorded before PR 26) have none."""
    return (event[5] if len(event) > 5 else "") or UNSCOPED


def op_label(event: Event) -> str:
    """``decode_loop/mlp fusion``: an operation's scope path (or
    ``(unscoped)``: the copies XLA inserts carry none), then its own name:
    an HLO instruction's left-hand side, less the ``%`` and the number that
    differs from one program variant to the next."""
    own = re.sub(r"[.]\d+$", "", event[2].split(" = ")[0][:80].lstrip("%"))
    return f"{scope_of(event)} {own}"


def own_seconds(ops: list[Event]) -> list[tuple[Event, float]]:
    """Each operation with its OWN seconds: its duration less the
    operations nested in it, so a ``while`` and its body are not counted
    twice."""
    ordered = sorted(ops, key=lambda e: (e[3], -e[4]))
    child_ns = [0] * len(ordered)
    stack: list[int] = []
    for i, e in enumerate(ordered):
        while stack and ordered[stack[-1]][3] + ordered[stack[-1]][4] <= e[3]:
            stack.pop()
        if stack:
            child_ns[stack[-1]] += e[4]
        stack.append(i)
    return [(e, max(0, e[4] - child_ns[i]) / 1e9) for i, e in enumerate(ordered)]


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
        own_by_op: dict[str, float] = defaultdict(float)
        by_scope: dict[str, float] = defaultdict(float)
        for e, seconds in own_seconds(ops):
            own_by_op[op_label(e)] += seconds
            by_scope[scope_of(e)] += seconds
        by_module: dict[str, float] = defaultdict(float)
        runs: dict[str, int] = defaultdict(int)
        for e in mods:
            by_module[module_family(e[2])] += e[4] / 1e9
            runs[module_family(e[2])] += 1
        per_device[plane] = {
            "busy_s": busy_s, "gaps": gaps, "by_op": dict(by_op),
            "own_by_op": dict(own_by_op), "by_scope": dict(by_scope),
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
        "own_by_op": mean_of("own_by_op"),
        "by_scope": mean_of("by_scope"),
        "idle_gaps": attribute_gaps(first["gaps"], host),
    }


def attribute_gaps(gaps: list[tuple[int, int]], host: list[Event], top: int = 10) -> list:
    """All idle time of the first device, by what the host was doing.  A
    gap is split at the edges of the host annotations that overlap it;
    each piece goes to the most specific (shortest) annotation that covers
    it, so the engine's exclusive phases (``engine.sync``,
    ``engine.handoff``, ...) split a gap exactly, and what none covers is
    ``unattributed``."""
    spans = sorted((h[3], h[3] + h[4], h[2]) for h in host
                   if h[2] != WINDOW_ANNOTATION and h[4] > 0)
    acc: dict[str, float] = defaultdict(float)
    live: list[tuple[int, int, str]] = []  # spans begun before the gap's end, not yet over
    nxt = 0
    for a, b in sorted(gaps):
        while nxt < len(spans) and spans[nxt][0] < b:
            live.append(spans[nxt])
            nxt += 1
        live = [s for s in live if s[1] > a]
        edges = sorted({a, b, *(t for s in live for t in s[:2] if a < t < b)})
        for lo, hi in zip(edges, edges[1:]):
            over = [s for s in live if s[0] <= lo and s[1] >= hi]
            name = min(over, key=lambda s: s[1] - s[0])[2] if over else UNATTRIBUTED
            acc[name] += (hi - lo) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def top_ops(reduced: dict, top: int = 10) -> list:
    """The operations that took most device time of their own, each as
    ``op_label`` names it: ``decode_loop/mlp fusion``."""
    ops = reduced.get("own_by_op") or {}
    return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]


def module_seconds(reduced: dict, patterns: list[str]) -> float:
    """Device seconds in modules whose family name matches any pattern."""
    rx = [re.compile(p) for p in patterns]
    return sum(s for name, s in reduced.get("by_module", {}).items()
               if any(r.search(name) for r in rx))
