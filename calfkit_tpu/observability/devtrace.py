"""An operator's look at the device: a few seconds of ``jax.profiler``,
reduced to where the device's time went and what the host was doing
whenever the device stood idle.

    from calfkit_tpu.observability import devtrace
    devtrace.capture(8.0)          # or GET /profile?seconds=8 on MetricsServer

``capture`` starts the profiler into a temporary directory, sleeps, stops
it, reduces the ``.xplane.pb`` and deletes it.  The reduction rests on
two things the program puts on the record (ISSUE 24):

- ``jax.named_scope`` names in every jit body (``SCOPES`` below).  The
  profiler keeps an operation's scope path in the ``tf_op`` stat of its
  event METADATA, which ``jax.profiler.ProfileData`` does not hand out
  (it gives an event's own stats only), so the file is read here as
  protobuf wire format, with the standard library alone.
- ``engine.<phase>`` host annotations from the engine's phase clock
  (``EngineStats.enter``): exclusive, so every idle nanosecond of the
  device falls under at most one of them.
- the ``seq`` those of ``enqueue`` and ``sync`` carry as metadata (ISSUE
  36): the number the engine gave the program it was about to enqueue, or
  was waiting for.  It joins the host's clock to the device's module runs,
  which tells an idle gap in front of a program the host had ALREADY
  enqueued (``queued``: launch latency, input transfer) from one in front
  of a program it had not (``drained``: the host was late), and gives
  ``dispatches``: one row a program, by its number.

Beside the reduction, ``capture`` gives ``chunk_attention``: what the
engines' four ``chunk_attn_*`` counters grew by over the window (ISSUE 39):
the (query, key) pairs the window's prefill chunks HAD to attend, by layer
kind, and the key-block steps walked.  ``4 x heads x head_dim x pairs``
over a chip's peak, over the seconds under ``chunk_loop/.../attention``, is
the chunk attention's share of its roofline.

A program loaded from a persistent compile cache that another build
filled carries THAT build's scope names (JAX leaves operation metadata
out of the cache key): after an upgrade from a build without names,
capture from an empty cache directory.

Two stages, so that the arithmetic is testable without a chip:
``read_trace(path)`` reads the file into plain tuples, ``reduce_trace(...)`` is pure
Python over them.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import sys
import tempfile
import threading
import time
from collections import defaultdict
from typing import Any, Iterator

__all__ = ["SCOPES", "CaptureBusy", "annotate", "capture", "read_trace", "reduce_trace"]

# every jax.named_scope the program opens (tests/test_devtrace.py holds
# the sources to this list); anything else in an operation's path is
# JAX's own (jit(...), while, body, the primitive)
SCOPES = frozenset({
    # engine.py jit bodies
    "decode_loop", "chunk_loop", "verify", "finalize", "seed_scratch", "prefill",
    # model.py, quant.py, sampler.py
    "gather_window", "qkv", "attention", "attn_out", "mlp", "lm_head",
    "kv_write", "dequant", "sample",
    # mamba.py (inside "mamba", which model.py opens around the mixer) and
    # the landing of a wave's recurrent state in its slots
    "mamba", "in_proj", "conv", "ssm", "gate_norm", "out_proj", "state_land",
    # model.py's latent-attention mixer ("mla" around it; "attention",
    # "attn_out" and "gather_window" keep their names inside) and moe.py's
    # expert layer ("moe" inside "mlp")
    "mla", "q_proj", "kv_latent", "absorb",
    "moe", "router", "group", "experts", "combine", "shared",
    # a window stack's attention cores by layer kind, inside "attention"
    # (model.py's _kind_scope): the sliding-window layers' and the global ones'
    "window", "global",
    # a window stack's cos/sin tables, one a kind that rotates, built once a
    # step outside the scan: "rope/window" and "rope/global" (model.py)
    "rope",
    # gdn.py (inside "gdn", which model.py opens around a Gated DeltaNet
    # mixer: "in_proj", "conv", "gate_norm", "out_proj" as Mamba-2's, and
    # "state" for the delta rule's pass over S) and the gated attention's
    # own steps beside "qkv" and "attention"
    "gdn", "state", "qk_norm", "out_gate",
    # Kimi Delta Attention's own step inside "gdn" (gdn.py: the decay's
    # product and the bounded gate) and the group-limited step of a gate's
    # choice inside "moe/router" (moe.py)
    "decay", "groups",
    # a gated short convolution's mixer (shortconv.py), in decode steps and
    # chunks: "in_proj", "conv" and "out_proj" inside it are Mamba-2's names
    "shortconv",
    # an EVA layer's mixer (eva.py), in decode steps and chunks: "qkv",
    # "attention" and "attn_out" inside it are the dense decoder's names;
    # a decode step's two reads are "attention/window" (the ring under the
    # aligned bound and the fresh tokens) and "attention/summary" (the summary
    # pages, and past an edge the dispatch crossed the chunks it completed
    # itself, pooled), their union under one softmax "merge", the chunk pooling
    # "pool" (under "eva" in a chunk, under "kv_write" where a dispatch lands)
    "eva", "summary", "merge", "pool",
})
UNSCOPED = "(unscoped)"
UNATTRIBUTED = "unattributed"
HOST_PREFIX = "engine."
# what the EVENT LOOP was doing, beside the tick's exclusive phases (ISSUE
# 52): synchronous stretches of the stream's road from a dispatch's landing
# to the broker, annotated where the work happens.  None takes a phase's
# name, none spans an await (the profiler nests annotations per thread, and
# tasks interleave): ``engine.deliver`` (``_deliver_batch``, one a fan-out:
# a consumer's take is booked, not annotated),
# ``engine.emit`` (detokenize and the text delta),
# ``node.publish`` (the step's wire message), ``mesh.produce`` (a Produce
# request's encoding).  ``gap_loop_s`` reads them.
LOOP_SIDE = ("engine.deliver", "engine.emit", "node.publish", "mesh.produce")
# a phase's annotation may come in PIECES (``EngineStats.restamp``: the
# profiler records an annotation when it ends, so a long phase is ended and
# begun again under the same name and ``seq`` at the heartbeat's beats).  The
# phases are exclusive and ``enter`` never opens the open one again, so two
# neighbours of one name and ``seq`` are one phase; those that carry no
# ``seq`` join only across the moment a re-stamp takes
PIECE_GAP_NS = 1_000_000
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MAX_SECONDS = 60.0

# the XLA modules of the programs the engine numbers (``engine._Program``:
# the jitted functions' own names; tests/test_devtrace.py holds them to it)
PROGRAM_MODULE = re.compile(
    r"^jit_(decode|ragged_paged|ragged_dense|verify|finalize|chunk_step|seed|prefill)$")
ENQUEUE, SYNC = HOST_PREFIX + "enqueue", HOST_PREFIX + "sync"
QUEUED, DRAINED, UNJOINED = "queued", "drained", "unjoined"

Op = tuple  # (plane, name, scope path, start_ns, duration_ns)
Module = tuple  # (plane, name, start_ns, duration_ns)
Host = tuple  # (name, start_ns, duration_ns[, seq])


_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name: str) -> Any:
    """A synchronous stretch of host work on the profiler's clock, for the
    layers that do not import JAX themselves (the nodes, the mesh): a
    ``jax.profiler.TraceAnnotation`` where this process has loaded JAX (a
    no-op with no profile running, as the engine's phase annotations are),
    else nothing.  Never around an ``await``."""
    jax = sys.modules.get("jax")
    return _NO_ANNOTATION if jax is None else jax.profiler.TraceAnnotation(name)


class CaptureBusy(RuntimeError):
    """A capture of this process is already running."""


_capturing = threading.Lock()


def capture(seconds: float) -> dict:
    """Trace ``seconds`` of whatever the process is doing and reduce it.
    Blocks for that long (call it from a thread).  A second capture while
    one runs raises :class:`CaptureBusy`; where someone else's profile is
    active (a benchmark's traced run), nothing is started and the result
    says so: ``{"captured": False, "reason": ...}``."""
    import jax

    seconds = float(seconds)
    if not 0.0 < seconds <= MAX_SECONDS:
        raise ValueError(f"seconds must be in (0, {MAX_SECONDS:g}], got {seconds!r}")
    if not _capturing.acquire(blocking=False):
        raise CaptureBusy("a capture is already running")
    trace_dir = tempfile.mkdtemp(prefix="calfkit-devtrace-")
    clock = time.perf_counter
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only where annotated
        # the needed (query, key) pairs of the window's prefill chunks, to
        # set beside the device seconds under chunk_loop/.../attention (only
        # a process that built an engine has the module)
        engines = sys.modules.get("calfkit_tpu.inference.engine")
        pairs = engines.chunk_attention_of_all_engines if engines else dict
        # the profiler records an annotation that BEGAN and ENDED inside the
        # capture: the phase open at either edge is re-stamped there, or a
        # stall that reaches the edge has no name (``EngineStats.restamp``)
        restamp = engines.restamp_all_engines if engines else (lambda: None)
        t0 = clock()
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except RuntimeError as exc:  # "Only one profile may be run at a time."
            return {"captured": False, "reason": str(exc)}
        t1 = clock()
        restamp()
        before = pairs()
        try:
            time.sleep(seconds)
        finally:
            t2 = clock()
            after = pairs()
            restamp()
            jax.profiler.stop_trace()
        t3 = clock()
        paths = sorted(glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            return {"captured": False, "reason": "the profiler wrote no .xplane.pb"}
        events = read_trace(paths[-1])
        t4 = clock()
        out = reduce_trace(*events, t2 - t1)
        # what the capture itself took, beside the window: the process is
        # slowed while the profiler starts and stops and the file is read
        out["took_s"] = {"start": t1 - t0, "stop": t3 - t2, "read": t4 - t3,
                         "reduce": clock() - t4}
        out["trace_bytes"] = os.path.getsize(paths[-1])
        out["chunk_attention"] = {f: after[f] - before[f] for f in after}
        return {"captured": True, **out}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        _capturing.release()


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
    ``decode_loop/qkv``: the program's scopes, in order."""
    return "/".join(part for part in tf_op.rstrip(":").split("/") if part in SCOPES)


def read_trace(path: str) -> tuple[list[Op], list[Module], list[Host]]:
    """Device operations with their scope paths, device modules, and the
    host's annotations (the engine's ``engine.*`` and the loop-side
    stretches, ``LOOP_SIDE``), from one ``.xplane.pb``.

    XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4
    stat_metadata=5; XLine: name=2 timestamp_ns=3 events=4; XEvent:
    metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata: name=2
    stats=5; XStat: metadata_id=1 str_value=5 ref_value=7;
    XStatMetadata: name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops: list[Op] = []
    modules: list[Module] = []
    host: list[Host] = []
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
            stat_names[key] = next(
                (_text(v) for f3, v in _fields(value) if f3 == 2), "")
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        seq_stat = {k for k, v in stat_names.items() if v == "seq"}
        events: dict[int, tuple[str, str]] = {}  # metadata id -> (name, scope path)
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
            if device or ev_name.startswith(HOST_PREFIX) or ev_name in LOOP_SIDE:
                events[key] = (ev_name, scope)
        if not events:
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
                if ev[0] == 0x08 and _varint(ev, 1)[0] not in events:
                    continue  # (field 1 comes first: most host events end here)
                meta = dict(_fields(ev))
                known = events.get(meta.get(1, 0))
                if known is None:
                    continue
                start = t0_ns + meta.get(2, 0) // 1000
                duration = meta.get(3, 0) // 1000
                if not device:
                    seq = _seq_of(ev, seq_stat)
                    host.append((known[0], start, duration, *(() if seq is None else (seq,))))
                elif line_name == OPS_LINE:
                    ops.append((name, known[0], known[1], start, duration))
                else:
                    modules.append((name, known[0], start, duration))
    return ops, modules, join_pieces(host)


def join_pieces(host: list[Host]) -> list[Host]:
    """The host's annotations with the pieces of a re-stamped phase joined
    into one interval again, from the first piece's start to the last one's
    end; the loop-side stretches as they are."""
    joined: list[Host] = []
    for h in sorted((h for h in host if h[0] not in LOOP_SIDE), key=lambda h: h[1]):
        last = joined[-1] if joined else None
        if (last is not None and last[0] == h[0] and last[3:] == h[3:]
                and (len(h) > 3 or h[1] - (last[1] + last[2]) <= PIECE_GAP_NS)):
            joined[-1] = (h[0], last[1], max(last[2], h[1] + h[2] - last[1]), *h[3:])
        else:
            joined.append(h)
    return [h for h in host if h[0] in LOOP_SIDE] + joined


def _seq_of(event: Any, seq_stat: set) -> "int | None":
    """The ``seq`` an annotation carries (XEvent.stats=4; XStat:
    metadata_id=1 uint64_value=3 int64_value=4), or None."""
    for field, v in _fields(event) if seq_stat else ():
        if field == 4:
            stat = dict(_fields(v))
            if stat.get(1) in seq_stat:
                return stat.get(4, stat.get(3))
    return None


# ------------------------------------------------------------ reducing
def _own_time(ops: list[Op]) -> tuple[list[tuple[Op, int]], int, list[tuple[int, int]]]:
    """For one device's operations: each with its own nanoseconds (its
    duration less the operations nested in it, so a ``while`` and its
    body are not counted twice), the busy nanoseconds (the union of the
    outermost intervals) and the gaps between them."""
    ordered = sorted(ops, key=lambda e: (e[3], -e[4]))
    child_ns = [0] * len(ordered)
    stack: list[int] = []
    busy, gaps, busy_end = 0, [], None
    for i, e in enumerate(ordered):
        start, end = e[3], e[3] + e[4]
        while stack and ordered[stack[-1]][3] + ordered[stack[-1]][4] <= start:
            stack.pop()
        if stack:
            child_ns[stack[-1]] += e[4]
        else:  # an outermost operation: part of the busy union
            if busy_end is None or start > busy_end:
                if busy_end is not None:
                    gaps.append((busy_end, start))
                busy += end - start
                busy_end = end
            elif end > busy_end:
                busy += end - busy_end
                busy_end = end
        stack.append(i)
    own = [(e, max(0, e[4] - child_ns[i])) for i, e in enumerate(ordered)]
    return own, busy, gaps


def _gaps_by_phase(gaps: list[tuple[int, int]], host: list[Host]) -> dict[str, float]:
    """Idle nanoseconds by the ``engine.<phase>`` annotation they fall
    under.  The phases are exclusive, so a gap is split exactly; what no
    annotation covers is ``unattributed``."""
    spans = sorted((h[1], h[1] + h[2], h[0]) for h in host if h[2] > 0)
    starts = [s for s, _, _ in spans]
    acc: dict[str, float] = defaultdict(float)
    for a, b in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(spans) and spans[i][0] < b:
            overlap = min(b, spans[i][1]) - max(a, spans[i][0])
            if overlap > 0:
                acc[spans[i][2]] += overlap / 1e9
                covered += overlap
            i += 1
        if b - a > covered:
            acc[UNATTRIBUTED] += (b - a - covered) / 1e9
    return dict(acc)


def _join_programs(modules: list[Module], host: list[Host]) -> "list[tuple[int, Module]]":
    """The engine's number of each program run of one device, in order.
    The device runs the engine's programs in the order it enqueued them, so
    run ``i`` is program ``offset + i`` and only the offset is unknown.  Two
    things bound it: the run of a program starts after the ``enqueue``
    annotation that carries its number began, and has ended when the
    ``sync`` annotation that carries its number ends.  The offset that
    breaks fewest of them wins (none, on a whole capture); [] where no
    annotation carries a number (a program without the account)."""
    runs = sorted((m for m in modules if PROGRAM_MODULE.match(m[1].split("(", 1)[0].strip())),
                  key=lambda m: m[2])
    marks = [h for h in host if len(h) > 3 and h[0] in (ENQUEUE, SYNC)]
    if not runs or not marks:
        return []
    ends = [m[2] + m[3] for m in runs]
    candidates = set()
    for name, start, duration, seq in marks:
        if name == SYNC:  # the last run complete when the sync returned
            i = bisect.bisect_right(ends, start + duration) - 1
            candidates.update(seq - j for j in (i - 1, i, i + 1))

    def broken(offset: int) -> int:
        n = 0
        for name, start, duration, seq in marks:
            i = seq - offset
            if 0 <= i < len(runs):
                n += runs[i][2] < start if name == ENQUEUE else ends[i] > start + duration
        return n

    if not candidates:
        return []
    offset = min(sorted(candidates), key=broken)
    return [(offset + i, m) for i, m in enumerate(runs)]


def _dispatch_rows(
    joined: "list[tuple[int, Module]]", gaps: list[tuple[int, int]], host: list[Host],
) -> "tuple[list[dict], dict[str, list], dict[str, float]]":
    """One row a program run (its number, module, device seconds, the idle
    gap in front of it and that gap's class), the idle gaps by class,
    and the ``drained`` ones by ``engine.<phase>``.  A gap is ``queued``
    where the ``enqueue`` phase that put the next program on the queue had
    ENDED when the gap began, ``drained`` where it had not (the host had
    yet to enqueue it, or was in the call), ``unjoined`` where no numbered
    program follows it in the capture."""
    phases = sorted((h[3], h[1] + h[2]) for h in host if len(h) > 3 and h[0] == ENQUEUE)
    firsts = [seq for seq, _ in phases]
    starts = [m[2] for _, m in joined]
    by_class: dict[str, list] = defaultdict(list)
    before: dict[int, list] = {}  # seq -> [idle seconds in front of it, class of their first]
    for a, b in gaps:
        i = bisect.bisect_left(starts, a)  # the next numbered program to start
        kind = UNJOINED
        if i < len(joined):
            seq = joined[i][0]
            j = bisect.bisect_right(firsts, seq) - 1  # the phase that enqueued it
            if j >= 0:
                kind = QUEUED if phases[j][1] <= a else DRAINED
            # an eager operation of the host's (a fresh scratch's zeros) may
            # split the idle in front of a program: the pieces add up
            before.setdefault(seq, [0.0, kind])[0] += (b - a) / 1e9
        by_class[kind].append((a, b))
    rows = []
    for seq, m in joined:
        idle_s, kind = before.get(seq, (0.0, None))
        rows.append({"seq": seq, "module": m[1].split("(", 1)[0].strip(),
                     "device_s": m[3] / 1e9, "gap_before_s": idle_s, "gap": kind})
    return rows, dict(by_class), _gaps_by_phase(by_class.get(DRAINED, []), host)


def _sorted(acc: dict[str, float]) -> dict[str, float]:
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def reduce_trace(ops: list[Op], modules: list[Module], host: list[Host], window_s: float) -> dict:
    """Device numbers of one captured window of ``window_s`` seconds, as
    the mean over the devices seen; the idle gaps are the first device's."""
    planes = sorted({e[0] for e in ops})
    out: dict[str, Any] = {"devices": len(planes), "window_s": window_s}
    if not planes:
        return out
    busy_s = 0.0
    depth1: dict[str, float] = defaultdict(float)
    depth2: dict[str, float] = defaultdict(float)
    first_gaps: list[tuple[int, int]] = []
    for plane in planes:
        own, busy, gaps = _own_time([e for e in ops if e[0] == plane])
        busy_s += busy / 1e9 / len(planes)
        if plane == planes[0]:
            first_gaps = gaps
        for e, ns in own:
            parts = e[2].split("/") if e[2] else [UNSCOPED]
            depth1[parts[0]] += ns / 1e9 / len(planes)
            depth2["/".join(parts[:2])] += ns / 1e9 / len(planes)
    by_module: dict[str, float] = defaultdict(float)
    for m in modules:
        by_module[m[1].split("(", 1)[0].strip()] += m[3] / 1e9 / len(planes)
    # the tick's phases are exclusive and split a gap exactly; the loop's
    # stretches run on another thread beside them and are read apart
    host = join_pieces(host)  # (a host list that did not come through ``read_trace``)
    loop = [h for h in host if h[0] in LOOP_SIDE]
    host = [h for h in host if h[0] not in LOOP_SIDE]
    gap_s = _gaps_by_phase(first_gaps, host)
    dispatches, gaps_by_class, gap_drained_s = _dispatch_rows(
        _join_programs([m for m in modules if m[0] == planes[0]], host), first_gaps, host)
    gap_class_s = {k: sum(b - a for a, b in v) / 1e9 for k, v in gaps_by_class.items()}
    gap_loop_s = {}
    for kind, gaps in gaps_by_class.items():
        covered = _gaps_by_phase(gaps, loop)
        covered.pop(UNATTRIBUTED, None)
        gap_loop_s[kind] = _sorted(covered)
    out.update(
        busy_s=busy_s,
        idle_pct=100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None,
        scope_s=_sorted(depth1),
        scope2_s=_sorted(depth2),
        unscoped_pct=100.0 * depth1.get(UNSCOPED, 0.0) / busy_s if busy_s else None,
        module_s=_sorted(by_module),
        gap_s=_sorted(gap_s),
        gap_unattributed_pct=(
            100.0 * gap_s.get(UNATTRIBUTED, 0.0) / sum(gap_s.values()) if gap_s else None),
        # the same idle seconds by what stood on the device's queue, the
        # drained ones by phase, and one row a numbered program run
        gap_class_s=_sorted(gap_class_s),
        gap_drained_s=_sorted(gap_drained_s),
        # each class's idle seconds that a loop-side stretch overlapped, by
        # its name (``LOOP_SIDE``): what the event loop ran while the device
        # stood idle, where the phases say only where the tick stood
        gap_loop_s=gap_loop_s,
        dispatches=dispatches,
    )
    return out
