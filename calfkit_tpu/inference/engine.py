"""The continuous-batching inference engine.

This is the boundary object between the two communication tiers (SURVEY.md
§2.4): Kafka partitions feed requests in; token streams come out.  Design:

- a fixed pool of ``max_batch_size`` slots backed by ONE device-resident KV
  cache [L, B, S, K, hd]; admission = prefill into a free slot's rows;
- decode runs for ALL active slots together: one jitted dispatch generates
  ``decode_steps_per_dispatch`` tokens per slot via ``lax.scan`` (host syncs
  once per dispatch, not per token);
- prefill is per-request, bucketed to ``prefill_chunk`` multiples so each
  bucket compiles once; a prefill never blocks the decode cadence for more
  than one tick (new work is admitted between decode dispatches —
  continuous batching, not static batching);
- caches are donated through jit, so memory stays at one cache copy;
- everything device-side is static-shape; per-request stop conditions (eos,
  max_new_tokens) are applied host-side on the freshly synced token block.

The engine is model-agnostic over :mod:`calfkit_tpu.inference.model`'s
functional forward and owns the jit specializations.
"""

from __future__ import annotations

import asyncio
import heapq
import itertools
import logging
import math
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from calfkit_tpu import cancellation, leases, qos
from calfkit_tpu.effects import hotpath
from calfkit_tpu.inference import ragged as ragged_math
from calfkit_tpu.exceptions import (
    DeadlineExceededError,
    EngineOverloadedError,
    EngineWedgedError,
    InferenceError,
    RunOrphanedError,
)
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.compile_cache import enable_compile_cache
from calfkit_tpu.inference.config import (
    ModelConfig,
    RuntimeConfig,
    UnsupportedWithLatentAttention,
    UnsupportedWithRecurrentLayers,
    UnsupportedWithEvaLayers,
    UnsupportedWithWindowLayers,
)
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.moe import _STEP_MAX_TOKENS, dense_form, moe_stats_init
from calfkit_tpu.observability import capacity, flightrec
from calfkit_tpu.observability.trace import TRACER, Span, TraceContext, detach_spans
from calfkit_tpu.observability.metrics import (
    INTER_TOKEN_BUCKETS_MS,
    REGISTRY,
    MetricsRegistry,
)
from calfkit_tpu.inference.sampler import (
    SamplingParams,
    retire_mask_slots,
    sample_slots,
    spec_accept_slots,
)
from calfkit_tpu.inference.sharding import (
    cache_sharding,
    make_mesh,
    param_shardings,
    place_params,
)

logger = logging.getLogger(__name__)

_DONE = object()

# the dispatch loop's phases and the admission ledger's reasons, by the
# EngineStats field that accumulates each (ISSUE 24); the text is the
# /metrics help line
_SECONDS_HELP = {
    "phase_reap_s": "dispatch loop: the per-pass sweeps (cancels, deadlines, orphans, stalls)",
    "phase_admit_s": "dispatch loop: wave formation, page reservation, activation",
    "phase_handoff_s": "dispatch loop: the hop to the tick thread and back",
    "phase_prep_s": "dispatch loop: host-side dispatch inputs",
    "phase_enqueue_s": "dispatch loop: the jit call, to its return",
    "phase_sync_s": "dispatch loop: blocked on the device",
    "phase_fanout_s": "dispatch loop: after the sync (fan-out, retirement, frees)",
    "phase_idle_s": "dispatch loop: awaiting work",
    "starved_s": "rows active or a wave in flight and the device known empty: "
                 "the sync that drained it to the next enqueue",
    "blocked_slots_s": "queue head held: no free slot",
    "blocked_pages_s": "queue head held: page allocation came back short",
    "blocked_wave_s": "queue head held: an admission wave in flight",
    "blocked_budget_s": "queue head held: ragged token budget, wave trim or bucket",
    "empty_slot_queued_s": "slot-seconds: free slots while a request was queued",
    "program_build_s": "calls in which JAX built a program (a jit key's first use, or "
                       "arguments of another kind under it): their seconds",
    "stream_deliver_wait_s": "token stream: from a dispatch's landing to the request's "
                             "consumer taking its block (the loop's turn), summed over blocks",
    "stream_emit_s": "token stream: detokenize, stop search and the delta's making, "
                     "summed over events",
    "stream_backpressure_s": "token stream: the stream suspended in its consumer (the "
                             "node's step, the publish, its acknowledgement), summed over events",
    "loop_stall_s": "event loop: lateness of the engine's 20 ms heartbeat, where it "
                    "passed 20 ms (something held the loop)",
    "phase_long_s": "dispatch loop: the whole seconds of phases that outlasted what the "
                    "two-deep device queue hides (a host phase one dispatch's wall time, "
                    "a sync four)",
}
_SECONDS_FIELDS = tuple(_SECONDS_HELP)
PHASES = tuple(f for f in _SECONDS_FIELDS if f.startswith("phase_") and f != "phase_long_s")
REAP, ADMIT, HANDOFF, PREP, ENQUEUE, SYNC, FANOUT, IDLE = PHASES
# "phase_reap_s" -> "engine.reap": the host annotation on the profiler's clock
_PHASE_ANNOTATION = {p: "engine." + p[len("phase_"):-len("_s")] for p in PHASES}
# the event loop's work between a dispatch's landing and the stream's
# consumer, on the same clock: synchronous stretches, none a phase's name
DELIVER, EMIT = "engine.deliver", "engine.emit"
# the engine's heartbeat on its loop: a beat that comes later than its own
# period is a stall of the loop (``loop_stall_s``, EV_LOOP_STALL)
HEARTBEAT_S = 0.020
# a phase is LONG where it outlasts what the two-deep device queue hides: a
# host phase ONE dispatch's wall time (``dispatch_ewma_ms``: while the host
# works, the device has the program it runs and one behind it), a ``sync``
# FOUR (it may wait for the program in front of its own too, and the EWMA is
# of the MEAN dispatch where a dispatch of the longest kind, eight steps and a
# chunk, is about twice that: at two, Mistral's cell read ten ordinary syncs
# of 227-296 ms a window long, 5.2% of it, and Qwen3-Next's three, on a device
# that never idled: PERF.md section 6, PR 52); ``idle`` never.  The floor
# stands in for an unprimed EWMA and keeps a toy engine's millisecond
# dispatches from reading every hiccup as a stall
LONG_FLOOR_S = 0.100
SYNC_DISPATCHES = 4.0
BLOCKED = tuple(f for f in _SECONDS_FIELDS if f.startswith("blocked_"))
NO_SLOT, NO_PAGES, WAVE_IN_FLIGHT, OVER_BUDGET = BLOCKED
# a window stack's prefill chunks, by what their attention HAS to compute and
# what walks it (EngineStats has what each counts)
CHUNK_ATTN_FIELDS = (
    "chunk_attn_pairs_window", "chunk_attn_pairs_global",
    "chunk_attn_key_blocks_visited", "chunk_attn_key_blocks_dense",
)
# an EVA stack's two caches at work (EngineStats has what each counts)
EVA_FIELDS = (
    "decode_eva_window_tokens_read", "decode_eva_summaries_read", "eva_chunks_pooled",
    "eva_windows_closed", "chunk_attn_pairs_eva_window", "chunk_attn_pairs_eva_summary",
)
# the EngineStats fields folded into /metrics counters once a dispatch
# counters that go to /metrics and ``counters()`` only, never on the
# heartbeat advert's window
_LOCAL_FIELDS = (
    "decode_pages_live", "decode_pages_window", "decode_rows_live",
    "prefix_reuse_declined_recurrent", "prefix_reuse_declined_window",
    "decode_window_tokens_read", "decode_global_tokens_read", "window_pages_given_back",
    *EVA_FIELDS, *CHUNK_ATTN_FIELDS, "chunk_tokens", "chunk_tokens_padding",
    "pipeline_drains", "pipeline_drains_wave", "wave_landings_deferred",
    "programs_built", "moe_assignments", "moe_assignments_absent",
    "moe_rows_in_held_groups", "moe_expert_tokens_max",
    "moe_expert_tokens_mean", "moe_experts_hit", "moe_step_kernel_steps", "moe_grouped_chunks",
    "moe_dense_chunks", "stream_blocks", "stream_events", "loop_stalls",
    "phase_longs", *_SECONDS_FIELDS,
)
_SYNCED_FIELDS = (
    "decode_tokens", "prefill_tokens", "spec_proposed", "spec_accepted",
    "overlap_wasted_tokens", *_LOCAL_FIELDS,
)

# process-wide active-request aggregation: the shared gauge must report
# the SUM across live engines, not the last dispatching engine's count
# (updated per dispatch; entries removed at engine stop / GC).  The lock
# serializes insert/pop/sum across decode threads and the event loop —
# an unguarded sum() during another engine's first insert would raise
# "dictionary changed size during iteration" INTO the decode tick,
# letting telemetry fault serving.
_ACTIVE_BY_ENGINE: dict[int, int] = {}
_ACTIVE_LOCK = threading.Lock()


# every live engine, for ``GET /programs`` (weak: an abandoned engine goes)
_ENGINES: "weakref.WeakSet[InferenceEngine]" = weakref.WeakSet()


def chunk_attention_of_all_engines() -> dict:
    """The chunk attention counters (``CHUNK_ATTN_FIELDS``) summed over the
    live engines of the process: what ``devtrace.capture`` takes before and
    after its window, so that ``GET /profile`` holds the needed pairs
    beside the device seconds under ``chunk_loop/.../attention``."""
    return {
        f: sum(getattr(e.stats, f) for e in list(_ENGINES)) for f in CHUNK_ATTN_FIELDS
    }


def restamp_all_engines() -> None:
    """``EngineStats.restamp`` on every live engine of the process: what
    ``devtrace.capture`` calls at its window's two edges."""
    for e in list(_ENGINES):
        e.stats.restamp()


def programs_of_all_engines() -> "list[dict]":
    """``InferenceEngine.programs()`` of every live engine of the process."""
    return [
        {"engine": e.config.name, "enqueued": e._enq_seq, "proved": e._done_seq,
         "programs": e.programs()}
        for e in list(_ENGINES)
    ]


def _drop_engine_active(key: int) -> None:
    """Remove one engine from the aggregation AND re-set the gauge —
    shared by stop() and the GC finalizer, so an abandoned engine's last
    count never stays pinned in the exposition."""
    with _ACTIVE_LOCK:
        if _ACTIVE_BY_ENGINE.pop(key, None) is None:
            return
        total = sum(_ACTIVE_BY_ENGINE.values())
    REGISTRY.gauge("calfkit_engine_active_requests").set(total)


def _engine_metrics(
    registry: "MetricsRegistry | None" = None, *, histograms_only: bool = False
) -> dict:
    """The engine's latency instruments, get-or-create from ``registry``
    (default: the process registry — many engines per process share one
    instrument per metric for the /metrics exposition; each engine also
    builds a private ``histograms_only`` set for per-node percentile
    attribution — counters/gauges stay process-level, so a private copy
    of them would just be dead zeros).  Everything observed here is PER
    DISPATCH or PER ADMISSION, never per token: the decode hot path must
    stay allocation-free."""
    reg = registry if registry is not None else REGISTRY
    out: dict = {
        "queue_wait_ms": reg.histogram(
            "calfkit_engine_queue_wait_ms",
            "submit-to-prefill-start wait (ms)",
        ),
        "prefill_ms": reg.histogram(
            "calfkit_engine_prefill_ms",
            "prefill wave latency, admission to landing (ms)",
        ),
        "ttft_ms": reg.histogram(
            "calfkit_engine_ttft_ms",
            "time to first token: submit to first-token emission (ms)",
        ),
        "inter_token_ms": reg.histogram(
            "calfkit_engine_inter_token_ms",
            "per-sequence inter-token latency (dispatch wall / steps, ms)",
            buckets=INTER_TOKEN_BUCKETS_MS,
        ),
        "decode_dispatch_ms": reg.histogram(
            "calfkit_engine_decode_dispatch_ms",
            "one decode/verify dispatch, enqueue to host sync (ms)",
        ),
        "dispatch_gap_ms": reg.histogram(
            "calfkit_engine_dispatch_gap_ms",
            "device-idle bubble: previous dispatch landing to next launch, "
            "zero while a dispatch is already in flight (ms)",
            buckets=INTER_TOKEN_BUCKETS_MS,
        ),
    }
    if histograms_only:
        return out
    out.update(
        decode_tokens=reg.counter(
            "calfkit_engine_decode_tokens_total", "decoded tokens emitted"
        ),
        prefill_tokens=reg.counter(
            "calfkit_engine_prefill_tokens_total", "prompt tokens prefilled"
        ),
        spec_proposed=reg.counter(
            "calfkit_engine_spec_proposed_total",
            "speculative draft tokens offered to verify dispatches",
        ),
        spec_accepted=reg.counter(
            "calfkit_engine_spec_accepted_total",
            "speculative draft tokens accepted by verify dispatches",
        ),
        overlap_wasted_tokens=reg.counter(
            "calfkit_engine_overlap_wasted_tokens_total",
            "pad tokens discarded by one-dispatch-late retirement "
            "(overlapped execution)",
        ),
        decode_pages_live=reg.counter(
            "calfkit_engine_decode_pages_live_total",
            "paged decode steps: KV pages the active rows hold "
            "(what a read in place touches)",
        ),
        decode_pages_window=reg.counter(
            "calfkit_engine_decode_pages_window_total",
            "paged decode steps: rows in the program x the window bucket's "
            "pages (what the XLA window gather copies)",
        ),
        decode_rows_live=reg.counter(
            "calfkit_engine_decode_rows_live_total",
            "paged decode steps: the active rows that hold a page (the walks "
            "a read in place makes a layer; pages_live over it is a mean walk)",
        ),
        prefix_reuse_declined_window=reg.counter(
            "calfkit_engine_prefix_reuse_declined_window_total",
            "requests whose prefix was not looked up for reuse because the model "
            "has window layers (a ring entry is written over as its row grows, so "
            "no page of such a layer is ever registered)",
        ),
        decode_window_tokens_read=reg.counter(
            "calfkit_engine_decode_window_tokens_read_total",
            "decode steps: rows x min(len, sliding_window) x window layers: the "
            "keys and values a step has to read of the layers that keep a window "
            "(an ATTENTION window; decode_pages_window above means a decode "
            "context bucket)",
        ),
        decode_global_tokens_read=reg.counter(
            "calfkit_engine_decode_global_tokens_read_total",
            "decode steps: rows x len x global layers, of a model with window "
            "layers beside them",
        ),
        decode_eva_window_tokens_read=reg.counter(
            "calfkit_engine_decode_eva_window_tokens_read_total",
            "decode steps of a model with EVA layers: rows x the exact keys of the "
            "query's own aligned window (q mod window_size, + 1 for itself) x layers, "
            "summed over a dispatch's steps",
        ),
        decode_eva_summaries_read=reg.counter(
            "calfkit_engine_decode_eva_summaries_read_total",
            "the same for the pooled entries: rows x (window_size / chunk_size) x "
            "(q // window_size) x layers, summed over steps",
        ),
        eva_chunks_pooled=reg.counter(
            "calfkit_engine_eva_chunks_pooled_total",
            "chunks of the rows' own tokens pooled into a summary entry, x layers: "
            "by a prefill chunk, or by the decode dispatch whose tokens completed them",
        ),
        eva_windows_closed=reg.counter(
            "calfkit_engine_eva_windows_closed_total",
            "aligned windows the rows left behind (a prompt's complete windows at "
            "its wave's landing; a decode dispatch that crosses an edge)",
        ),
        chunk_attn_pairs_eva_window=reg.counter(
            "calfkit_engine_chunk_attn_pairs_eva_window_total",
            "prefill chunks of a model with EVA layers: the (query, exact key) pairs "
            "the chunk's own positions must attend inside their window, x layers",
        ),
        chunk_attn_pairs_eva_summary=reg.counter(
            "calfkit_engine_chunk_attn_pairs_eva_summary_total",
            "the same for (query, pooled entry) pairs: own positions x the summaries "
            "of the windows before the chunk, x layers",
        ),
        eva_summary_cache_bytes=reg.gauge(
            "calfkit_engine_eva_summary_cache_bytes",
            "device bytes reserved for the summary pages of a model with EVA layers "
            "(the last engine built; 0 without them)",
        ),
        chunk_attn_pairs_window=reg.counter(
            "calfkit_engine_chunk_attn_pairs_window_total",
            "prefill chunks of a model with window layers: the (query, key) pairs "
            "the chunk's own positions must attend, sum of min(q + 1, "
            "sliding_window) x window layers (host arithmetic at launch)",
        ),
        chunk_attn_pairs_global=reg.counter(
            "calfkit_engine_chunk_attn_pairs_global_total",
            "the same for the global layers: sum of (q + 1) x global layers",
        ),
        chunk_attn_key_blocks_visited=reg.counter(
            "calfkit_engine_chunk_attn_key_blocks_visited_total",
            "(query tile, key block) steps the chunk attention kernel's bounds "
            "walk for a launched chunk, over rows and layers of both kinds",
        ),
        chunk_attn_key_blocks_dense=reg.counter(
            "calfkit_engine_chunk_attn_key_blocks_dense_total",
            "the same steps as the key-block loop of model.blocked_attention "
            "walks them (every tile from the chunk's first block to its last): "
            "visited / dense is what the per-tile bounds save",
        ),
        chunk_tokens=reg.counter(
            "calfkit_engine_chunk_tokens_total",
            "positions the launched prefill chunks computed: rows x chunk, the "
            "wave's bucket and its rows' padding included (host arithmetic at launch)",
        ),
        chunk_tokens_padding=reg.counter(
            "calfkit_engine_chunk_tokens_padding_total",
            "of those, the positions that held no prompt token (past a row's own "
            "length in its bucket): padding / chunk_tokens is what grouping a "
            "wave's rows by length would save",
        ),
        window_pages_given_back=reg.counter(
            "calfkit_engine_window_pages_given_back_total",
            "ring pages of window layers written over while their row lived (and "
            "pages of a prompt longer than the ring that never landed)",
        ),
        kv_pages_global_in_use=reg.gauge(
            "calfkit_engine_kv_pages_global_in_use",
            "pages of the global pool (layers that keep every token) reserved by "
            "live rows (the last engine that moved; 0 without window layers)",
        ),
        kv_pages_window_in_use=reg.gauge(
            "calfkit_engine_kv_pages_window_in_use",
            "pages of the window pool (a ring a row) reserved by live rows",
        ),
        kv_pages_global_total=reg.gauge(
            "calfkit_engine_kv_pages_global_total",
            "allocatable pages of the global pool (pools by cache kind)",
        ),
        kv_pages_window_total=reg.gauge(
            "calfkit_engine_kv_pages_window_total",
            "allocatable pages of the window pool",
        ),
        prefix_reuse_declined_recurrent=reg.counter(
            "calfkit_engine_prefix_reuse_declined_recurrent_total",
            "requests whose cached prefix was not reused because the model "
            "carries recurrent state (pages hold no state at their boundary)",
        ),
        pipeline_drains=reg.counter(
            "calfkit_engine_pipeline_drains_total",
            "host syncs that left the device known empty (every program "
            "enqueued proved complete) while rows were active or a wave was "
            "in flight",
        ),
        pipeline_drains_wave=reg.counter(
            "calfkit_engine_pipeline_drains_wave_total",
            "those of them that were an admission wave's landing sync: a wave "
            "that landed with no dispatch to ride (an engine with no active "
            "rows, the legacy and speculative lanes)",
        ),
        wave_landings_deferred=reg.counter(
            "calfkit_engine_wave_landings_deferred_total",
            "admission waves whose first tokens came down with the landing of "
            "the dispatch that carried their last chunk, a later dispatch "
            "already queued behind it: no drain",
        ),
        stream_blocks=reg.counter(
            "calfkit_engine_stream_blocks_total",
            "token stream: blocks (one dispatch's tokens for one request) "
            "taken by their consumers",
        ),
        stream_events=reg.counter(
            "calfkit_engine_stream_events_total",
            "token stream: text deltas handed to the stream's consumer",
        ),
        loop_stalls=reg.counter(
            "calfkit_engine_loop_stalls_total",
            "event loop: heartbeats of the engine's that came more than 20 ms late",
        ),
        phase_longs=reg.counter(
            "calfkit_engine_phase_longs_total",
            "dispatch loop: phases that outlasted what the device queue hides "
            "(a host phase one dispatch's wall time, a sync four)",
        ),
        programs_built=reg.counter(
            "calfkit_engine_programs_built_total",
            "calls in which JAX built a program: a jit key's first use, or "
            "arguments of another kind under it (GET /programs lists them)",
        ),
        recurrent_state_bytes=reg.gauge(
            "calfkit_engine_recurrent_state_bytes",
            "device bytes reserved for the slots' recurrent state "
            "(the last engine built)",
        ),
        moe_assignments=reg.counter(
            "calfkit_engine_moe_assignments_total",
            "token-expert pairs the expert layers computed for REAL tokens "
            "(experts per token x expert layers x tokens; a chunk's padded "
            "positions and a decode step's inactive rows are computed and "
            "not counted)",
        ),
        moe_assignments_absent=reg.counter(
            "calfkit_engine_moe_assignments_absent_total",
            "token-expert pairs of REAL tokens whose expert another device "
            "holds (experts held by share: the gate chose it, this device "
            "left its part out); 0 where every expert is held",
        ),
        moe_rows_in_held_groups=reg.counter(
            "calfkit_engine_moe_rows_in_held_groups_total",
            "REAL tokens, summed over expert layers, one of whose kept routing "
            "groups lies among the experts held here (a gate that chooses by "
            "group: the rows an exchange would send this device); 0 for a "
            "gate without groups",
        ),
        moe_expert_tokens_max=reg.counter(
            "calfkit_engine_moe_expert_tokens_max_total",
            "tokens of the busiest expert, summed over expert layers and "
            "dispatches (a wave's chunks count as one dispatch)",
        ),
        moe_expert_tokens_mean=reg.counter(
            "calfkit_engine_moe_expert_tokens_mean_total",
            "tokens of the mean expert, summed the same way: max / mean is "
            "the routing's imbalance",
        ),
        moe_experts_hit=reg.counter(
            "calfkit_engine_moe_experts_hit_total",
            "distinct experts a decode step had to read, summed over expert "
            "layers and steps",
        ),
        moe_step_kernel_steps=reg.counter(
            "calfkit_engine_moe_step_kernel_steps_total",
            "decode steps whose routed-expert products took the step kernel "
            "(pallas_moe: the experts the step's real rows hit, read in place)",
        ),
        moe_grouped_chunks=reg.counter(
            "calfkit_engine_moe_grouped_chunks_total",
            "chunk dispatches whose expert products took the grouped form "
            "(rows x chunk tokens past moe.dense_form's limit for the shape)",
        ),
        moe_dense_chunks=reg.counter(
            "calfkit_engine_moe_dense_chunks_total",
            "chunk dispatches whose expert products took the dense form, as "
            "every decode step's do",
        ),
        latent_cache_bytes=reg.gauge(
            "calfkit_engine_latent_cache_bytes",
            "device bytes reserved for the latent (MLA) page pool "
            "(the last engine built; 0 for a model that keeps K and V)",
        ),
        active_requests=reg.gauge(
            "calfkit_engine_active_requests",
            "requests holding a slot (summed across the process's engines)",
        ),
    )
    for name in _SECONDS_FIELDS:
        # "phase_sync_s" -> calfkit_engine_phase_sync_seconds_total
        out[name] = reg.counter(
            f"calfkit_engine_{name[:-len('_s')]}_seconds_total",
            _SECONDS_HELP[name],
        )
    return out


@hotpath
def _deliver_batch(deliveries: "list[tuple[asyncio.Queue, tuple[float, list]]]") -> None:
    """Event-loop side of the batched cross-thread token fan-out.

    Each request's whole dispatch-worth of tokens lands as ONE queue item
    (a block: the moment its dispatch landed and the list of its tokens,
    possibly ending in _DONE): one consumer wakeup per dispatch
    instead of one per token — at 32-step dispatches that is 32x less
    event-loop churn on the serving hot path.  ``engine.deliver`` on the
    profiler's clock: the loop's work, beside the tick's phases."""
    with jax.profiler.TraceAnnotation(DELIVER):
        for queue, block in deliveries:
            queue.put_nowait(block)


class _Program:
    """One entry of the engine's jit caches: the jitted function, and the
    account the engine keeps of it.  Calling it IS the enqueue: the
    engine's ``_enq_seq`` goes up by one (the program's number on the
    device's in-order queue).  A call in which JAX BUILT something is kept
    apart (trace + lower + compile, or a load from the compile cache: JAX
    does all of it inside the call): the key's first use, and every later
    call that met arguments of another kind under the same key (an
    uncommitted array where a program's output was: JAX specializes again,
    and only the count of what it holds under the function shows it).
    Everything else (``lower``, ...) is the jitted function's own."""

    __slots__ = ("engine", "family", "key", "fn", "builds", "build_s", "first_seq",
                 "built_seq", "built_s", "uses")

    def __init__(self, engine: "InferenceEngine", family: str, key: tuple, fn: Any):
        self.engine, self.family, self.key, self.fn = engine, family, key, fn
        self.builds = 0  # specializations JAX holds under the key
        self.build_s = 0.0  # the seconds of the calls that built them
        self.first_seq: "int | None" = None
        self.built_seq: "int | None" = None  # the newest such call, and its seconds
        self.built_s = 0.0
        self.uses = 0

    def __call__(self, *args: Any, **kw: Any) -> Any:
        engine = self.engine
        engine._enq_seq += 1
        self.uses += 1
        began = time.perf_counter()
        out = self.fn(*args, **kw)
        if self.fn._cache_size() != self.builds:
            self.builds = self.fn._cache_size()
            self.built_s = time.perf_counter() - began
            self.built_seq = engine._enq_seq
            if self.first_seq is None:
                self.first_seq = self.built_seq
            self.build_s += self.built_s
            engine.stats.programs_built += 1
            engine.stats.program_build_s += self.built_s
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(self.fn, name)


def _layer_kinds(cfg: ModelConfig) -> "tuple | None":
    """For pools by cache kind: which rows of the prefill scratch and of the
    fresh-token ring (all K and V layers, stack order) belong to the global
    and which to the window pool."""
    return (cfg.global_layer_ids, cfg.window_layer_ids) if cfg.windowed else None


def _pool_dtype(side: Any) -> Any:
    """The cache's dtype, from one side of it (a pair by cache kind, or the
    array): what a ring or a scratch beside it is made in."""
    return jax.tree.leaves(side)[0].dtype


def _stored_layout(cfg: ModelConfig, k: Any, v: Any) -> str:
    """Each side of the page pool AS IT IS STORED, by cache kind, with ``f``,
    the positions of a head that share a stored row (``model.make_page_pool``
    defines the form): what says, once at start-up, whether a head narrower
    than a lane tile lies the way the decode kernel and the write's loop
    take it (``f`` > 1) or as declared."""
    names = ("c", "k_rope") if cfg.latent else ("K", "V")
    kinds = zip(("global ", "window "), k, v) if cfg.windowed else (("", k, v),)
    return "; ".join(
        kind + ", ".join(
            f"{name} {side.dtype.name}{list(side.shape)} f={side.shape[-1] // width}"
            for name, side, width in zip(names, sides, cfg.cache_dims))
        for kind, *sides in kinds)


def _some(x: Any) -> tuple:
    """``(x,)``, or ``()`` for None: an optional argument or result that a
    program without it never sees."""
    return () if x is None else (x,)


def _carried_kw(state: Any, moe: Any, carried: list, ssm_impl: str, moe_step_impl: str) -> dict:
    """What a decode step takes besides the cache, out of its scan's carry:
    a hybrid's recurrent state first, then a routed-expert model's counters
    (each only where the program was given one, with the implementation
    the engine resolved for what steps it)."""
    carried, kw = list(carried), {}
    if state is not None:
        kw.update(state=carried.pop(0), ssm_impl=ssm_impl)
    if moe is not None:
        kw.update(moe=carried.pop(0), moe_step_impl=moe_step_impl)
    return kw


@jax.named_scope("finalize")
def _finalize_wave_math(
    cfg, paged, sampled,
    k, v, sk, sv, last, lens, slots, true_lens, last_logits,
    slot_keys, temp, top_k, top_p,
    seeds, w_temp, w_top_k, w_top_p,
    tables, page_rows, scatter_ids,
    state=None, wstate=None,
):
    """The wave-landing math shared by single-shot and chunked prefill:
    scatter scratch K/V into the cache (rows or pages), install per-slot
    sampling state, scatter the wave's last/lens rows, sample each row's
    first token from its last-position logits.  Runs inside jit (all
    callers trace it) — the last/lens scatter used to run eagerly on the
    host, costing two XLA dispatches PER REQUEST at admission."""
    R = slots.shape[0]
    if paged:
        k, v = M.write_prefill_pages((k, v), (sk, sv), scatter_ids, _layer_kinds(cfg))
        # (pools by kind: the tables and the wave's rows are pairs)
        tables = jax.tree.map(lambda table, rows: table.at[slots].set(rows), tables, page_rows)
    else:
        P = sk.shape[3]
        for r in range(R):  # R is small & static: unrolled row scatter
            k = lax.dynamic_update_slice_in_dim(
                k, lax.dynamic_slice_in_dim(sk, r, 1, axis=1)[:, :, :, :P],
                slots[r], axis=1,
            )
            v = lax.dynamic_update_slice_in_dim(
                v, lax.dynamic_slice_in_dim(sv, r, 1, axis=1)[:, :, :, :P],
                slots[r], axis=1,
            )
    wave_keys = jax.vmap(jax.random.key)(seeds)
    slot_keys = slot_keys.at[slots].set(wave_keys)
    temp = temp.at[slots].set(w_temp)
    top_k = top_k.at[slots].set(w_top_k)
    top_p = top_p.at[slots].set(w_top_p)
    if sampled:
        subs = jax.vmap(jax.random.fold_in)(wave_keys, true_lens)
        firsts = sample_slots(last_logits, subs, w_temp, w_top_k, w_top_p)
    else:
        with jax.named_scope("sample"):
            firsts = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    last = last.at[slots].set(firsts)
    lens = lens.at[slots].set(true_lens)
    out = (k, v, tables, last, lens, slot_keys, temp, top_k, top_p, firsts)
    if state is None:
        return out
    # the wave's recurrent state lands in its slots as its pages do: the
    # whole of a slot's state is overwritten, nothing of the last tenant stays
    # (a short convolution's matrix side is empty: its scatter moves no byte)
    with jax.named_scope("state_land"):
        (ssm, conv), (w_ssm, w_conv) = state, wstate
        state = (ssm.at[:, slots].set(w_ssm), conv.at[:, :, slots].set(w_conv))
    return (*out, state)


@dataclass
class GenRequest:
    prompt: list[int]
    max_new_tokens: int
    stop_tokens: frozenset[int]
    sampling: SamplingParams | None = None  # None → engine default
    seed: int | None = None  # None → engine-derived per-admission stream
    # speculative decoding only: prompt + every emitted token, maintained
    # by _record_token — the n-gram drafter matches against it and the
    # draft model catches its KV up from it.  None when speculation is off
    # (the non-spec hot path never pays the append).
    history: "list[int] | None" = None
    # unbounded-ok: delivery growth is bounded by the max_out_blocks
    # stall-cancel in the scheduler (_check_stalls), not by queue maxsize —
    # a maxsize put_nowait would drop tokens mid-stream instead of reaping
    # the stalled consumer whole
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    pages: list[int] = field(default_factory=list)  # paged-KV reservation
    # prefix caching: reused token count, the shared (cache-owned) page
    # prefix of ``pages``, and the prompt's full-page chain hashes
    reuse_len: int = 0
    shared_pages: list[int] = field(default_factory=list)
    # pools by cache kind: the row's ring of window pages (``pages``: its global pages)
    ring_pages: list[int] = field(default_factory=list)
    page_hashes: list = field(default_factory=list)
    reuse_declined: bool = False  # counted once, however often it is replanned
    slot: int = -1
    generated: int = 0
    prefill_ms: float = 0.0
    cancelled: bool = False
    # deadline-aware overload protection (ISSUE 5): the request's absolute
    # wall-clock deadline (epoch seconds via cancellation.wall_clock) —
    # None = undeadlined.  ``expired`` marks a deadline-driven cancel so
    # the consumer's _consume raises a typed DeadlineExceededError instead
    # of ending the stream silently; ``stalled`` marks a max_out_blocks
    # stall-cancel the same way (typed EngineOverloadedError on resume).
    deadline: "float | None" = None
    expired: bool = False
    stalled: bool = False
    # caller liveness lease (ISSUE 10): the CALLER's process lease this
    # run is registered against (None = un-leased, the pre-lease
    # behavior).  ``orphaned`` marks a lease-lapse reap so _raise_terminal
    # raises the typed non-retriable RunOrphanedError — published to the
    # (dead) reply topic for the record, since nobody is listening.
    lease_id: "str | None" = None
    lease_ttl: float = 0.0
    # back-pointer into _lease_heap, nulled at retirement like
    # deadline_entry so the heap never pins a finished request's memory
    lease_entry: "list | None" = None
    orphaned: bool = False
    # multi-tenant QoS (ISSUE 20): the caller's priority class
    # ("interactive" | "batch"), resolved at submit — under overload,
    # batch sheds first, reaps first at equal expiry.  ``shed`` marks a
    # QUEUED request evicted by priority-ordered shedding (an arriving
    # interactive request claimed its place at a full lane) so
    # _raise_terminal raises the typed retriable EngineOverloadedError;
    # ``shed_detail`` carries the (lane, pending, limit) observed at the
    # eviction so the typed fault reports the same detail as a
    # shed-at-submit (the ISSUE 20 drive-by's uniformity law).
    priority: str = "interactive"
    shed: bool = False
    shed_detail: "tuple[str, int, int] | None" = None
    # the dispatch-progress watchdog faulted this request (ISSUE 9): the
    # consumer's _consume raises a typed RETRIABLE EngineWedgedError so
    # the caller fails over to another replica instead of timing out
    wedged: bool = False
    # back-pointer into _deadline_heap so a FINISHED request's entry can
    # be nulled immediately (_drop_deadline) instead of strongly holding
    # the prompt/history/queue until the deadline lazily pops — minutes
    # of dead memory per request under sustained load otherwise
    deadline_entry: "list | None" = None
    # the request's trace/correlation id (the tracing layer's trace_id —
    # client-minted equal to the correlation id), attached to every
    # flight-recorder event so ``ck timeline <correlation-id>`` can
    # reconstruct this request's lifecycle from a dump.  Precomputed
    # string: journal appends never format.
    corr: "str | None" = None
    # the logical run this request serves (ISSUE 19): the node kernel's
    # run-identity contextvar (x-mesh-run) captured at submit, so the
    # page ledger can attribute HBM by run, not just by attempt.  None =
    # un-linked (direct engine use, pre-run emitters).  Precomputed
    # string, like corr: ledger appends never format.
    run: "str | None" = None
    started_at: float = field(default_factory=time.perf_counter)
    # admission, measured where the wait happens (ISSUE 24): the moment a
    # slot was granted, the wave it was granted in, and the admission
    # ledger's four blocked_*_s counters as they stood at submit — their
    # growth until the grant says what held the queue longest meanwhile
    granted_at: "float | None" = None
    wave_rows: int = 0
    wave_bucket: int = 0
    blocked_at_submit: "tuple[float, ...] | None" = None
    blocked_on: str = "none"
    # the request's live _retire_heap entry ([bound, seq, request] list);
    # cleared at retirement so the heap stops pinning this object's
    # prompt/queue memory (r3 advisor finding)
    heap_entry: Any = None
    # the caller's account of this request's stream (``generate``'s
    # ``account``), booked a block by ``_consume``; None: the totals only
    account: "StreamAccount | None" = None


class StreamAccount:
    """One request's blocks as its consumer took them: how many, the moments
    the first and the last one's dispatch LANDED (``perf_counter``: the
    engine's own time for the stream, free of every consumer), the wait
    from a landing to the take (the loop's turn: the hop from the tick
    thread, ``_deliver_batch``, the task's wake-up) and the time suspended
    waiting for a block.  One ``perf_counter`` read a block a stage,
    nothing a token."""

    __slots__ = ("blocks", "first_landed", "last_landed", "deliver_wait_s",
                 "deliver_wait_max_s", "block_wait_s")

    def __init__(self) -> None:
        self.blocks = 0
        self.first_landed = self.last_landed = 0.0
        self.deliver_wait_s = self.deliver_wait_max_s = self.block_wait_s = 0.0

    @hotpath
    def take(self, landed: float, waited: float, suspended: float) -> None:
        if not self.blocks:
            self.first_landed = landed
        self.blocks += 1
        self.last_landed = landed
        self.deliver_wait_s += waited
        if waited > self.deliver_wait_max_s:
            self.deliver_wait_max_s = waited
        self.block_wait_s += suspended


def _annotation(phase: str, seq: "int | None") -> Any:
    """The ``engine.<phase>`` annotation of the phase clock, ``seq`` riding
    it as metadata where the phase has one."""
    name = _PHASE_ANNOTATION[phase]
    return (jax.profiler.TraceAnnotation(name) if seq is None
            else jax.profiler.TraceAnnotation(name, seq=seq))


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    decode_dispatches: int = 0
    decode_time_s: float = 0.0
    occupancy_sum: float = 0.0
    # occupancy distribution: dispatch counts per quartile of max_batch_size
    # (diagnoses WHERE a low mean comes from: ramp-up, tail, or admission
    # starvation — the round-2 bench's 0.365 mean needs this split)
    occupancy_hist: list = field(default_factory=lambda: [0, 0, 0, 0])
    # dispatch lengths actually used (adaptive shortening visibility)
    short_dispatches: int = 0
    long_requests: int = 0  # served via the sequence-parallel lane
    long_dispatches: int = 0  # sp-lane decode dispatches (whole-mesh units)
    prefix_hits: int = 0  # admissions that reused cached prefix pages
    prefix_reused_tokens: int = 0  # prompt tokens NOT re-prefilled
    # speculative decoding: drafts offered to verify dispatches, and how
    # many were accepted (each accepted draft is a token the engine did
    # NOT pay a full weight-read dispatch for)
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_emitted: int = 0  # tokens emitted by verify dispatches (device)
    spec_rows: int = 0  # Σ over verify dispatches of active rows
    # overlapped execution: pad tokens discarded because their row retired
    # (or cancelled) while the dispatch that generated them was already in
    # flight — the price of one-dispatch-late retirement, bounded by
    # retired rows x steps_per_dispatch
    overlap_wasted_tokens: int = 0
    # overload protection (ISSUE 5): requests refused at submit by the
    # max_pending bound; requests whose deadline passed (at submit, in
    # queue, or while active); consumer-cancelled requests actually
    # reaped; cancels that arrived via the mesh `cancel` record
    # (cancel_correlation) — a subset of cancelled_requests; and requests
    # stall-cancelled by the max_out_blocks delivery bound
    shed_requests: int = 0
    expired_requests: int = 0
    cancelled_requests: int = 0
    cancel_propagated: int = 0
    delivery_stalled: int = 0
    # caller liveness (ISSUE 10): runs abandoned because their CALLER's
    # lease lapsed (queued or active — the server-side orphan reaper),
    # surfaced as the ORPHANS column of `ck stats`
    orphaned_requests: int = 0
    # ragged unified waves (ISSUE 6): prefill chunk tokens absorbed into
    # decode dispatches (slack compute that would otherwise idle), and
    # how many dispatches actually carried both kinds of work.  The
    # occupancy accounting above counts absorbed chunk rows as dispatch
    # participants — mean_occupancy IS the unified-wave fill metric.
    prefill_absorbed_tokens: int = 0
    unified_dispatches: int = 0
    # engine wedge watchdog (ISSUE 9): how many times the dispatch-
    # progress watchdog declared the engine wedged, and how many requests
    # it faulted with the typed retriable EngineWedgedError (so callers
    # failed over instead of burning their deadlines)
    watchdog_trips: int = 0
    watchdog_faulted: int = 0
    # capacity observatory (ISSUE 19): pages reclaimed from the prefix
    # cache under allocation pressure, and admissions whose page alloc
    # came up short on the first try (evictable shortfall or not) — the
    # advert's density-pressure signals, windowed like every counter
    prefix_evictions: int = 0
    alloc_stalls: int = 0
    # multi-tenant QoS (ISSUE 20): the per-class split of the shed and
    # expiry counters above (shed_requests/expired_requests stay the
    # totals).  The advert carries these so RoutingPolicy can tie-break
    # on interactive pressure and `ck stats` can show WHO degradation
    # actually hit — the shed-fairness gate law (zero interactive sheds
    # while any batch request is sheddable) is only auditable with the
    # split visible.
    interactive_shed: int = 0
    batch_shed: int = 0
    interactive_expired: int = 0
    batch_expired: int = 0
    # EWMA of decode-dispatch latency (ms) — the advert's tiebreak signal
    # for many-router coherence (ISSUE 10 satellite): N independent
    # routers seeing identical queue depths between heartbeat beats stop
    # herding when ties break on which replica is actually dispatching
    # faster.  A fold, not a counter: it never enters _COUNTER_FIELDS /
    # window deltas.
    dispatch_ewma_ms: float = 0.0
    # the dispatch loop's exclusive phase clock (ISSUE 24): host seconds
    # by what the ONE thread of control (serve loop -> tick thread -> serve
    # loop) was doing.  At any moment the loop is in exactly one phase, so
    # the eight sum to the loop's wall time.  See :meth:`enter`.
    phase_reap_s: float = 0.0  # per-pass sweeps: cancels, deadlines, orphans, stalls
    phase_admit_s: float = 0.0  # wave formation, page reservation, activation
    phase_handoff_s: float = 0.0  # the asyncio.to_thread hop, both ways
    phase_prep_s: float = 0.0  # host-side dispatch inputs
    phase_enqueue_s: float = 0.0  # the jit call, to its return
    phase_sync_s: float = 0.0  # blocked on the device
    phase_fanout_s: float = 0.0  # after the sync: fan-out, retirement, frees
    phase_idle_s: float = 0.0  # awaiting work
    # not a phase: with rows active or a wave in flight, seconds from the
    # sync that left the device known empty to the next enqueue
    # (Σ dispatch_gap_ms observations).  "Empty" is judged by the device's
    # queue (every program enqueued proved complete by a host sync), not by
    # the host's bookkeeping of what it has landed
    starved_s: float = 0.0
    # the syncs that left it so, and those of them that were a wave's
    # landing sync: a wave that landed with no dispatch to ride (onto an
    # engine with no active rows; the legacy and speculative lanes)
    pipeline_drains: int = 0
    pipeline_drains_wave: int = 0
    # the waves whose first tokens came down with the landing of the dispatch
    # that carried their last chunk, the next dispatch already queued behind
    # it: the landings that were NOT a drain.  The engaged share is
    # deferred / (deferred + pipeline_drains_wave)
    wave_landings_deferred: int = 0
    # calls in which JAX built a program (a jit key's first use, or a later
    # one with arguments of another kind under it): how many, and their
    # seconds (trace + lower + compile, or a cache load; ``enqueue`` holds
    # them too and cannot tell them apart).  ``InferenceEngine.programs()``
    # is the table
    programs_built: int = 0
    program_build_s: float = 0.0
    # a token's road from its dispatch's landing to the stream's consumer,
    # process totals booked on the event loop where the work happens: blocks
    # (one dispatch's tokens for one request) taken and their wait since the
    # landing (``_consume``); text deltas handed over, the seconds making
    # them and the seconds the stream stood suspended in its consumer
    # (``JaxLocalModelClient.request_stream``, which holds the engine)
    stream_blocks: int = 0
    stream_events: int = 0
    stream_deliver_wait_s: float = 0.0
    stream_emit_s: float = 0.0
    stream_backpressure_s: float = 0.0
    # the loop's heartbeat (``_heartbeat``): beats that came more than their
    # own period late, and their lateness: a stall with no sync in it
    loop_stalls: int = 0
    loop_stall_s: float = 0.0
    # the tick's side of a stall (``enter``): phases other than ``idle`` that
    # outlasted ``long_after`` and their WHOLE seconds, booked where the
    # phase closes; ``counters()`` counts an open one up to now
    phase_longs: int = 0
    phase_long_s: float = 0.0
    # the admission-blocked ledger: seconds the head of the queue waited,
    # by what held it, and the free-slot integral while anyone queued
    blocked_slots_s: float = 0.0  # no free slot
    blocked_pages_s: float = 0.0  # page allocation came back short
    blocked_wave_s: float = 0.0  # an admission wave already in flight
    blocked_budget_s: float = 0.0  # ragged token budget / wave trim / bucket
    empty_slot_queued_s: float = 0.0  # slot-seconds
    # the paged decode read, a sum over decode steps from the host mirror
    # of the row lengths (no device sync): the pages the active rows hold,
    # ceil(len / page) each, beside rows in the program x the window
    # bucket's pages.  live / window by difference is the share of the XLA
    # window gather's bytes that a read in place still moves.  The rows that
    # hold a page, summed the same way, are the walks the paged decode kernel
    # makes a layer: pages_live over rows_live is the mean walk in pages
    # (the kernel's copy pipeline runs from one row's walk into the next).
    decode_pages_live: int = 0
    decode_pages_window: int = 0
    decode_rows_live: int = 0
    # a second kind of per-sequence state (models with recurrent layers):
    # requests whose cached prefix went unused because reused pages carry
    # no state at their boundary; and, a gauge, the device bytes the slots'
    # state reserves (0 for a model without such layers)
    prefix_reuse_declined_recurrent: int = 0
    recurrent_state_bytes: int = 0
    # pages by cache kind (a model with window layers; 0 without): requests
    # whose prefix reuse was declined; sums over decode steps, from the host
    # mirror of the row lengths, of the keys and values a step has to read,
    # rows x min(len, W) x window layers and rows x len x global layers (a
    # window here is the model's ATTENTION window: ``decode_pages_window``
    # above means a decode context bucket); ring pages written over while
    # their row lived; and, gauges, each pool's pages reserved and in all
    prefix_reuse_declined_window: int = 0
    decode_window_tokens_read: int = 0
    decode_global_tokens_read: int = 0
    window_pages_given_back: int = 0
    # a window stack's prefill chunks (0 for every other model), host
    # arithmetic at launch (``_note_chunk``): the (query, key)
    # pairs the chunk's OWN positions must attend, ``sum_q min(q + 1, W)`` a
    # window layer and ``sum_q (q + 1)`` a global one, over that kind's
    # layers: the needed work, whatever computes it; and the (query tile,
    # key block) steps the kernel's bounds walk against those the key-block
    # loop walks for the same chunk (all layers)
    chunk_attn_pairs_window: int = 0
    chunk_attn_pairs_global: int = 0
    chunk_attn_key_blocks_visited: int = 0
    chunk_attn_key_blocks_dense: int = 0
    # every model's prefill chunks (``_note_chunk``): the positions a launched
    # chunk computed (rows x chunk) and those of them past their row's own
    # prompt: what a wave's bucket and its widest row cost the others
    chunk_tokens: int = 0
    chunk_tokens_padding: int = 0
    kv_pages_global_in_use: int = 0
    kv_pages_window_in_use: int = 0
    kv_pages_global_total: int = 0
    kv_pages_window_total: int = 0
    # routed experts (0 for a model without them): token-expert pairs
    # computed for real tokens (the HELD experts' alone, and beside them the
    # pairs whose expert another device holds, where the experts are held
    # by share); the busiest and the mean expert's tokens,
    # summed over expert layers and dispatches (their ratio is the
    # routing's imbalance); distinct experts the decode steps had to read,
    # summed over layers and steps; chunk dispatches by the form
    # ``moe.dense_form`` gave their rows x chunk tokens (counted on the host
    # at enqueue, from shapes).  And, a gauge, the device bytes of a
    # latent (MLA) page pool.
    moe_assignments: int = 0
    moe_assignments_absent: int = 0
    moe_rows_in_held_groups: int = 0  # a gate that chooses by group (moe.kept_groups)
    moe_expert_tokens_max: int = 0
    moe_expert_tokens_mean: float = 0.0
    moe_experts_hit: int = 0
    moe_step_kernel_steps: int = 0  # decode steps that read the hit alone (pallas_moe)
    moe_grouped_chunks: int = 0
    moe_dense_chunks: int = 0
    latent_cache_bytes: int = 0
    # an EVA stack (0 without one): the exact keys of their own window and the
    # pooled entries behind it that the decode steps' rows had to read (rows x
    # entries x layers, summed over steps); chunks pooled and windows closed;
    # the pairs its prefill chunks had to attend; a gauge, the summary pool's bytes
    decode_eva_window_tokens_read: int = 0
    decode_eva_summaries_read: int = 0
    eva_chunks_pooled: int = 0
    eva_windows_closed: int = 0
    chunk_attn_pairs_eva_window: int = 0
    chunk_attn_pairs_eva_summary: int = 0
    eva_summary_cache_bytes: int = 0
    # snapshot_and_delta state: the previous window's counter values +
    # timestamp.  Single-consumer by design (the heartbeat advert) — two
    # delta readers would steal each other's intervals.
    _window: Any = field(default=None, repr=False, compare=False)
    # the open intervals of the three clocks above, each ONE tuple so a
    # reader on another thread never sees a name without its start:
    # (phase field, since, annotation, seq), (blocked field, since),
    # (free slots, since)
    _phase: Any = field(default=None, repr=False, compare=False)
    # ``enter`` (the one thread of control) against ``restamp`` (the loop's
    # heartbeat): the least that keeps an annotation from ending twice
    _switch: Any = field(default_factory=threading.Lock, repr=False, compare=False)
    # the engine's flight recorder, for ``PHASE_LONG`` (None: not journalled)
    journal: Any = field(default=None, repr=False, compare=False)
    _blocked: Any = field(default=None, repr=False, compare=False)
    _empty: Any = field(default=None, repr=False, compare=False)

    _COUNTER_FIELDS = (
        "prefill_tokens", "decode_tokens", "decode_dispatches",
        "decode_time_s", "occupancy_sum", "short_dispatches",
        "long_requests", "long_dispatches", "prefix_hits",
        "prefix_reused_tokens", "spec_proposed", "spec_accepted",
        "spec_emitted", "spec_rows", "overlap_wasted_tokens",
        "shed_requests", "expired_requests", "cancelled_requests",
        "cancel_propagated", "delivery_stalled", "orphaned_requests",
        "prefill_absorbed_tokens", "unified_dispatches",
        "watchdog_trips", "watchdog_faulted",
        "prefix_evictions", "alloc_stalls",
        "interactive_shed", "batch_shed",
        "interactive_expired", "batch_expired",
        *_LOCAL_FIELDS,
    )
    # what the heartbeat advert's window carries: the phase clock, the
    # admission ledger and the page sums go to /metrics and ``counters()``
    # only
    _ADVERT_FIELDS = _COUNTER_FIELDS[: -len(_LOCAL_FIELDS)]

    # EWMA smoothing for dispatch_ewma_ms: ~5-dispatch memory — fresh
    # enough to react inside one heartbeat interval, smooth enough that
    # one slow compile-bearing dispatch doesn't whipsaw the tiebreak
    EWMA_ALPHA = 0.2

    def note_dispatch_ewma(self, sample_ms: float) -> None:
        """Fold one dispatch's wall latency into the EWMA (hot path: one
        multiply-add).  The first sample primes the fold directly — a
        zero start would under-report for the whole warm-up."""
        prev = self.dispatch_ewma_ms
        if prev == 0.0:
            self.dispatch_ewma_ms = sample_ms
        else:
            a = self.EWMA_ALPHA
            self.dispatch_ewma_ms = a * sample_ms + (1.0 - a) * prev

    def enter(self, phase: "str | None", seq: "int | None" = None) -> float:
        """Switch the loop's phase clock: close the open phase (its
        seconds to its counter, its ``engine.<phase>`` annotation ended)
        and open ``phase`` (None: the loop has ended).  Returns the one
        ``perf_counter`` read, for callers that need the moment.  The
        annotation puts the same interval on the profiler's clock
        whenever anyone's profile is running, and is a no-op otherwise;
        it may end on another thread than it began on (the profiler
        files it under the thread that ended it).  ``seq`` rides the
        annotation as metadata (its name stays ``engine.<phase>``): the
        number of the first program an ``enqueue`` is about to put on the
        device's queue, or of the program a ``sync`` waits for, which is
        what joins the host's clock to the device's module runs
        (``devtrace.reduce_trace``).  A phase that closes LONG
        (:meth:`long_after`) is booked whole (``phase_longs``,
        ``phase_long_s``) and journalled (``PHASE_LONG``).  The lock is
        for :meth:`restamp`, which the loop's heartbeat calls while the
        tick thread may switch."""
        now = time.perf_counter()
        with self._switch:
            prev = self._phase
            if prev is not None:
                name, since, annotation, began_seq = prev
                if name == phase:
                    return now
                took = now - since
                setattr(self, name, getattr(self, name) + took)
                annotation.__exit__(None, None, None)
                if self._is_long(name, took):
                    self.phase_longs += 1
                    self.phase_long_s += took
                    if self.journal is not None:
                        self.journal.append(
                            flightrec.EV_PHASE_LONG, None, -1, int(took * 1000.0),
                            -1 if began_seq is None else began_seq, _PHASE_ANNOTATION[name])
            if phase is None:
                self._phase = None
            else:
                annotation = _annotation(phase, seq)
                annotation.__enter__()
                self._phase = (phase, now, annotation, seq)
        return now

    def long_after(self, phase: str) -> float:
        """The seconds past which ``phase`` is long: what the two-deep
        device queue hides, one dispatch's wall time for a host phase and
        ``SYNC_DISPATCHES`` of them for a ``sync``, the dispatch's wall time
        being ``dispatch_ewma_ms`` with ``LONG_FLOOR_S`` under it; ``idle``
        is never long."""
        if phase == IDLE:
            return math.inf
        bound = max(LONG_FLOOR_S, self.dispatch_ewma_ms / 1000.0)
        return SYNC_DISPATCHES * bound if phase == SYNC else bound

    def _is_long(self, phase: str, seconds: float) -> bool:
        # (the floor first: one comparison on the ordinary path)
        return seconds > LONG_FLOOR_S and seconds > self.long_after(phase)

    def restamp(self) -> None:
        """End the OPEN phase's annotation and begin another under the same
        name and ``seq``, the phase's counter and its start untouched.  The
        profiler records an annotation when it ENDS, and drops one whose end
        finds no capture running: a phase still open when a capture stops
        (the phase that holds a stall reaching the capture's end) is never
        written.  After this call what lay before it is.  The engine's
        heartbeat calls it for a phase older than :meth:`long_after`
        (:meth:`restamp_if_long`), a capture before it stops the profiler;
        ``devtrace.read_trace`` joins the pieces again."""
        with self._switch:
            prev = self._phase
            if prev is not None:
                phase, since, annotation, seq = prev
                annotation.__exit__(None, None, None)
                annotation = _annotation(phase, seq)
                annotation.__enter__()
                self._phase = (phase, since, annotation, seq)

    def restamp_if_long(self, now: float) -> None:
        """A beat of the engine's heartbeat: :meth:`restamp` where the open
        phase is older than :meth:`long_after` gives it, so a phase of
        ordinary length is never split.  (``idle`` is never long, but a
        capture of an idle engine wants its name too: a host phase's bound.)"""
        phase = self._phase
        if phase is not None and self._is_long(
                HANDOFF if phase[0] == IDLE else phase[0], now - phase[1]):
            self.restamp()

    def note_blocked(
        self, reason: "str | None", free_slots: int, now: float,
        queued_since: "float | None" = None,
    ) -> None:
        """The admission ledger, once a pass: what holds the head of the
        queue from ``now`` on (a ``blocked_*_s`` field; None: nobody is
        queued) and how many slots stand free meanwhile.  The interval
        that ends here goes to the reason that held through it.  A queue
        found non-empty for the first time has been so since its head
        was submitted (``queued_since``), between two passes."""
        blocked, empty = self._blocked, self._empty
        if blocked is not None:
            setattr(self, blocked[0], getattr(self, blocked[0]) + (now - blocked[1]))
            self.empty_slot_queued_s += empty[0] * (now - empty[1])
        elif queued_since is not None:
            now = min(now, queued_since)
        if reason is None:
            self._blocked = self._empty = None
        else:
            self._blocked = (reason, now)
            self._empty = (free_slots, now)

    def blocked_now(self, now: float) -> "tuple[float, ...]":
        """The four ``blocked_*_s`` counters with the open interval
        counted up to ``now`` (serve-loop context)."""
        out = [getattr(self, f) for f in BLOCKED]
        blocked = self._blocked
        if blocked is not None:
            out[BLOCKED.index(blocked[0])] += now - blocked[1]
        return tuple(out)

    def counters(self) -> dict:
        """Every cumulative counter as a plain dict (occupancy_hist as a
        copied list) — the windowing substrate.  The open intervals of
        the phase clock and of the admission ledger are counted up to
        now, so a difference of two snapshots covers exactly the time
        between them."""
        for _ in range(4):  # the tick thread may switch phase meanwhile
            phase = self._phase
            out: dict = {f: getattr(self, f) for f in self._COUNTER_FIELDS}
            if self._phase is phase:
                break
        out["occupancy_hist"] = list(self.occupancy_hist)
        out["recurrent_state_bytes"] = self.recurrent_state_bytes  # a gauge
        out["latent_cache_bytes"] = self.latent_cache_bytes  # a gauge
        out["eva_summary_cache_bytes"] = self.eva_summary_cache_bytes  # a gauge
        for gauge in ("kv_pages_global_in_use", "kv_pages_window_in_use",
                      "kv_pages_global_total", "kv_pages_window_total"):
            out[gauge] = getattr(self, gauge)
        now = time.perf_counter()
        blocked, empty = self._blocked, self._empty
        if phase is not None:
            open_s = now - phase[1]
            out[phase[0]] += open_s
            if self._is_long(phase[0], open_s):
                out["phase_longs"] += 1
                out["phase_long_s"] += open_s
        if blocked is not None:
            out[blocked[0]] += now - blocked[1]
        if empty is not None:
            out["empty_slot_queued_s"] += empty[0] * (now - empty[1])
        return out

    def snapshot_and_delta(self) -> "tuple[dict, dict]":
        """(cumulative, per-interval delta) since the previous call.

        The delta is what heartbeat adverts should report: per-interval
        rates (``tokens_per_second`` over the interval, occupancy-hist
        increments) instead of lifetime cumulative values that flatten
        toward the mean as uptime grows.  The first call's delta covers
        everything since engine construction."""
        now = time.monotonic()
        cur = self.counters()
        prev, prev_t = self._window or (
            {f: 0 for f in self._ADVERT_FIELDS} | {"occupancy_hist": [0, 0, 0, 0]},
            None,
        )
        delta: dict = {
            f: cur[f] - prev[f] for f in self._ADVERT_FIELDS
        }
        delta["occupancy_hist"] = [
            a - b for a, b in zip(cur["occupancy_hist"], prev["occupancy_hist"])
        ]
        delta["interval_s"] = (
            round(now - prev_t, 3) if prev_t is not None else None
        )
        dt = delta["decode_time_s"]
        delta["tokens_per_second"] = (
            round(delta["decode_tokens"] / dt, 1) if dt > 0 else 0.0
        )
        dd = delta["decode_dispatches"]
        delta["mean_occupancy"] = (
            round(delta["occupancy_sum"] / dd, 4) if dd else 0.0
        )
        self._window = (cur, now)
        return cur, delta

    @property
    def tokens_per_second(self) -> float:
        return self.decode_tokens / self.decode_time_s if self.decode_time_s else 0.0

    @property
    def mean_occupancy(self) -> float:
        if not self.decode_dispatches:
            return 0.0
        return self.occupancy_sum / self.decode_dispatches

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify dispatch accepted."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / self.spec_proposed

    @property
    def mean_tokens_per_dispatch(self) -> float:
        """Tokens PROCESSED per decode dispatch: decode tokens plus the
        prefill chunk tokens the ragged scheduler absorbed into those
        same dispatches.  The axis unified waves move — a bifurcated
        schedule pays a separate device invocation for every chunk this
        counts for free."""
        if not self.decode_dispatches:
            return 0.0
        return (
            self.decode_tokens + self.prefill_absorbed_tokens
        ) / self.decode_dispatches

    @property
    def tokens_per_dispatch(self) -> float:
        """Tokens emitted PER SEQUENCE per verify dispatch — the axis
        speculation moves: 1.0 is the non-speculative ratio (one forward,
        one token), k+1 is full acceptance; every point above 1 is a
        weight read the sequence did not pay for.  Batch-aggregate
        throughput is a different axis (occupancy) — this metric
        deliberately excludes it."""
        if not self.spec_rows:
            return 0.0
        return self.spec_emitted / self.spec_rows


class InferenceEngine:
    def __init__(
        self,
        config: ModelConfig,
        runtime: RuntimeConfig | None = None,
        *,
        params: Any = None,
        mesh: Any = None,
        sampling: SamplingParams | None = None,
        seed: int = 0,
        draft_params: Any = None,  # speculative draft-model weights
    ):
        self.config = config
        self.runtime = runtime or RuntimeConfig()
        self.sampling = sampling or SamplingParams()
        rt = self.runtime
        if rt.compilation_cache:
            # persistent XLA cache: window/prefill specializations compile
            # once per machine, not once per process
            enable_compile_cache()

        self.mesh = mesh if mesh is not None else make_mesh(tp=rt.tp, dp=rt.dp)
        # a model with recurrent layers carries per-slot state beside its
        # KV; what cannot keep that state right yet is refused HERE, with
        # its reason, and never served by a path that would drop it
        self._recurrent = config.recurrent
        self._moe = config.moe
        if self._recurrent:
            kind = config.recurrent_kind
            refused = {
                "speculative": (rt.speculative is not None,
                                "a rejected draft needs the state rolled back, and there "
                                "is no state snapshot to roll back to"),
                "tp > 1": (rt.tp > 1 or self.mesh.size > 1,
                           f"the {kind} leaves and the per-slot state have no sharding "
                           "over a mesh of more than one device"),
                "quantization": (rt.quantization is not None,
                                 f"the {kind} leaves have no scales"),
                "long_context": (rt.long_context,
                                 "the sequence-parallel lane carries no recurrent state"),
            }
            if config.expert_hybrid:  # experts in the hybrid stack, held by share or whole
                refused = {
                    "dp > 1": (rt.dp > 1,
                               "the expert leaves and the per-slot state have no sharding "
                               "over a mesh of more than one device"),
                    **refused,
                    "kv_layout='dense'": (rt.kv_layout == "dense",
                                          "the dense decode programs thread no expert "
                                          "counters; the model is served from pages"),
                }
            for option, (asked, why) in refused.items():
                if asked:
                    raise UnsupportedWithRecurrentLayers(
                        f"{config.name} has recurrent ({kind}) layers: "
                        f"RuntimeConfig {option} is not supported with them ({why})"
                    )
        # a model with latent attention keeps ONE latent a token where the
        # others keep K and V per head, and its experts are leaves of their
        # own; what has no code for either yet is refused HERE, with its reason
        if config.latent:
            refused = {
                "speculative": (rt.speculative is not None,
                                "the verify programs attend K and V pairs of heads"),
                "tp > 1 / dp > 1": (rt.tp > 1 or rt.dp > 1 or self.mesh.size > 1,
                                    "the latent pool and the expert leaves have no sharding "
                                    "over a mesh of more than one device"),
                "quantization": (rt.quantization is not None,
                                 "the latent and expert leaves have no scales"),
                "long_context": (rt.long_context,
                                 "the sequence-parallel lane attends K and V per head"),
                "kv_layout='dense'": (rt.kv_layout == "dense",
                                      "the dense rows hold K and V per head; the latent "
                                      "is served from pages"),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise UnsupportedWithLatentAttention(
                        f"{config.name} has latent attention (MLA): RuntimeConfig "
                        f"{option} is not supported with it ({why})"
                    )
        # a model with window layers keeps its pages BY CACHE KIND (a ring of
        # pages a row for the window layers) and reads them under a lower
        # bound; what knows neither yet is refused HERE, with its reason
        self._windowed = config.windowed
        if self._windowed:
            kind, unsupported = (
                ("EVA layers", UnsupportedWithEvaLayers) if config.eva
                else ("sliding-window layers", UnsupportedWithWindowLayers))
            refused = {
                "tp > 1 / dp > 1": (rt.tp > 1 or rt.dp > 1 or self.mesh.size > 1,
                                    "the two pools and the expert leaves have no sharding "
                                    "over a mesh of more than one device, and a layer held "
                                    "by share has no exchange"),
                "quantization": (rt.quantization is not None,
                                 "the expert leaves have no scales"),
                "speculative": (rt.speculative is not None,
                                "the verify programs know no lower bound and write a "
                                "chunk straight to pages, past what a ring leaves room for"),
                "long_context": (rt.long_context,
                                 "the sequence-parallel lane knows no lower bound"),
                "kv_layout='dense'": (rt.kv_layout == "dense",
                                      "dense rows keep every token of every layer: the "
                                      "window layers are served from rings of pages"),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise unsupported(
                        f"{config.name} has {kind}: RuntimeConfig "
                        f"{option} is not supported with them ({why})"
                    )
            if rt.decode_steps_per_dispatch > config.attention_window:
                raise UnsupportedWithWindowLayers(
                    f"{config.name}: decode_steps_per_dispatch "
                    f"({rt.decode_steps_per_dispatch}) is more than the window "
                    f"({config.attention_window}): a dispatch's fresh tokens are read whole")
        # an EVA stack keeps, beside the ring, summary pages that are COMPUTED
        # from it: what cannot hold with that is refused HERE too, by name
        if config.eva:
            from calfkit_tpu.inference.model import positions_per_row

            W, c = config.window_size, config.chunk_size
            refused = {
                "prefix_cache": (rt.prefix_cache,
                                 "a summary page holds what a row pooled of its own ring: "
                                 "no page of either pool outlives its row for a later "
                                 "prompt to share"),
                "chunked_prefill=False": (not rt.chunked_prefill,
                                          "a prompt is prefilled a window at a time against "
                                          "the summaries of the windows before it"),
                f"prefill_chunk={rt.prefill_chunk}": (
                    rt.prefill_chunk != W,
                    f"a prefill chunk is ONE window ({W}): its own part is the causal block "
                    "the chunk kernel computes, everything before it summaries"),
                f"page_size={rt.page_size}": (
                    rt.page_size % c != 0 or (W // c) % rt.page_size != 0
                    or positions_per_row(config.head_dim, rt.page_size, config.dtype) != 1,
                    f"a chunk ({c}) lies in one page, a window's summaries ({W // c}) fill "
                    "whole pages, and the ring is read by position (a head of whole lane "
                    "tiles, or pages too small to pack a narrower one)"),
            }
            for option, (asked, why) in refused.items():
                if asked:
                    raise UnsupportedWithEvaLayers(
                        f"{config.name} has EVA layers: RuntimeConfig {option} is not "
                        f"supported with them ({why})"
                    )
        shardings = param_shardings(config, self.mesh)
        if params is None:
            logger.info(
                "initializing random %s params (%.2fB)", config.name,
                config.param_count / 1e9,
            )
            # born sharded: each device materializes only its own shard (a
            # plain init would build the whole tree on the first device —
            # 16 GB for Llama-3-8B bf16 — before place_params spread it)
            params = jax.jit(
                lambda key: M.init_params(config, key), out_shardings=shardings
            )(jax.random.key(seed))
        if rt.quantization in ("int8", "int4"):
            from calfkit_tpu.inference.quant import (
                align_quant_sharding_keys,
                is_quantized,
                is_quantized4,
                quantize_params,
                quantize_shardings,
            )

            bits = 8 if rt.quantization == "int8" else 4
            wq = params.get("layers", {}).get("wq")
            matching = is_quantized(wq) if bits == 8 else is_quantized4(wq)
            if (is_quantized(wq) or is_quantized4(wq)) and not matching:
                raise ValueError(
                    f"params are pre-quantized at the other bitness than "
                    f"runtime quantization={rt.quantization!r}"
                )
            if not matching:
                # consume: free each full-precision tensor as it quantizes
                # (peak ~1x model size — the 8B random-init path needs this)
                params = quantize_params(params, consume=True, bits=bits)
            shardings = quantize_shardings(shardings, bits=bits)
            if bits == 4:
                shardings = align_quant_sharding_keys(shardings, params)
        elif rt.quantization is not None:
            raise ValueError(f"unsupported quantization {rt.quantization!r}")
        if rt.chunked_prefill and rt.max_seq_len % rt.prefill_chunk:
            # buckets cap at max_seq_len; chunked admission needs every
            # bucket to be a whole number of chunks
            raise ValueError(
                "chunked_prefill requires prefill_chunk to divide "
                f"max_seq_len ({rt.prefill_chunk} vs {rt.max_seq_len})"
            )
        if rt.attention_impl not in ("auto", "xla", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unsupported attention_impl {rt.attention_impl!r}: the "
                "paged decode read is auto | xla | pallas | pallas_interpret"
            )
        if rt.max_prefill_wave < 1:
            raise ValueError("max_prefill_wave must be >= 1")
        if rt.max_prefill_wave & (rt.max_prefill_wave - 1):
            # waves are power-of-two trimmed; a non-power-of-two cap would
            # silently behave as the next power down — reject it loudly
            raise ValueError(
                f"max_prefill_wave must be a power of two "
                f"(got {rt.max_prefill_wave})"
            )
        self._spec = rt.speculative
        self._drafter: Any = None
        if self._spec is not None:
            if self._spec.k < 1:
                raise ValueError(
                    f"speculative.k must be >= 1 (got {self._spec.k})"
                )
            if self._spec.draft is None and draft_params is not None:
                raise ValueError(
                    "draft_params given but speculative.draft is unset"
                )
        elif draft_params is not None:
            raise ValueError("draft_params given but speculation is off")
        self.params = place_params(params, shardings)

        B, S = rt.max_batch_size, rt.max_seq_len
        if rt.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"unsupported kv_layout {rt.kv_layout!r} (dense | paged)"
            )
        self._paged = rt.kv_layout == "paged"
        self._attn_impl = self._resolved_attn_impl()
        self._ssm_impl = self._resolved_ssm_impl()
        self._chunk_attn_impl = self._resolved_chunk_attn_impl()
        self._moe_step_impl = self._resolved_moe_step_impl()
        if self._moe:
            logger.info(
                "routed experts: %d held of %d scored a layer; a decode step's products take %s",
                config.n_routed_experts, config.experts_scored,
                "the step kernel over the experts its rows hit (pallas_moe)"
                if self._moe_step_impl != "xla"
                else "the dense form" if dense_form(rt.max_batch_size, config)
                else "the grouped form",
            )
        if self._paged:
            from calfkit_tpu.inference.paged import PageAllocator
            from calfkit_tpu.inference.sharding import pool_sharding

            if rt.prefill_chunk % rt.page_size:
                raise ValueError(
                    "page_size must divide prefill_chunk "
                    f"({rt.page_size} vs {rt.prefill_chunk})"
                )
            if rt.max_seq_len % rt.page_size:
                # a prefill bucket capped at max_seq_len must still be a
                # whole number of pages (page-granular scatter)
                raise ValueError(
                    "page_size must divide max_seq_len "
                    f"({rt.page_size} vs {rt.max_seq_len})"
                )
            n_pages = rt.pool_pages()
            # entries of a row's table of pages it keeps: a page a page_size
            # tokens, or (an EVA stack's summary pages) a page_size CHUNKS
            self._pages_per_seq = rt.pages_per_seq()
            if config.eva:
                from calfkit_tpu.inference.paged import pages_needed

                self._pages_per_seq = pages_needed(
                    config.summary_entries(rt.max_seq_len), rt.page_size)
                n_pages = rt.num_kv_pages or B * self._pages_per_seq + 1
            pool_sh = pool_sharding(config, self.mesh)
            # born sharded, like the params: never whole on one device
            # the pool is a pair: K and V, or the two parts (c, k_rope) of the
            # one latent a token of a latent-attention model leaves behind
            if self._windowed:
                # pages BY CACHE KIND: each side of the pool, the block tables
                # and the allocator are pairs (global, window).  A row's window
                # table is a ring of ``_ring_pages`` entries that it writes over
                # as it grows; the window pool holds every slot's ring, so what
                # a wave can wait for is global pages (a short request takes a
                # shorter ring)
                from calfkit_tpu.inference.paged import PagesByKind

                self._ring_pages = config.window_ring_pages(
                    rt.page_size, rt.decode_steps_per_dispatch)
                n_window = B * self._ring_pages + 1
                self._k, self._v = jax.jit(
                    lambda: M.make_page_pool(
                        config, n_pages, rt.page_size, window_pages=n_window),
                    out_shardings=((pool_sh, pool_sh), (pool_sh, pool_sh)),
                )()
                self._tables = (jnp.zeros((B, self._pages_per_seq), jnp.int32),
                                jnp.zeros((B, self._ring_pages), jnp.int32))
                # ONE ledger for both pools, in pages of equal bytes: a
                # LAYER's page (a global page is n_global_layers of them, a
                # window page n_window_layers)
                self._page_alloc = PagesByKind(
                    n_pages, n_window, (config.n_global_layers, config.n_window_layers))
                self._ledger = capacity.PageLedger(self._page_alloc.num_pages - 1)
            else:
                self._k, self._v = jax.jit(
                    lambda: M.make_page_pool(config, n_pages, rt.page_size),
                    out_shardings=(pool_sh, pool_sh),
                )()
                self._tables = jnp.zeros((B, rt.pages_per_seq()), jnp.int32)
                self._page_alloc = PageAllocator(n_pages)
                # capacity observatory (ISSUE 19): the page-ownership mirror —
                # maintained O(1) at every alloc/free/evict site below, always
                # on for paged engines (attribution is the headroom advert's
                # substrate; the SAMPLER below is the opt-in part)
                self._ledger = capacity.PageLedger(n_pages - 1)
            self._prefix: Any = None
            if rt.prefix_cache and not self._windowed:
                if not rt.chunked_prefill:
                    raise ValueError(
                        "prefix_cache=True requires chunked_prefill=True "
                        "(reuse seeds the chunk lane's scratch)"
                    )
                from calfkit_tpu.inference.paged import PrefixCache

                self._prefix = PrefixCache()
            logger.info(
                "paged %s pool: %d pages x %d tokens (%.2f GB), stored %s",
                "latent" if config.latent else "KV", n_pages, rt.page_size,
                sum(side.nbytes for side in jax.tree.leaves((self._k, self._v))) / 1e9,
                _stored_layout(config, self._k, self._v),
            )
        else:
            self._prefix = None
            if rt.prefix_cache:
                raise ValueError(
                    "prefix_cache=True requires kv_layout='paged' "
                    "(reuse shares pages between requests)"
                )
            cache_sh = cache_sharding(config, self.mesh, B)
            self._k, self._v = jax.jit(
                lambda: M.make_empty_cache(config, B, S),
                out_shardings=(cache_sh, cache_sh),
            )()
        # the second kind of per-sequence state: per slot, every Mamba
        # layer's SSM and conv state (None for a model without such layers).
        # Threaded and donated through the decode and landing programs like
        # the KV; a slot's state is wholly overwritten when a wave lands in it
        self._state: Any = None
        if self._recurrent:
            from calfkit_tpu.inference.sharding import replicated

            rep_sh = replicated(self.mesh)
            self._state = jax.jit(
                lambda: make_recurrent_state(config, B),
                out_shardings=(rep_sh, rep_sh),
            )()
            logger.info(
                "recurrent state: %d slots x %d %s layers (%.2f GB)",
                B, config.n_recurrent_layers, config.recurrent_kind,
                config.recurrent_state_bytes(B) / 1e9,
            )
        self._last = jnp.zeros((B,), jnp.int32)
        self._lens = jnp.zeros((B,), jnp.int32)
        self._host_lens = np.zeros((B,), np.int64)  # host mirror for windows
        if rt.max_stop_tokens < 1:
            raise ValueError("max_stop_tokens must be >= 1")
        # device-side retirement inputs (overlapped execution): each slot's
        # stop tokens as a fixed-shape row (-1 padded) and the absolute
        # cache length at which the row hits its hard generation bound —
        # min(prompt + max_new - 1, max_seq - 2), so bound-steps-remaining
        # is just hard_end - lens ON DEVICE (always exact, even for a
        # dispatch launched before the previous one's tokens reached the
        # host).  Written at activation, shipped per dispatch like the
        # active mask.
        self._stop_np = np.full((B, rt.max_stop_tokens), -1, np.int32)
        self._hard_end = np.zeros((B,), np.int32)
        # device copies of the two arrays above, re-uploaded only when an
        # activation rewrites them — the launch path must not pay a
        # host→device transfer per dispatch for admission-time constants
        self._retire_dev: "tuple[Any, Any] | None" = None
        self._done_zero = jnp.zeros((B,), jnp.bool_)
        # the launched-but-not-landed decode dispatch (overlap mode only):
        # device handles for its outputs, the slot->request snapshot it
        # was launched with, and the slots whose resource frees are
        # deferred to its landing
        self._pend: "dict | None" = None
        self._last_sync_t: "float | None" = None
        # the device-queue account: every program the dispatch loop enqueues
        # gets a number (``_Program.__call__``), and every designated host
        # sync records the number whose output it read (``_landed``); on one
        # device's in-order stream everything before it is complete too, so
        # the device is KNOWN EMPTY exactly when the two are equal
        self._enq_seq = 0
        self._done_seq = 0
        self._emitter = f"engine/{config.name}"
        # the moment of the sync that left it so (None: a program is queued,
        # or the engine stands idle), the start of that sync's wait, the
        # dispatches enqueued and not yet proved complete (the open
        # ``engine.dispatch`` spans, oldest first) and the moment the last
        # one was
        self._empty_at: "float | None" = None
        self._sync_began = 0.0
        self._unproved: "deque[dict]" = deque(maxlen=8)  # overlap keeps at most two
        self._proved_at = 0.0
        # per-slot sampling state: one decode dispatch serves mixed settings
        # (row-wise knobs are data, not jit specializations)
        self._slot_keys = jax.random.split(jax.random.key(seed + 2), B)
        self._temp = jnp.zeros((B,), jnp.float32)
        self._top_k = jnp.zeros((B,), jnp.int32)
        self._top_p = jnp.ones((B,), jnp.float32)
        self._admissions = 0  # per-request default seed stream

        self._free: list[int] = list(range(B))
        self._active: dict[int, GenRequest] = {}
        # bound-retirement horizon tracking: a min-heap of
        # [absolute decode-clock step at which the request hits a bound,
        # tiebreak, request] so _retirement_near is O(log n) amortized
        # instead of an O(active) scan on the decode thread every dispatch.
        # Pushes happen on the event loop (activation), peeks/pops on the
        # decode thread — the lock covers both.  Early retirements
        # (stop token / cancel) null the entry's request slot via
        # _untrack_retirement so the heap never pins retired-request
        # memory; nulled entries pop lazily, with a compaction pass when
        # they outnumber the live ones.
        self._retire_heap: list[list] = []
        self._retire_lock = threading.Lock()
        self._retire_seq = itertools.count()
        self._retire_stale = 0
        self._decode_clock = 0
        self._cancel_dirty = False  # at least one .cancelled flag is set
        # mesh cancels whose candidate snapshot lost the race with the
        # decode thread (see cancel_correlation): re-matched on the next
        # scheduler pass, where nothing mutates the queues concurrently
        self._deferred_cancels: set[str] = set()
        # deadline enforcement: min-heap of [deadline_epoch, seq, request]
        # peeked once per scheduler pass (O(1) when nothing expired; pops
        # only on actual expiry).  Event-loop-only — submit and reap both
        # run there, so no lock.  Finished requests' entries pop lazily
        # (liveness re-checked at pop time).
        self._deadline_heap: list[list] = []
        self._deadline_seq = itertools.count()
        # caller liveness (ISSUE 10): min-heap of [lease_expiry_epoch,
        # seq, request] — the orphan reaper's O(1)-peek sweep, shaped
        # exactly like the deadline heap (event-loop-only, lazy pops).
        # A popped entry whose lease was REFRESHED since registration is
        # re-pushed at its new expiry, so sustained heartbeats cost one
        # push per TTL per run, not per pass.
        self._lease_heap: list[list] = []
        self._lease_seq = itertools.count()
        # released-lease sweep cursor: a clean caller close must reap
        # NOW, not at the registered expiry — one int compare per pass
        self._lease_release_gen = leases.release_generation()
        # chaos seam (tests/_chaos.py): when set, called with a point name
        # ("tick" per scheduler pass, "dispatch" per decode tick) — an
        # exception it raises crosses the dispatch loop like any real
        # engine fault (journal dump + teardown)
        self._chaos: Any = None
        self._inflight: dict | None = None  # chunked-prefill wave in flight
        # the admission ledger's reason (a BLOCKED field) left by the last
        # _form_wave: what held the head of the queue out of that wave
        self._held_by: str = NO_SLOT
        # requests whose (non-chunked) admission prefill is running in
        # to_thread: otherwise they live only in a local during the JIT
        # compile + prefill — exactly when an early cancel or deadline
        # check most needs to see them.  Flags set here are honored at
        # activation (_activate_wave sheds cancelled corpses).
        self._admitting: list[GenRequest] = []
        self._carry: list[GenRequest] = []  # wave-trimmed, ahead of the queue
        # unbounded-ok: growth is bounded by the max_pending admission shed
        # in generate() (_shed_if_full), typed rejection instead of maxlen
        # silently evicting queued callers
        self._pending: deque[GenRequest] = deque()
        # long-context lane (sequence-parallel; one request at a time)
        # unbounded-ok: bounded by the same max_pending shed (long lane)
        self._long_pending: deque[GenRequest] = deque()
        self._long: dict | None = None  # active long request's device state
        self._long_inflight: dict | None = None  # chunked long prefill
        self._sp_mesh_cache: Any = None
        # ragged unified waves (ISSUE 6): effective only where the fused
        # dispatch has both of its substrates — the chunk lane to absorb
        # from and the overlap launch path to ride; anything else runs
        # the legacy bifurcated schedule (which doubles as the parity
        # oracle at ragged_waves=False)
        self._ragged = bool(
            rt.ragged_waves and rt.chunked_prefill and rt.overlap_dispatch
        )
        self._ragged_budget = ragged_math.token_budget(
            rt.ragged_token_budget, B, rt.decode_steps_per_dispatch,
            rt.prefill_chunk, rt.max_prefill_wave,
        )
        self._wake = asyncio.Event()
        self._task: asyncio.Task[None] | None = None
        self._running = False
        # engine wedge watchdog (ISSUE 9): a separate event-loop task —
        # the serve loop itself blocks inside asyncio.to_thread when a
        # device grant wedges, which is exactly the state the watchdog
        # exists to detect.  ``_progress_at`` is stamped (wall_clock seam,
        # so the chaos virtual clock drives it) at every dispatch/wave
        # LANDING; with work pending and no stamp for watchdog_stall_s
        # the engine is declared wedged: journal dump, readiness false,
        # every pending request faulted typed-retriable.  A later landing
        # un-wedges (the stuck requests were already cancelled; the
        # ordinary reap frees their resources).
        self._wedged = False
        self._wedged_at = 0.0
        self._progress_at = cancellation.wall_clock()
        self._watchdog_task: asyncio.Task[None] | None = None
        self.stats = EngineStats()
        if self._recurrent:
            self.stats.recurrent_state_bytes = config.recurrent_state_bytes(B)
        if config.latent:
            self.stats.latent_cache_bytes = self._k.nbytes + self._v.nbytes
        if self._windowed:
            self.stats.kv_pages_global_total, self.stats.kv_pages_window_total = (
                a.num_pages - 1 for a in self._page_alloc.by_kind)
        if config.eva:
            self.stats.eva_summary_cache_bytes = self._k[0].nbytes + self._v[0].nbytes
        # a dispatch of a model with routed experts carries their counters
        # beside whatever state it carries (moe.py): zeros in, the
        # dispatch's counts out, read at the landing's one sync
        self._moe_zero = moe_stats_init(config) if self._moe else None
        # tokens sent to each HELD expert of each expert layer since the start
        # (what the scalar moe_* counters are sums of): moe_expert_counts()
        self._moe_counts = (
            np.zeros((config.n_moe_layers, config.n_routed_experts), np.int64)
            if self._moe else None)
        # flight recorder: the ring journal every scheduler decision point
        # appends to (admission, waves, page alloc/free, spec/overlap
        # dispatches, deferred retirement, faults).  Appends are O(1)
        # lock-free; the ring dumps to JSONL on engine fault, SIGUSR2, or
        # the /flightrec endpoint.  flightrec_events=0 makes append a
        # single attribute check.
        self._journal = flightrec.FlightRecorder(
            rt.flightrec_events, label=config.name
        )
        self.stats.journal = self._journal  # ``PHASE_LONG``, from the phase clock
        # capacity observatory (ISSUE 19): the occupancy timeline ring —
        # one sample per dispatch landing, flightrec's ring discipline
        # (capacity_samples=0 makes append a single attribute check).
        # Dense engines get a pool-less ledger so the snapshot/advert
        # keys exist with zeros everywhere.
        if not self._paged:
            self._ledger = capacity.PageLedger(0)
        self._sampler = capacity.CapacitySampler(
            rt.capacity_samples, label=config.name, ledger=self._ledger
        )
        # one precomputed bool so the per-dispatch guard is a single
        # attribute read (capacity_samples=0 must stay effectively free)
        self._capacity_on = self._sampler.capacity > 0
        # the sampler's analytic HBM roofline constants, precomputed once
        # (bench's _perf_model formula; mean context = half the window)
        self._hbm_constants = capacity.hbm_constants(
            config, rt.quantization
        )
        self._hbm_ctx = rt.max_seq_len / 2.0
        self._hbm_state = capacity.recurrent_bytes_per_token(config)
        self._ledger.recurrent_state_bytes = (
            config.recurrent_state_bytes(B) if self._recurrent else 0
        )
        # mesh cancel fan-out: a `cancel` record arriving at any node in
        # the process reaches this engine's request abandonment
        cancellation.register_cancel_target(self)
        # latency telemetry: process-registry instruments + the sync
        # cursors that turn cumulative stats into counter increments
        self.metrics = _engine_metrics()
        self.metrics["recurrent_state_bytes"].set(self.stats.recurrent_state_bytes)
        self.metrics["latent_cache_bytes"].set(self.stats.latent_cache_bytes)
        self.metrics["eva_summary_cache_bytes"].set(self.stats.eva_summary_cache_bytes)
        self.metrics["kv_pages_global_total"].set(self.stats.kv_pages_global_total)
        self.metrics["kv_pages_window_total"].set(self.stats.kv_pages_window_total)
        # per-ENGINE latency histograms: the advert's percentiles must
        # attribute to THIS engine, not blend every engine in the process
        # (the process-registry instruments above stay shared for the
        # /metrics exposition; both are observed, each O(1))
        self._own_registry = MetricsRegistry()
        self.latency = _engine_metrics(self._own_registry, histograms_only=True)
        self._counted = dict.fromkeys(_SYNCED_FIELDS, 0)
        self._counted_lock = threading.Lock()
        # self-cleaning gauge aggregation: an engine abandoned without
        # stop() must not pin its last active count into the process
        # gauge (stop() also clears eagerly and re-sets the gauge)
        weakref.finalize(self, _drop_engine_active, id(self))
        _ENGINES.add(self)

        self._decode_jits: dict[tuple, Any] = {}  # (window, steps, ...)
        self._prefill_jits: dict[tuple, Any] = {}
        if self._spec is not None:
            from calfkit_tpu.inference.spec import build_drafter

            self._drafter = build_drafter(
                self._spec, rt, self.mesh,
                draft_params=draft_params, seed=seed + 3,
            )
            logger.info(
                "speculative decoding on: %s drafter, k=%d",
                "draft-model" if self._spec.draft is not None else "ngram",
                self._spec.k,
            )

    # ------------------------------------------------------------ jit build
    def _resolved_attn_impl(self) -> str:
        """Which implementation the PAGED DECODE READ uses: one of the two
        attention computations that have a kernel (the other, a window
        stack's prefill chunks, is chosen beside it in
        :meth:`_resolved_chunk_attn_impl`; three kernels in all: two bodies
        of this read and the chunk's).  Decided HERE and nowhere
        else, once at construction (``self._attn_impl``), from what the
        engine can observe (PERF.md section 6, PRs 25, 28 and 32: measured
        on the v5e).  Under "auto": the Pallas kernel that reads each row's
        live pages in place when the backend is a TPU, KV is paged, one
        device holds the model (``tp == 1`` and ``dp == 1``: a
        ``pallas_call`` under GSPMD needs a ``shard_map`` over the KV heads
        first) and the shapes are the kernel's; else XLA, the reference.

        WHICH kernel, and so which shapes, follows from the model
        (``config.latent``), for the pool is another thing: K and V pairs of
        heads (:func:`pallas_attention.paged_decode_in_place_ok`: a head of
        whole lane tiles, or one that divides a lane tile, such as 64), or
        one latent a token read in the absorbed form
        (:func:`pallas_attention.latent_decode_in_place_ok`: a latent of
        whole lane tiles beside a rope part that divides one, such as
        512 | 64).

        "pallas" / "pallas_interpret" are for tests and bring-up: they
        waive the platform test alone.  Outside the rest of the rule there
        is no kernel to build, and the engine is refused."""
        impl = self.runtime.attention_impl
        if not impl.startswith("pallas") and not (
            impl == "auto" and jax.devices()[0].platform == "tpu"
        ):
            return "xla"
        from calfkit_tpu.inference import pallas_attention as PA

        c, page = self.config, self.runtime.page_size
        if c.latent:
            shapes_ok = PA.latent_decode_in_place_ok(
                c.kv_lora_rank, c.qk_rope_head_dim, page, c.dtype)
            shapes = (f"a latent of {c.kv_lora_rank} | {c.qk_rope_head_dim} "
                      "(pallas_attention.latent_decode_in_place_ok)")
        else:
            shapes_ok = PA.paged_decode_in_place_ok(c.head_dim, page, c.dtype)
            shapes = f"head_dim={c.head_dim} (pallas_attention.paged_decode_in_place_ok)"
        in_rule = self._paged and self.mesh.size == 1 and shapes_ok
        if impl == "auto":
            return "pallas" if in_rule else "xla"
        if not in_rule:
            raise PA.PallasShapeError(
                f"attention_impl={impl!r} names the paged decode kernel, "
                "which takes kv_layout='paged', one device (tp == dp == 1) "
                "and a page slab of whole tiles; this engine has "
                f"kv_layout={self.runtime.kv_layout!r} on "
                f"{self.mesh.size} device(s), {shapes}, page_size={page}, "
                f"{jnp.dtype(c.dtype).name}"
                ': use "auto" or "xla"'
            )
        return impl

    def _resolved_chunk_attn_impl(self) -> str:
        """Which implementation a WINDOW STACK's prefill and prefill-chunk
        attention uses: the third computation that has a kernel.  Decided
        HERE, once at construction (``self._chunk_attn_impl``), under the
        same ``attention_impl`` values as the paged decode read, and handed
        to ``model.forward`` as a static argument.  Under "auto": the Pallas
        kernel that keeps a query tile's scores in VMEM and walks each
        tile's own key blocks (``pallas_attention.chunk_attention_pallas``)
        when the backend is a TPU, one device holds the model, the model
        has window layers beside global ones (``config.windowed``: the one
        stack whose chunks run ``model.blocked_attention``) and every
        forward the engine will build is inside the kernel's rule
        (:func:`pallas_attention.chunk_attention_ok`: a head of whole lane
        tiles, ``prefill_chunk`` whole query tiles, a scratch of whole key
        blocks: a scratch is a bucket, a multiple of ``prefill_chunk`` or
        ``max_seq_len`` itself); else ``blocked_attention``, the XLA
        reference and the CPU path (PERF.md section 6, PR 39: measured on
        the v5e).

        "pallas" / "pallas_interpret" waive the platform test alone; they
        NAME the decode read's kernel, so a chunk outside this rule is
        served by XLA and not refused (as ``_resolved_ssm_impl`` has it)."""
        impl = self.runtime.attention_impl
        c, rt = self.config, self.runtime
        if not c.windowed or impl == "xla" or (
            impl == "auto" and jax.devices()[0].platform != "tpu"
        ):
            return "xla"
        from calfkit_tpu.inference.pallas_attention import chunk_attention_ok

        in_rule = self.mesh.size == 1 and all(
            chunk_attention_ok(c.head_dim, rt.prefill_chunk, scratch, c.dtype)
            for scratch in (rt.prefill_chunk, rt.max_seq_len)
        )
        if not in_rule:
            return "xla"
        return "pallas" if impl == "auto" else impl

    def _resolved_ssm_impl(self) -> str:
        """Which implementation a recurrent layer's DECODE STEP uses for its
        pass over the per-slot state: the other computation that has a
        kernel.  Decided HERE, once at construction (``self._ssm_impl``),
        under the same ``attention_impl`` values as the paged decode read.
        Under "auto": the Pallas kernel that reads and writes the active
        rows' state once, in place, when the backend is a TPU, one device
        holds the model and the state is the kernel's; else the XLA body,
        the reference (PERF.md section 6: measured on the v5e).

        - Mamba-2 layers: ``pallas_ssm.ssm_step_pallas`` (PR 30: the update
          and ``y`` out of one pass) where :func:`pallas_ssm.ssm_step_in_place_ok`
          holds (float32, ``mamba_d_state`` whole lane tiles, ``mamba_d_head``
          whole sublane tiles); else the XLA body of ``mamba.mamba_step``.
        - Delta-rule layers (``config.gdn``: Gated DeltaNet and Kimi Delta
          Attention): ``pallas_gdn.delta_step_pallas`` (PR 42: the reduction
          ``S^T [k | q]`` and the update on one read of ``S``) where
          :func:`pallas_gdn.delta_step_in_place_ok` holds (float32, ``gdn_d_v``
          whole lane tiles, ``gdn_d_k`` whole sublane tiles); else
          ``gdn.delta_step_xla``.
        - Gated short convolutions (``config.shortconv``): XLA.  The state is
          the conv tail alone, two numbers a channel, and the step is a weight
          stream that XLA fuses: there is no matrix state for a kernel to pass
          over (``shortconv.shortconv_step``).

        "pallas" / "pallas_interpret" waive the platform test alone, as
        they do for the read; they NAME the attention kernel, so a state
        outside the rule is served by XLA and not refused."""
        impl = self.runtime.attention_impl
        c = self.config
        if not self._recurrent or c.shortconv or impl == "xla" or (
            impl == "auto" and jax.devices()[0].platform != "tpu"
        ):
            return "xla"
        if c.gdn:
            from calfkit_tpu.inference.pallas_gdn import delta_step_in_place_ok

            in_rule = delta_step_in_place_ok(
                c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v, c.state_dtype)
        else:
            from calfkit_tpu.inference.pallas_ssm import ssm_step_in_place_ok

            in_rule = ssm_step_in_place_ok(
                c.mamba_n_heads, c.mamba_n_groups, c.mamba_d_head, c.mamba_d_state,
                c.state_dtype,
            )
        if self.mesh.size != 1 or not in_rule:
            return "xla"
        return "pallas" if impl == "auto" else impl

    def _resolved_moe_step_impl(self) -> str:
        """Which implementation the ROUTED EXPERTS of a decode step use: the
        sixth computation that has a kernel.  Decided HERE, once at
        construction (``self._moe_step_impl``), under the same
        ``attention_impl`` values as the paged decode read, and handed to
        the decode step as ``ssm_impl`` is.  Under "auto": the Pallas kernel
        that reads the experts the step's real rows HIT, in place out of the
        stack (``pallas_moe.moe_step_pallas``), when the backend is a TPU,
        one device holds the model, the experts are held by SHARE
        (``config.expert_share``: a step's rows spread over every expert
        scored and hit only some of the held; experts held whole are hit
        whole, and the dense form's one stream of them all is the faster:
        PERF.md section 6, PR 53), the slots' rows are at most the kernel's
        (``moe._STEP_MAX_TOKENS``) and the matrices are whole lane tiles
        (:func:`pallas_moe.moe_step_ok`); else the form ``moe.dense_form``
        gives, the dense one being the reference.  Chunks keep that form
        under any value.

        "pallas" / "pallas_interpret" waive the platform test alone; they
        NAME the attention kernel, so experts outside the rule are served
        by XLA and not refused (as ``_resolved_ssm_impl`` has it)."""
        impl = self.runtime.attention_impl
        c = self.config
        if not (self._moe and c.expert_share) or impl == "xla" or (
            impl == "auto" and jax.devices()[0].platform != "tpu"
        ):
            return "xla"
        from calfkit_tpu.inference.pallas_moe import moe_step_ok

        in_rule = (
            self._paged and self.mesh.size == 1
            and self.runtime.max_batch_size <= _STEP_MAX_TOKENS
            and moe_step_ok(c.d_model, c.moe_d_ff, c.dtype)
        )
        if not in_rule:
            return "xla"
        return "pallas" if impl == "auto" else impl

    def _keep_program(self, cache: dict, family: str, key: tuple, fn: Any) -> "_Program":
        program = cache[key] = _Program(self, family, key, fn)
        return program

    def programs(self) -> "list[dict]":
        """The jit caches as a table, one row a key: the family, the key as
        the engine holds it (window or its pages, steps, sampled, chunk,
        rows, bucket ...), how many times JAX built under it (``builds``: 0,
        never called; more than 1, the key met arguments of another kind and
        was specialized again without any new key) and the seconds of those
        calls (trace + lower + compile, or a cache load), the program's
        number on the device's queue at its first call and at its newest
        build, and its uses.  ``GET /programs`` serves it; what built in a
        ramp-in is the row whose ``built_seq`` is high."""
        return [
            {"family": p.family, "key": list(p.key), "builds": p.builds, "build_s": p.build_s,
             "first_seq": p.first_seq, "built_seq": p.built_seq, "uses": p.uses}
            for cache in (self._decode_jits, self._prefill_jits)
            for p in list(cache.values())
        ]

    @property
    def proved_seq(self) -> int:
        """The number of the newest program a host sync has proved
        complete: what a request's ``engine.decode`` span records at its
        first and last token, to join the ``engine.dispatch`` spans between."""
        return self._done_seq

    def _wpages(self, window: int) -> int:
        """A decode context bucket of ``window`` tokens in pages of the pool
        the rows keep: of tokens, or (an EVA stack) of a summary a chunk."""
        if self.config.eva:
            window = self.config.summary_entries(window)
        return max(1, -(-window // self.runtime.page_size))

    def _window_bucket(self, needed: int) -> int:
        """Smallest configured window ≥ needed (cap max_seq): the decode
        attention scan only reads this prefix of the cache, and each bucket
        is one compile."""
        cap = self.runtime.max_seq_len
        for w in self.runtime.window_buckets:
            if needed <= w <= cap:
                return w
        return cap

    def _decode_jit(
        self, window: int, steps: int | None = None, sampled: bool = False
    ) -> Any:
        if self._paged:
            return self._decode_jit_paged(window, steps, sampled)
        steps = steps or self.runtime.decode_steps_per_dispatch
        fn = self._decode_jits.get((window, steps, sampled))
        if fn is not None:
            return fn
        fn = jax.jit(
            self._decode_fn_dense(window, steps, sampled),
            donate_argnums=(1, 2, 13) if self._recurrent else (1, 2),
        )
        return self._keep_program(self._decode_jits, "decode", (window, steps, sampled), fn)

    def _decode_fn_dense(self, window: int, steps: int, sampled: bool) -> Any:
        """The dense decode dispatch BODY (untraced): shared verbatim by
        the standalone decode jit and the fused ragged-wave jit, so the
        two compile the identical subgraph (ragged-on parity is structural,
        not coincidental)."""
        cfg = self.config
        ssm_impl = self._ssm_impl

        @jax.named_scope("decode_loop")
        def decode(params, k, v, last, lens, active, done_prev,
                   stop_table, hard_end, slot_keys, temp, top_k, top_p,
                   state=None):
            # ring-buffer decode: the main cache is READ-ONLY during the
            # scan; fresh K/V goes to a dense ring, consolidated once below.
            # The attention window is sliced ONCE per dispatch (a loop
            # constant), so per-step reads cover only live prefixes.
            # ``done_prev`` is the PREVIOUS dispatch's device-side done
            # mask: under overlapped execution this dispatch launches
            # before the host has seen the previous block, and a row that
            # retired there must be frozen here by pure device dataflow.
            active = active & jnp.logical_not(done_prev)
            B = last.shape[0]
            kw = k[:, :, :, :window]
            vw = v[:, :, :, :window]
            ring = M.cache_sides(cfg, (cfg.n_kv_layers, steps, B, cfg.cache_heads), k.dtype)

            def step(carry, t):
                ring, last, *st = carry
                logits, ring, *st = M.decode_step_ring(
                    params, cfg, last[:, None], (kw, vw), ring, t, lens,
                    **({"state": st[0], "active": active, "ssm_impl": ssm_impl}
                       if st else {}),
                )
                if sampled:
                    # per-(request, position) streams: deterministic for a
                    # given seed regardless of batch composition / slot reuse
                    # (+1: position ``lens`` itself was the prefill's draw)
                    subs = jax.vmap(jax.random.fold_in)(slot_keys, lens + t + 1)
                    nxt = sample_slots(logits[:, -1], subs, temp, top_k, top_p)
                else:
                    with jax.named_scope("sample"):
                        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, last)
                return (ring, nxt, *st), nxt

            (ring, last, *st), toks = lax.scan(
                step, (ring, last, *_some(state)), jnp.arange(steps)
            )
            k, v = M.consolidate_ring((k, v), ring, lens)
            new_lens = jnp.where(active, lens + steps, lens)
            # device-side retirement: classify the fresh block against each
            # row's stop table and hard bound, so the NEXT dispatch can
            # launch (consuming ``done``) before any host sync of this one
            n_valid, done = retire_mask_slots(
                toks.T, stop_table, hard_end - lens, active
            )
            return (k, v, last, new_lens, toks, n_valid, done, *st)  # toks [steps, B]

        return decode

    def _decode_jit_paged(
        self, window: int, steps: int | None, sampled: bool
    ) -> Any:
        """Decode dispatch reading/writing KV through the block tables."""
        steps = steps or self.runtime.decode_steps_per_dispatch
        wpages = self._wpages(window)
        fn = self._decode_jits.get((wpages, steps, sampled, "paged"))
        if fn is not None:
            return fn
        fn = jax.jit(
            self._decode_fn_paged(wpages, steps, sampled),
            donate_argnums=(1, 2, 14) if self._recurrent else (1, 2),
        )
        return self._keep_program(
            self._decode_jits, "decode", (wpages, steps, sampled, "paged"), fn)

    def _decode_fn_paged(self, wpages: int, steps: int, sampled: bool) -> Any:
        """The paged decode dispatch body (untraced) — see
        :meth:`_decode_fn_dense` for why the body builder is separate."""
        cfg = self.config
        attn_impl, ssm_impl, moe_step_impl = self._attn_impl, self._ssm_impl, self._moe_step_impl
        from calfkit_tpu.inference.pallas_attention import latent_rope_view

        @jax.named_scope("decode_loop")
        def decode(params, k, v, tables, last, lens, active, done_prev,
                   stop_table, hard_end, slot_keys, temp, top_k, top_p,
                   state=None, moe=None):
            # rows that retired in the still-in-flight previous dispatch
            # are frozen out here (and their consolidation writes routed
            # to the trash page) by the device-side done-mask chain
            active = active & jnp.logical_not(done_prev)
            B = last.shape[0]
            ring = M.cache_sides(
                cfg, (cfg.n_kv_layers, steps, B, cfg.cache_heads), _pool_dtype(k))
            pool = (k, v)
            if cfg.latent and attn_impl.startswith("pallas"):
                # the kernel's view of a latent pool's narrow rope side is a
                # relayout of that side: made HERE, once a dispatch (the pool
                # is a constant of the step loop and of the layer scan),
                # never per step.  K and V pairs are read as they are stored.
                pool = (k, latent_rope_view(v))

            def step(carry, t):
                ring, last, *st = carry
                logits, ring, *st = M.decode_step_ring_paged(
                    params, cfg, last[:, None], pool, tables, ring, t,
                    lens, wpages=wpages, attn_impl=attn_impl, active=active,
                    **_carried_kw(state, moe, st, ssm_impl, moe_step_impl),
                )
                if sampled:
                    subs = jax.vmap(jax.random.fold_in)(slot_keys, lens + t + 1)
                    nxt = sample_slots(logits[:, -1], subs, temp, top_k, top_p)
                else:
                    with jax.named_scope("sample"):
                        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, last)
                return (ring, nxt, *st), nxt

            (ring, last, *st), toks = lax.scan(
                step, (ring, last, *_some(state), *_some(moe)), jnp.arange(steps)
            )
            if cfg.eva:  # the ring's tokens, then the chunks they completed, pooled
                from calfkit_tpu.inference.eva import consolidate as eva_consolidate

                k2, v2 = eva_consolidate(params, cfg, (k, v), ring, tables, lens, active)
            else:
                k2, v2 = M.consolidate_ring_paged(
                    (k, v), ring, tables, lens, active, _layer_kinds(cfg)
                )
            new_lens = jnp.where(active, lens + steps, lens)
            n_valid, done = retire_mask_slots(
                toks.T, stop_table, hard_end - lens, active
            )
            return (k2, v2, last, new_lens, toks, n_valid, done, *st)

        return decode

    def _verify_jit(self, window: int, S: int, sampled: bool) -> Any:
        """The speculative VERIFY dispatch: feed [last, d_0..d_{S-2}] per
        row, score all S positions in one forward against the cache,
        accept a ragged per-row prefix (``sampler.spec_accept_slots``),
        consolidate the chunk's K/V, and advance each row's length by its
        own ``emitted`` — ragged acceptance needs no physical rollback
        because rejected slots land beyond the advanced length and the
        next wave's chunk overwrites them (the same garbage-beyond-length
        law the decode ring already relies on)."""
        if self._paged:
            return self._verify_jit_paged(window, S, sampled)
        key = ("verify", window, S, sampled)
        fn = self._decode_jits.get(key)
        if fn is not None:
            return fn
        cfg = self.config

        @jax.named_scope("verify")
        def verify(params, k, v, last, lens, active, drafts, ndraft,
                   stop_table, hard_end, slot_keys, temp, top_k, top_p):
            kw = k[:, :, :, :window]
            vw = v[:, :, :, :window]
            tokens = jnp.concatenate([last[:, None], drafts], axis=1)
            logits, ring = M.verify_step_ring(
                params, cfg, tokens, (kw, vw), lens
            )
            out_toks, emitted = spec_accept_slots(
                logits, drafts, ndraft, lens, slot_keys, temp, top_k,
                top_p, sampled=sampled,
            )
            emitted = jnp.where(active, emitted, 0)
            k, v = M.consolidate_ring((k, v), ring, lens)
            idx = jnp.clip(emitted - 1, 0, S - 1)
            new_last = jnp.where(
                active,
                jnp.take_along_axis(out_toks, idx[:, None], axis=1)[:, 0],
                last,
            )
            n_valid, done = retire_mask_slots(
                out_toks, stop_table, hard_end - lens, active,
                emitted=emitted,
            )
            return (
                k, v, new_last, lens + emitted, out_toks, emitted,
                n_valid, done,
            )

        fn = jax.jit(verify, donate_argnums=(1, 2))
        return self._keep_program(self._decode_jits, "verify", key, fn)

    def _verify_jit_paged(self, window: int, S: int, sampled: bool) -> Any:
        page = self.runtime.page_size
        wpages = -(-window // page)
        key = ("verify", wpages, S, sampled, "paged")
        fn = self._decode_jits.get(key)
        if fn is not None:
            return fn
        cfg = self.config

        @jax.named_scope("verify")
        def verify(params, k, v, tables, last, lens, active, drafts,
                   ndraft, stop_table, hard_end, slot_keys, temp, top_k,
                   top_p):
            tokens = jnp.concatenate([last[:, None], drafts], axis=1)
            logits, ring = M.verify_step_ring_paged(
                params, cfg, tokens, (k, v), tables, lens, wpages=wpages
            )
            out_toks, emitted = spec_accept_slots(
                logits, drafts, ndraft, lens, slot_keys, temp, top_k,
                top_p, sampled=sampled,
            )
            emitted = jnp.where(active, emitted, 0)
            # inactive rows write to the trash page; writes past a
            # row's reservation hit its table row's trash padding —
            # shared (prefix-cache) pages are never touched because the
            # chunk starts at lens >= prompt_len, past every registered
            # page (the same invariant plain decode relies on)
            k2, v2 = M.consolidate_ring_paged((k, v), ring, tables, lens, active)
            idx = jnp.clip(emitted - 1, 0, S - 1)
            new_last = jnp.where(
                active,
                jnp.take_along_axis(out_toks, idx[:, None], axis=1)[:, 0],
                last,
            )
            n_valid, done = retire_mask_slots(
                out_toks, stop_table, hard_end - lens, active,
                emitted=emitted,
            )
            return (
                k2, v2, new_last, lens + emitted, out_toks, emitted,
                n_valid, done,
            )

        fn = jax.jit(verify, donate_argnums=(1, 2))
        return self._keep_program(self._decode_jits, "verify", key, fn)

    def _short_steps(self) -> int:
        """Dispatch length while a waiting admission could actually unblock:
        a new request's time-to-prefill is bounded by one SHORT dispatch
        instead of a full one (TTFT lever; never longer than a full tick)."""
        steps = self.runtime.decode_steps_per_dispatch
        return min(steps, max(4, steps // 4))

    def _retirement_bound(self, request: GenRequest) -> int:
        """Decode steps until the request hits a hard stop bound."""
        remaining = request.max_new_tokens - request.generated
        seq_room = self.runtime.max_seq_len - 1 - (
            len(request.prompt) + request.generated
        )
        return min(remaining, seq_room)

    def _track_retirement(self, request: GenRequest) -> None:
        """Register an activated request's bound-retirement horizon.  A row
        activated with its wave's landing still riding a dispatch has its
        first token on the device yet (``generated`` 0): that token counts,
        so the horizon is the one ``_record_token`` will retire it at."""
        with self._retire_lock:
            entry = [
                self._decode_clock + self._retirement_bound(request)
                - (request.generated == 0),
                next(self._retire_seq),
                request,
            ]
            request.heap_entry = entry
            heapq.heappush(self._retire_heap, entry)

    def _untrack_retirement(self, request: GenRequest) -> None:
        """Drop the heap's reference to a retired request NOW (the entry
        itself pops lazily): a retired request must not stay pinned —
        prompt list, token queue and all — until its original bound
        surfaces at the heap top (r3 advisor finding).  Compacts the heap
        once nulled entries outnumber live ones, so sustained early
        retirement (stop tokens, cancels) keeps the heap O(active)."""
        entry = request.heap_entry
        if entry is None:
            return
        request.heap_entry = None
        with self._retire_lock:
            entry[2] = None
            self._retire_stale += 1
            if self._retire_stale * 2 > len(self._retire_heap):
                self._retire_heap = [
                    e for e in self._retire_heap if e[2] is not None
                ]
                heapq.heapify(self._retire_heap)
                self._retire_stale = 0

    def _retirement_near(self, horizon: int) -> bool:
        """Will any active request hit a stop bound within ``horizon`` steps?
        (Shortening ticks while nothing can retire just multiplies dispatch
        overhead — slots only free on retirement.)  O(log n) amortized: the
        heap top is the earliest bound; entries nulled by early retirement
        (stop token / cancel) pop lazily here.  A nulled entry[2] is THE
        staleness marker — every retirement path for a tracked request
        runs _untrack_retirement, so no other invariant is needed."""
        with self._retire_lock:
            heap = self._retire_heap
            while heap and heap[0][2] is None:
                heapq.heappop(heap)
                self._retire_stale = max(0, self._retire_stale - 1)
            return bool(heap) and heap[0][0] <= self._decode_clock + horizon

    def _prefill_jit(self, bucket: int, rows: int, sampled: bool = False) -> Any:
        """Batched prefill: R admissions run as one [R, bucket] forward on a
        scratch cache, then scatter into the slot rows (dense) or the
        reserved pages (paged) — one dispatch per admission WAVE, not per
        request.  The wave's per-slot sampling state (keys/temp/top_k/top_p)
        and, when paged, the block-table rows are scattered in the same
        dispatch."""
        paged = self._paged
        fn = self._prefill_jits.get((bucket, rows, sampled))
        if fn is not None:
            return fn
        cfg, chunk_attn_impl = self.config, self._chunk_attn_impl

        def prefill(
            params, k, v, last, lens, tokens, slots, true_lens,
            slot_keys, temp, top_k, top_p,  # [B] engine state
            seeds, w_temp, w_top_k, w_top_p,  # [R] wave values
            tables=None, page_rows=None, scatter_ids=None,  # paged only
            state=None,  # models with recurrent layers only
            moe=None,  # models with routed experts only: zeroed counters
        ):
            # tokens: [R, bucket]; slots/true_lens: [R]
            R, P = tokens.shape
            scratch = M.cache_sides(cfg, (cfg.n_kv_layers, R, cfg.cache_heads, P), _pool_dtype(k))
            pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (R, P))
            with jax.named_scope("prefill"):
                logits, (sk, sv), *wstate = M.forward(
                    params, cfg, tokens, pos, scratch,
                    jnp.full((R,), P, jnp.int32),
                    **({} if state is None else {"state": make_recurrent_state(cfg, R)}),
                    **({} if moe is None else {"moe": moe}),
                    **({} if state is None and moe is None else {"n_valid": true_lens}),
                    chunk_attn_impl=chunk_attn_impl,
                )
            if moe is not None:  # the wave's expert counters leave last
                moe = wstate.pop()
            idx = jnp.clip(true_lens - 1, 0, P - 1)
            last_logits = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1
            )[:, 0]
            return (*_finalize_wave_math(
                cfg, paged, sampled,
                k, v, sk, sv, last, lens, slots, true_lens, last_logits,
                slot_keys, temp, top_k, top_p,
                seeds, w_temp, w_top_k, w_top_p,
                tables, page_rows, scatter_ids,
                state, *wstate,
            ), *_some(moe))

        fn = jax.jit(prefill, donate_argnums=(1, 2, 3, 4), donate_argnames=("state",))
        return self._keep_program(self._prefill_jits, "prefill", (bucket, rows, sampled), fn)

    # ------------------------------------------------- chunked prefill jits
    def _chunk_jit(self, chunk: int, rows: int) -> Any:
        """One prefill CHUNK: forward [R, chunk] at a data offset into the
        wave's scratch cache.  One compile per (chunk, R) regardless of how
        long prompts get — the offset is data."""
        fn = self._prefill_jits.get(("chunk", chunk, rows))
        if fn is not None:
            return fn
        fn = jax.jit(
            self._chunk_fn(chunk),
            donate_argnums=(1, 2, 5) if self._recurrent else (1, 2),
        )
        return self._keep_program(self._prefill_jits, "chunk", ("chunk", chunk, rows), fn)

    def _chunk_fn(self, chunk: int) -> Any:
        """The prefill-chunk body (untraced): shared verbatim by the
        standalone chunk jit and the fused ragged-wave jit (same
        structural-parity argument as :meth:`_decode_fn_dense`).  The
        chunk is a RAGGED row kind — q_len=chunk queries at data offset
        ``start`` against a scratch holding the chunk itself (the
        per-row positions/lens ARE the (kind, start, q_len, kv_len)
        descriptor, serialized as arrays)."""
        cfg, chunk_attn_impl = self.config, self._chunk_attn_impl

        @jax.named_scope("chunk_loop")
        def chunk_step(params, sk, sv, tokens_chunk, offset,
                       wstate=None, true_lens=None, wmoe=None):
            R = tokens_chunk.shape[0]
            pos = offset + jnp.broadcast_to(
                jnp.arange(chunk, dtype=jnp.int32), (R, chunk)
            )
            lens = jnp.full((R,), offset + chunk, jnp.int32)
            # the wave's recurrent state rides from chunk to chunk beside
            # its KV scratch; a row's padding (past its true length) must
            # not move it, so the mixer is told how much of the chunk is
            # the row's own
            # (a model with routed experts: the wave's expert counters ride
            # the same way, and count the row's own positions alone)
            logits, (sk, sv), *wstate = M.forward(
                params, cfg, tokens_chunk, pos, (sk, sv), lens,
                **({} if wstate is None else {"state": wstate}),
                **({} if wmoe is None else {"moe": wmoe}),
                **({} if wstate is None and wmoe is None else {
                    "n_valid": jnp.clip(true_lens - offset, 0, chunk)}),
                chunk_attn_impl=chunk_attn_impl,
            )
            return (sk, sv, logits, *wstate)  # logits [R, chunk, V]

        return chunk_step

    def _ragged_jit(
        self, window: int, steps: int, sampled: bool, chunk: int, rows: int
    ) -> Any:
        """THE unified prefill+decode wave dispatch (ISSUE 6): one jitted
        invocation that advances the active decode rows by ``steps``
        tokens AND the inflight admission wave by one prefill chunk —
        the ragged batch of arXiv:2604.15464's design, expressed as one
        XLA program (one launch, one retirement-mask chain, one host
        sync) instead of the bifurcated admission-dispatch + decode-
        dispatch pair.  Both halves trace the SAME body builders as their
        standalone jits, so ragged-on output is structurally identical to
        ragged-off."""
        wkey = self._wpages(window) if self._paged else window
        key = ("ragged", wkey, steps, sampled, chunk, rows)
        fn = self._decode_jits.get(key)
        if fn is not None:
            return fn
        chunk_fn = self._chunk_fn(chunk)
        if self._paged:
            decode_fn = self._decode_fn_paged(wkey, steps, sampled)

            def ragged_paged(
                params, k, v, tables, last, lens, active, done_prev,
                stop_table, hard_end, slot_keys, temp, top_k, top_p,
                sk, sv, tokens_chunk, offset,
                state=None, wstate=None, true_lens=None, moe=None, wmoe=None,
            ):
                # out: (.., [state], [moe]); wave: (sk, sv, logits, [wstate], [wmoe])
                wave = chunk_fn(params, sk, sv, tokens_chunk, offset, wstate, true_lens, wmoe)
                out = decode_fn(
                    params, k, v, tables, last, lens, active, done_prev,
                    stop_table, hard_end, slot_keys, temp, top_k, top_p,
                    state, moe,
                )
                return (*out, *wave)

            fn = jax.jit(
                ragged_paged,
                donate_argnums=(1, 2, 14, 15, 18, 19) if self._recurrent
                else (1, 2, 14, 15),
            )
        else:
            decode_fn = self._decode_fn_dense(window, steps, sampled)

            def ragged_dense(
                params, k, v, last, lens, active, done_prev,
                stop_table, hard_end, slot_keys, temp, top_k, top_p,
                sk, sv, tokens_chunk, offset,
                state=None, wstate=None, true_lens=None,
            ):
                wave = chunk_fn(params, sk, sv, tokens_chunk, offset, wstate, true_lens)
                out = decode_fn(
                    params, k, v, last, lens, active, done_prev,
                    stop_table, hard_end, slot_keys, temp, top_k, top_p,
                    state,
                )
                return (*out, *wave)

            fn = jax.jit(
                ragged_dense,
                donate_argnums=(1, 2, 13, 14, 17, 18) if self._recurrent
                else (1, 2, 13, 14),
            )
        return self._keep_program(self._decode_jits, "ragged", key, fn)

    def _seed_scratch_jit(self, bucket: int, n_pages: int, rows: int) -> Any:
        """Fresh chunk-lane scratch with every row's first ``n_pages``
        pages gathered from the paged pool (prefix-cache reuse; ids is
        [rows, n_pages]).  One compile per (bucket, n_pages, rows) —
        reuse lengths are page-aligned, so the variant count is bounded
        by bucket/page times the power-of-two wave widths."""
        key = ("seed", bucket, n_pages, rows)
        fn = self._prefill_jits.get(key)
        if fn is not None:
            return fn
        cfg = self.config
        page = self.runtime.page_size

        @jax.named_scope("seed_scratch")
        def seed(pool_k, pool_v, ids):
            def gather(pool_side):
                # the pages as stored, f positions a row: taken apart in the
                # gathered rows (a row-major reshape of the result)
                g = pool_side[:, ids]  # [L, R, n, K, page / f, f * hd]
                L, R, n, K = g.shape[:4]
                return g.transpose(0, 1, 3, 2, 4, 5).reshape(
                    L, R, K, n * page, -1
                )

            sk, sv = M.cache_sides(
                cfg, (cfg.n_kv_layers, rows, cfg.cache_heads, bucket), pool_k.dtype)
            sk = sk.at[:, :, :, : n_pages * page].set(gather(pool_k))
            sv = sv.at[:, :, :, : n_pages * page].set(gather(pool_v))
            return sk, sv

        return self._keep_program(self._prefill_jits, "seed", key, jax.jit(seed))

    def _finalize_jit(self, bucket: int, rows: int, sampled: bool) -> Any:
        """The chunked wave's landing: scatter the finished scratch into the
        cache (rows or pages), install sampling state, sample first tokens
        from the LAST chunk's logits (same-bucket admission ⇒ every row's
        final position lives in the final chunk)."""
        fn = self._prefill_jits.get(("final", bucket, rows, sampled))
        if fn is not None:
            return fn
        cfg = self.config
        paged = self._paged
        chunk = min(self.runtime.prefill_chunk, bucket)

        def finalize(
            k, v, sk, sv, last, lens, slots, true_lens, last_chunk_logits,
            slot_keys, temp, top_k, top_p,
            seeds, w_temp, w_top_k, w_top_p,
            tables=None, page_rows=None, scatter_ids=None,
            state=None, wstate=None,
        ):
            # logits index local to the final chunk
            idx = jnp.clip(true_lens - 1 - (bucket - chunk), 0, chunk - 1)
            last_logits = jnp.take_along_axis(
                last_chunk_logits, idx[:, None, None], axis=1
            )[:, 0]
            return _finalize_wave_math(
                cfg, paged, sampled,
                k, v, sk, sv, last, lens, slots, true_lens, last_logits,
                slot_keys, temp, top_k, top_p,
                seeds, w_temp, w_top_k, w_top_p,
                tables, page_rows, scatter_ids,
                state, wstate,
            )

        # donate the cache (k/v alias their outputs); sk/sv have NO
        # same-shaped output to alias into, so donating them only emits
        # "donated buffers were not usable" warnings — peak HBM at landing
        # (cache + scratch) already equals the chunk-step peak either way
        fn = jax.jit(finalize, donate_argnums=(0, 1, 4, 5), donate_argnames=("state",))
        return self._keep_program(
            self._prefill_jits, "finalize", ("final", bucket, rows, sampled), fn)

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._loop = asyncio.get_running_loop()
        # SIGUSR2 dumps every live journal (best-effort: non-main-thread
        # or signal-less platforms simply skip; recording still works)
        flightrec.install_sigusr2()
        self._task = self._loop.create_task(self._serve(), name="inference-engine")
        if self.runtime.watchdog_stall_s > 0:
            self._progress_at = cancellation.wall_clock()
            self._watchdog_task = self._loop.create_task(
                self._watchdog(), name="inference-engine-watchdog"
            )

    async def stop(self) -> None:
        self._running = False
        self._wake.set()
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            try:
                await self._watchdog_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._watchdog_task = None
        if self._task is not None:
            try:
                await asyncio.wait_for(self._task, timeout=30)
            except asyncio.TimeoutError:
                self._task.cancel()
            self._task = None
        self._finish_all()
        # a stopped engine must not pin a stale count in the process gauge
        _drop_engine_active(id(self))

    def _finish_all(self) -> None:
        """Terminate every waiter: active slots AND still-queued requests
        (a queued request left without _DONE hangs its generate() forever)."""
        if self._pend is not None:
            # abandon the in-flight dispatch (and a wave's landing riding
            # it: its rows are active, and end below); its deferred frees
            # must still run or the slots/pages leak into the next start()
            self._free_deferred(self._pend)
            self._pend = None
        for request in list(self._active.values()):
            request.out.put_nowait(_DONE)
        self._active.clear()
        for request in self._carry:
            request.out.put_nowait(_DONE)
        self._carry.clear()
        if self._inflight is not None:
            for request in self._inflight["wave"]:
                request.out.put_nowait(_DONE)
            self._inflight = None
        while self._pending:
            self._pending.popleft().out.put_nowait(_DONE)
        if self._long is not None:
            self._long["request"].out.put_nowait(_DONE)
            self._long = None
        if self._long_inflight is not None:
            self._long_inflight["request"].out.put_nowait(_DONE)
            self._long_inflight = None
        while self._long_pending:
            self._long_pending.popleft().out.put_nowait(_DONE)

    # -------------------------------------------------------------- submit
    async def generate(
        self,
        prompt: list[int],
        *,
        max_new_tokens: int = 256,
        stop_tokens: frozenset[int] = frozenset(),
        sampling: SamplingParams | None = None,
        seed: int | None = None,
        corr: str | None = None,
        run: str | None = None,
        deadline: float | None = None,
        lease: "tuple[str, float] | None" = None,
        priority: "str | None" = None,
        trace: "TraceContext | None" = None,
        account: "StreamAccount | None" = None,
    ) -> AsyncIterator[int]:
        """Submit a prompt; yields generated token ids as they decode.

        ``sampling``/``seed`` override the engine defaults for this request
        only — requests with different settings share decode dispatches
        (row-wise sampling state).  Abandoning the iterator cancels the
        request: its slot is reclaimed at the next scheduler tick.
        ``corr`` tags the request's flight-recorder events with its
        trace/correlation id (``ck timeline``'s join key).  ``run`` is
        the logical run id (x-mesh-run), when present — the capacity
        ledger attributes the request's HBM pages to it (ISSUE 19).

        ``deadline`` is the request's ABSOLUTE wall-clock deadline (epoch
        seconds on :func:`calfkit_tpu.cancellation.wall_clock`): an
        already-expired submit raises :class:`DeadlineExceededError`
        immediately, and a queued or active request whose deadline passes
        is reaped through the cancellation path (the stream then raises
        the same typed error).  With ``RuntimeConfig.max_pending`` set, a
        submit that finds its lane's queue full is SHED with a typed
        :class:`EngineOverloadedError` — O(1), before any device work.

        ``lease`` is the CALLER's liveness lease ``(lease_id, ttl_s)``
        (ISSUE 10): the run registers against it, and the orphan reaper
        abandons it — queued or active, slot/pages/prefix refs freed
        through the ordinary retirement path — once the caller's
        heartbeats lapse past the TTL (typed :class:`RunOrphanedError`
        on the stream).  A lease already lapsed at submit is refused
        before any device work, like an expired deadline.

        ``priority`` is the caller's QoS class (ISSUE 20):
        ``"interactive"`` | ``"batch"``; anything else (including None)
        resolves to the mesh default.  Under overload batch-class work
        degrades FIRST: an interactive submit at a full lane evicts a
        queued batch request (oldest lease beat first) instead of being
        shed, and the deadline/orphan reapers take batch before
        interactive at equal expiry.

        ``trace`` is the caller's span context (ISSUE 24): with it the
        engine records an ``engine.queue`` span, child of that context,
        from this submit to the moment the request is granted its slot
        (attrs ``blocked_on``, ``bucket``, ``wave_rows``).

        ``account`` is the caller's :class:`StreamAccount`: the consumer's
        side of the stream books each block it takes there (its dispatch's
        landing, the wait since, the time suspended for it), for the caller
        to end its own span with.  The ``stream_*`` totals count either way.
        """
        req_priority = qos.resolve_priority(priority)
        if not self._running:
            raise InferenceError("engine not started")
        if self._wedged:
            # fast typed rejection while wedged: admitting work behind a
            # hung device grant would only grow the pile the watchdog
            # just faulted — callers should be failing over
            self.stats.watchdog_faulted += 1
            raise EngineWedgedError(
                "engine is wedged (no dispatch progress for "
                f"{self.runtime.watchdog_stall_s:.1f}s with work pending); "
                "retry against another replica",
                stalled_s=self.runtime.watchdog_stall_s,
            )
        if deadline is not None:
            overdue = cancellation.wall_clock() - deadline
            if overdue >= 0:
                # expired on arrival: record the fault fast — admitting it
                # would burn prefill + decode dispatches for a dead caller
                self.stats.expired_requests += 1
                self._count_expired_class(req_priority)
                self._journal.append(
                    flightrec.EV_EXPIRE, corr, -1, int(overdue * 1000)
                )
                raise DeadlineExceededError(
                    f"request expired {overdue:.3f}s before admission"
                )
        if lease is not None and leases.lease_lapsed(lease[0]):
            # orphaned on arrival: the caller was already gone when this
            # submit reached the engine — admitting it would burn a full
            # prefill+decode for nobody (the EXPIRE-at-submit twin)
            self.stats.orphaned_requests += 1
            self._journal.append(flightrec.EV_ORPHAN, corr, -1, 0)
            raise RunOrphanedError(
                "caller lease lapsed before admission",
                lease_id=lease[0],
            )
        long_lane = len(prompt) >= self.runtime.max_seq_len
        if long_lane and not self.runtime.long_context:
            raise InferenceError(
                f"prompt of {len(prompt)} tokens exceeds max_seq_len "
                f"{self.runtime.max_seq_len} "
                "(enable RuntimeConfig(long_context=True) to serve it via "
                "the sequence-parallel lane)"
            )
        if long_lane and len(prompt) > self._long_max_prompt():
            raise InferenceError(
                f"prompt of {len(prompt)} tokens exceeds long_max_prompt "
                f"{self._long_max_prompt()}"
            )
        request = GenRequest(
            prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            stop_tokens=stop_tokens,
            sampling=sampling,
            seed=seed,
            corr=corr,
            run=run,
            deadline=deadline,
            priority=req_priority,
            account=account,
        )
        if lease is not None:
            request.lease_id, request.lease_ttl = lease
        self._journal.append(
            flightrec.EV_SUBMIT, corr, -1, len(request.prompt), max_new_tokens
        )
        if self._drafter is not None and not long_lane:
            # drafters read prompt + emitted history (the long lane decodes
            # through its own sp dispatch and never speculates)
            request.history = list(prompt)
        if long_lane:
            if max_new_tokens > self.runtime.long_new_cap:
                # the carried fresh cache is statically sized by the cap,
                # so the budget CANNOT be honored — fault by default (the
                # caller's token budget is a contract; silently shrinking
                # it corrupted downstream accounting) unless the caller
                # explicitly negotiated clamping via the config flag
                if not self.runtime.long_clamp_new_tokens:
                    raise InferenceError(
                        f"long-context request asked for {max_new_tokens} "
                        f"new tokens but long_new_cap is "
                        f"{self.runtime.long_new_cap}; lower "
                        f"max_new_tokens, raise RuntimeConfig.long_new_cap, "
                        "or opt in to clamping with "
                        "RuntimeConfig(long_clamp_new_tokens=True)"
                    )
                request.max_new_tokens = self.runtime.long_new_cap
                logger.warning(
                    "long request clamped to long_new_cap=%d new tokens "
                    "(long_clamp_new_tokens=True)",
                    self.runtime.long_new_cap,
                )
            if not self._effective_sampling(request).is_greedy:
                # covers a non-greedy ENGINE default too, not just
                # per-request settings
                logger.warning(
                    "long-context lane decodes greedily; sampling settings "
                    "are ignored for this request"
                )
            self._shed_if_full("long", len(self._long_pending), request)
            queue_span = self._open_queue_span(request, trace)
            self._long_pending.append(request)
            self._submit_deadline(request)
            self._submit_lease(request)
            self._wake.set()
            inner = self._consume(request, queue_span)
            try:
                async for item in inner:
                    yield item
            finally:
                await inner.aclose()
            return
        if (
            self.runtime.overlap_dispatch or self._spec is not None
        ) and len(stop_tokens) > self.runtime.max_stop_tokens:
            # device-side retirement scans a fixed-shape per-slot stop
            # table; silently truncating the set would MISS stops — fault
            raise InferenceError(
                f"request has {len(stop_tokens)} stop tokens but device-side"
                f" retirement caps the per-slot table at max_stop_tokens="
                f"{self.runtime.max_stop_tokens}; raise "
                "RuntimeConfig.max_stop_tokens (or set "
                "overlap_dispatch=False with speculation off for the "
                "host-side lockstep path)"
            )
        if self._paged:
            # reject what the pool could NEVER serve — re-queueing it would
            # wait (and starve everything behind it) forever
            reserve = self._reserve_pages(request, self._bucket_of(len(prompt)))
            usable = self._page_alloc.num_pages - 1
            if not self._page_alloc.fits(reserve):  # (pools by kind: pool by pool)
                raise InferenceError(
                    f"request needs {reserve} KV pages but the pool only has "
                    f"{usable}; lower max_new_tokens or raise num_kv_pages"
                )
        # the short-lane count includes _admitting (requests parked in the
        # chunked-admission window): they hold queue slots and page
        # reservations exactly like _pending entries, and excluding them
        # let a wave-heavy engine under-report pending in its shed replies
        self._shed_if_full(
            "short",
            len(self._pending) + len(self._carry) + len(self._admitting),
            request,
        )
        queue_span = self._open_queue_span(request, trace)
        self._pending.append(request)
        self._submit_deadline(request)
        self._submit_lease(request)
        self._wake.set()
        inner = self._consume(request, queue_span)
        try:
            async for item in inner:
                yield item
        finally:
            # aclose() on OUR iterator must cancel NOW, not whenever the
            # asyncgen finalizer gets around to collecting the inner one
            await inner.aclose()

    # ------------------------------------------------- overload protection
    def _count_shed_class(self, priority: str) -> None:
        if qos.class_rank(priority):
            self.stats.batch_shed += 1
        else:
            self.stats.interactive_shed += 1

    def _count_expired_class(self, priority: str) -> None:
        if qos.class_rank(priority):
            self.stats.batch_expired += 1
        else:
            self.stats.interactive_expired += 1

    @hotpath
    def _shed_victim(self, lane: str) -> "GenRequest | None":
        """Priority-ordered shed selection (ISSUE 20): the QUEUED
        batch-class request to evict so an arriving interactive request
        can take its place at a full lane.  Lease-aware ordering: among
        batch candidates, the one whose caller lease has the OLDEST
        beat sheds first — a leased-but-silent caller is the weakest
        claim on the queue, an actively-beating one the strongest.
        Un-leased (or never-beaten) requests read age 0.0 = most alive,
        so they shed last among batch.  Only queued entries are
        candidates — evicting an ACTIVE slot would discard paid prefill
        work.  None = no batch request queued (the incoming request
        sheds instead, whatever its class)."""
        queued = (
            self._long_pending
            if lane == "long"
            else (*self._carry, *self._pending, *self._admitting)
        )
        victim: "GenRequest | None" = None
        victim_age = -1.0
        for r in queued:
            if r.cancelled or not qos.class_rank(r.priority):
                continue
            age = leases.lease_age(r.lease_id)
            age = 0.0 if age is None else age
            if age > victim_age:
                victim, victim_age = r, age
        return victim

    def _shed_queued(
        self, victim: GenRequest, lane: str, pending: int, limit: int
    ) -> None:
        """Evict one queued batch request through the ordinary
        cancellation path: the reap frees its place, the consumer's
        _raise_terminal surfaces the same typed retriable
        EngineOverloadedError (with the same lane/pending/limit detail)
        a shed-at-submit would have."""
        victim.shed = True
        victim.shed_detail = (lane, pending, limit)
        victim.cancelled = True
        self._cancel_dirty = True
        self.stats.shed_requests += 1
        self._count_shed_class(victim.priority)
        self._journal.append(
            flightrec.EV_SHED, victim.corr, -1, pending, limit
        )
        self._wake.set()

    def _shed_if_full(
        self, lane: str, pending: int, request: GenRequest
    ) -> None:
        """Bounded admission (ISSUE 5), priority-ordered (ISSUE 20):
        when the lane's queue is at ``max_pending``, batch-class work
        sheds FIRST — an interactive submit evicts a queued batch
        request (oldest lease beat first) and is admitted in its place;
        only when no batch request is sheddable is the incoming request
        itself refused with a typed, retriable error.  Still O(queued)
        at worst and only on the full-lane path — the un-loaded submit
        stays the ISSUE 5 O(1) check — and the gate law holds
        structurally: an interactive request is never shed while any
        batch request is sheddable."""
        limit = self.runtime.max_pending
        if not limit or pending < limit:
            return
        if not qos.class_rank(request.priority):
            victim = self._shed_victim(lane)
            if victim is not None:
                self._shed_queued(victim, lane, pending, limit)
                return  # admitted in the victim's place
        self.stats.shed_requests += 1
        self._count_shed_class(request.priority)
        self._journal.append(
            flightrec.EV_SHED, request.corr, -1, pending, limit
        )
        raise EngineOverloadedError(
            f"{lane} lane has {pending} queued requests (max_pending="
            f"{limit}); retry with backoff or add capacity",
            lane=lane, pending=pending, limit=limit,
        )

    @hotpath
    def _reap_order(self, request: GenRequest, seq: int) -> "tuple[int, int]":
        """Class-weighted reap tiebreak (ISSUE 20): the heap-entry key
        between expiry and the request.  At EQUAL expiry (common under
        the sim's quantized clock, and whenever a storm's arrivals share
        a deadline) the batch-class entry sorts FIRST, so both reapers
        take batch before interactive — degradation stays ordered even
        at the reap.  Expiry itself is untouched: class never reaps a
        request before its actual deadline/lapse."""
        return (1 - qos.class_rank(request.priority), seq)

    def _submit_deadline(self, request: GenRequest) -> None:
        """Register a deadlined request for the scheduler's expiry reap."""
        if request.deadline is None:
            return
        entry = [
            request.deadline,
            self._reap_order(request, next(self._deadline_seq)),
            request,
        ]
        request.deadline_entry = entry
        heapq.heappush(self._deadline_heap, entry)

    def _drop_deadline(self, request: GenRequest) -> None:
        """A finished request must not linger in the deadline heap until
        its deadline lazily pops: null the entry's request slot so the
        heap holds no strong reference to the dead prompt/history."""
        entry = request.deadline_entry
        if entry is not None:
            entry[2] = None
            request.deadline_entry = None

    def _request_live(self, request: GenRequest) -> bool:
        """Is this request still queued or holding engine resources?
        (Identity scan — only runs when a deadline actually expired.)"""
        if request.slot != -1:
            return True
        if self._long is not None and self._long["request"] is request:
            return True
        if (
            self._long_inflight is not None
            and self._long_inflight["request"] is request
        ):
            return True
        return any(
            r is request
            for r in (
                *self._carry, *self._pending, *self._long_pending,
                *self._admitting,
            )
        )

    @hotpath
    def _check_deadlines(self) -> None:
        """Reap queued AND active requests whose deadline passed, through
        the ordinary cancellation path (so overlap's one-dispatch-late
        retirement semantics hold unchanged).  O(1) per scheduler pass
        when nothing expired: one heap peek."""
        heap = self._deadline_heap
        if not heap:
            return
        now = cancellation.wall_clock()
        if heap[0][0] > now:
            return
        while heap and heap[0][0] <= now:
            _, _, request = heapq.heappop(heap)
            if (
                request is None  # finished: _drop_deadline nulled the entry
                or request.cancelled
                or not self._request_live(request)
            ):
                continue  # finished or already being reaped: lazy entry
            request.expired = True
            request.cancelled = True
            self._cancel_dirty = True
            self.stats.expired_requests += 1
            self._count_expired_class(request.priority)
            self._journal.append(
                flightrec.EV_EXPIRE, request.corr, request.slot,
                int((now - request.deadline) * 1000),
            )

    # ------------------------------------------------- orphan reaper
    # (ISSUE 10) The server-side half of failure recovery: a run whose
    # CALLER's liveness lease lapsed is abandoned through the ordinary
    # cancellation path — same reap, same one-dispatch-late retirement,
    # same slot/page/prefix accounting — with a typed, NON-retriable
    # ``mesh.orphaned`` terminal.  Precedence law (shared with
    # _raise_terminal; pinned in tests): wedged > expired > orphaned >
    # shed > stalled > plain cancel — exactly ONE typed error per run, checked
    # in the same order on both schedulers (ragged and bifurcated reap
    # through the same _reap_cancelled/_consume pair).

    @hotpath
    def _submit_lease(self, request: GenRequest) -> None:
        """Register a leased request for the orphan sweep (heap-shaped
        like _submit_deadline; un-leased requests cost nothing)."""
        if request.lease_id is None:
            return
        expiry = leases.lease_expiry(request.lease_id)
        if expiry is None:
            # never-beaten lease: grant a full TTL from now (the submit
            # itself is proof of life — the kernel stamps admission, but
            # direct engine callers may not)
            expiry = cancellation.wall_clock() + request.lease_ttl
        entry = [
            expiry, self._reap_order(request, next(self._lease_seq)), request,
        ]
        request.lease_entry = entry
        heapq.heappush(self._lease_heap, entry)

    @hotpath
    def _drop_lease(self, request: GenRequest) -> None:
        """Null a finished request's lease entry (the heap entry itself
        pops lazily) — mirrors _drop_deadline's memory law."""
        entry = request.lease_entry
        if entry is not None:
            entry[2] = None
            request.lease_entry = None

    @hotpath
    def _check_orphans(self) -> None:
        """Reap queued AND active runs whose caller lease lapsed.  O(1)
        per scheduler pass when no registered expiry has arrived: one
        heap peek.  A popped entry whose lease was refreshed by a newer
        beat is re-pushed at the new expiry — heartbeats keep a live
        caller's runs off the reap for one push per TTL, not per pass."""
        heap = self._lease_heap
        if not heap:
            return
        now = cancellation.wall_clock()
        gen = leases.release_generation()
        if gen != self._lease_release_gen:
            # a lease was RELEASED somewhere (clean caller close): its
            # runs must orphan NOW, ahead of their registered expiry —
            # one O(registered) sweep per release event, not per pass
            self._lease_release_gen = gen
            for entry in heap:
                request = entry[2]
                if (
                    request is not None
                    and not request.cancelled
                    and leases.lease_lapsed(request.lease_id, now)
                ):
                    entry[0] = now  # surfaces in the pop loop below
            heapq.heapify(heap)
        if heap[0][0] > now:
            return
        while heap and heap[0][0] <= now:
            entry = heapq.heappop(heap)
            request = entry[2]
            if (
                request is None  # finished: _drop_lease nulled the entry
                or request.cancelled
                or not self._request_live(request)
            ):
                continue
            expiry = leases.lease_expiry(request.lease_id)
            if expiry is None:
                expiry = entry[0] + request.lease_ttl
            if expiry > now:
                # the caller beat since registration: re-arm at the
                # fresh expiry and keep serving
                fresh = [
                    expiry,
                    self._reap_order(request, next(self._lease_seq)),
                    request,
                ]
                request.lease_entry = fresh
                heapq.heappush(heap, fresh)
                continue
            request.orphaned = True
            request.cancelled = True
            self._cancel_dirty = True
            self.stats.orphaned_requests += 1
            # clamp: a RELEASED lease reads expiry -inf (lapsed forever)
            self._journal.append(
                flightrec.EV_ORPHAN, request.corr, request.slot,
                int(min(now - expiry, 86400.0) * 1000),
            )

    def _check_stalls(self) -> None:
        """Bound per-request token delivery: a consumer that stopped
        draining its stream (``max_out_blocks`` undrained queue items)
        is stall-cancelled through the ordinary cancellation path — its
        accumulated blocks free with the request instead of growing
        forever."""
        bound = self.runtime.max_out_blocks
        if not bound:
            return
        stalled = [
            r for r in self._active.values()
            if not r.cancelled and r.out.qsize() > bound
        ]
        if self._long is not None:
            r = self._long["request"]
            if not r.cancelled and r.out.qsize() > bound:
                stalled.append(r)
        for request in stalled:
            request.stalled = True
            request.cancelled = True
            self._cancel_dirty = True
            self.stats.delivery_stalled += 1
            self._journal.append(
                flightrec.EV_CANCEL, request.corr, request.slot,
                request.out.qsize(),
            )

    # ------------------------------------------------- wedge watchdog
    def _note_progress(self) -> None:
        """A dispatch/wave actually LANDED (device produced output and the
        host observed it) — the watchdog's progress signal.  Called from
        the decode thread and the serve loop; a bare float store, so no
        lock.  Reads the wall_clock seam: the chaos virtual clock drives
        wedge detection deterministically."""
        self._progress_at = cancellation.wall_clock()

    def _watchdog_requests(self) -> "list[GenRequest]":
        """Every request the engine currently owes an outcome: active
        slots, queued lanes, mid-admission prefills, the inflight chunked
        wave, and the long lane."""
        out: list[GenRequest] = [
            *self._active.values(), *self._carry, *self._pending,
            *self._admitting, *self._long_pending,
        ]
        if self._inflight is not None:
            out.extend(self._inflight["wave"])
        if self._long is not None:
            out.append(self._long["request"])
        if self._long_inflight is not None:
            out.append(self._long_inflight["request"])
        return out

    def _work_pending(self) -> bool:
        return bool(
            self._active or self._pending or self._carry
            or self._admitting or self._inflight is not None
            or self._pend is not None or self._long is not None
            or self._long_inflight is not None or self._long_pending
        )

    async def _watchdog(self) -> None:
        """Dispatch-progress watchdog (ISSUE 9): its OWN task because the
        state it detects — a device grant that never returns — blocks the
        serve loop inside asyncio.to_thread, so no in-loop check can ever
        run.  Polls on real time; measures the stall on the wall_clock
        seam (deterministic under the chaos virtual clock)."""
        threshold = self.runtime.watchdog_stall_s
        interval = max(0.01, min(threshold / 4.0, 0.25))
        while self._running:
            await asyncio.sleep(interval)
            now = cancellation.wall_clock()
            if self._wedged:
                if self._progress_at > self._wedged_at:
                    # the grant came back: resume serving.  The faulted
                    # requests were flagged cancelled at the trip, so the
                    # ordinary reap frees their slots/pages on the very
                    # pass that just landed.
                    self._wedged = False
                    logger.warning(
                        "engine un-wedged: a dispatch landed after the "
                        "watchdog tripped; serving resumes"
                    )
                continue
            if not self._work_pending():
                # idle is not a stall: re-anchor so the next submit starts
                # its stall clock from now, not from the last busy period
                self._progress_at = now
                continue
            if now - self._progress_at >= threshold:
                self._trip_wedge(now - self._progress_at)

    def _trip_wedge(self, stalled_s: float) -> None:
        """Declare the engine wedged: journal + dump the flight recorder
        (the postmortem IS the decision sequence that led here), flip the
        readiness signal, and fault every owed request with the typed
        RETRIABLE EngineWedgedError so callers fail over NOW instead of
        burning the rest of their deadlines.  Requests are also flagged
        cancelled: if the wedge ever clears, the ordinary cancellation
        reap reclaims their slots/pages — nothing is freed here, because
        an in-flight dispatch may still write through them."""
        self._wedged = True
        self._wedged_at = cancellation.wall_clock()
        self.stats.watchdog_trips += 1
        requests = self._watchdog_requests()
        self._journal.append(
            flightrec.EV_WEDGE, None, -1, int(stalled_s * 1000),
            len(requests),
        )
        try:
            path = self._journal.dump(reason="wedge")
            logger.error(
                "engine WEDGED: no dispatch landing for %.1fs with %d "
                "request(s) pending; flight-recorder dump: %s",
                stalled_s, len(requests), path,
            )
        except Exception:  # noqa: BLE001 - the dump must never mask the fault
            logger.exception("flight-recorder wedge dump failed")
        faulted = 0
        for request in requests:
            if request.wedged:
                continue
            request.wedged = True
            request.cancelled = True
            faulted += 1
            # wake the consumer NOW — the serve loop that normally
            # delivers _DONE is the thing that is stuck
            request.out.put_nowait(_DONE)
        self.stats.watchdog_faulted += faulted
        self._cancel_dirty = True
        self._wake.set()

    def _note_cancel(self, request: GenRequest) -> None:
        """One cancelled request drained from any lane or queue: the
        journal line + counter.  Expiry- and stall-driven cancels were
        already recorded (EV_EXPIRE at the deadline reap, EV_CANCEL at
        the stall flag) and have their own counters — they ride the same
        drain but must not double-count as consumer cancels."""
        self._drop_deadline(request)
        self._drop_lease(request)
        if (
            request.expired or request.stalled or request.wedged
            or request.orphaned or request.shed
        ):
            # wedge-faulted requests were journaled/counted at the trip;
            # orphans at the reaper's EV_ORPHAN; priority-shed victims
            # at _shed_queued's EV_SHED
            return
        self._journal.append(flightrec.EV_CANCEL, request.corr, request.slot)
        self.stats.cancelled_requests += 1

    def cancel_correlation(self, corr: str) -> int:
        """Abandon every request tagged ``corr`` — the mesh ``cancel``
        record's fan-out target (see :mod:`calfkit_tpu.cancellation`; the
        engine registers itself at construction).  Event-loop context;
        returns how many requests were newly flagged.  The scheduler's
        next pass reaps them through the ordinary cancellation path.

        The decode thread concurrently retires slots out of ``_active``
        (flag-only protocol: every other reader runs on the serve loop,
        never alongside the decode tick — this is the one foreign-task
        scan), so the snapshot retries around a mid-iteration resize and,
        if the race persists, defers the match to the scheduler pass
        rather than ever dropping the cancel."""
        if not corr:
            return 0
        for _ in range(4):
            try:
                candidates: list[GenRequest] = [
                    *self._active.values(), *self._carry, *self._pending,
                    *self._long_pending, *self._admitting,
                ]
                break
            except RuntimeError:
                continue
        else:
            self._deferred_cancels.add(corr)
            self._wake.set()
            return 0
        if self._inflight is not None:
            candidates += self._inflight["wave"]
        if self._long is not None:
            candidates.append(self._long["request"])
        if self._long_inflight is not None:
            candidates.append(self._long_inflight["request"])
        matched = 0
        for request in candidates:
            if request.corr == corr and not request.cancelled:
                request.cancelled = True
                matched += 1
        if matched:
            self.stats.cancel_propagated += matched
            self._cancel_dirty = True
            self._wake.set()
        return matched

    def _raise_terminal(self, request: GenRequest) -> None:
        """Typed stream endings: an engine-initiated cancel must surface
        as a typed error at the consumer, not a silent short stream.

        THE precedence law (ISSUE 10 satellite; pinned for BOTH
        schedulers in tests — the ragged and bifurcated lanes share this
        one copy, so agreement is structural): **wedged > expired >
        orphaned > shed > stalled** — a run that is simultaneously
        several of these faults with exactly ONE typed error.  Wedged
        first because a live caller must fail over, not eat a dead-end
        fault; expired before orphaned because the deadline is the
        caller's own contract while orphanhood is the server's inference
        about the caller; a priority shed (ISSUE 20) after the
        non-retriable causes — a victim that also expired/orphaned has a
        truer, terminal cause, and surfacing the retriable shed instead
        would invite a retry for a spent budget; stalled last — a
        stalled consumer that also expired/orphaned/shed already has a
        truer cause."""
        if request.wedged:
            # checked FIRST: a wedged request may also look expired by the
            # time its consumer resumes, but the watchdog faulted it so
            # the caller would fail over — the retriable code must win
            raise EngineWedgedError(
                "engine wedged while this request was pending "
                f"({request.generated} tokens delivered); "
                "retry against another replica",
                stalled_s=self.runtime.watchdog_stall_s,
            )
        if request.expired:
            raise DeadlineExceededError(
                f"request deadline passed after {request.generated} "
                "generated tokens"
            )
        if request.orphaned:
            raise RunOrphanedError(
                "caller lease lapsed; the run was reaped after "
                f"{request.generated} generated tokens",
                lease_id=request.lease_id or "",
            )
        if request.shed:
            # priority-ordered shedding (ISSUE 20): this queued
            # batch-class request was evicted to admit interactive work
            # at a full lane — the same typed RETRIABLE code (and the
            # same lane/pending/limit detail) as a shed-at-submit, so
            # callers back off identically whichever side of the queue
            # the shed landed on
            lane, pending, limit = request.shed_detail or (
                "short", 0, self.runtime.max_pending or 0
            )
            raise EngineOverloadedError(
                f"queued batch-class request was shed from the {lane} "
                f"lane to admit interactive work (pending={pending}, "
                f"max_pending={limit}); retry with backoff",
                lane=lane, pending=pending, limit=limit,
            )
        if request.stalled:
            raise EngineOverloadedError(
                "token delivery stalled past max_out_blocks="
                f"{self.runtime.max_out_blocks}; request was cancelled",
                lane="delivery",
                pending=request.out.qsize(),
                limit=self.runtime.max_out_blocks,
            )

    def _open_queue_span(
        self, request: GenRequest, trace: "TraceContext | None"
    ) -> "Span | None":
        """A traced request joins the queue: its ``engine.queue`` span
        starts, and the admission ledger is marked for ``blocked_on``."""
        if trace is None:
            return None
        request.blocked_at_submit = self.stats.blocked_now(time.perf_counter())
        return TRACER.start_span(
            "engine.queue", parent=trace, kind="engine",
            emitter=self._emitter,
        )

    def _end_queue_span(self, span: "Span", request: GenRequest) -> None:
        """End the request's ``engine.queue`` span at the moment its slot
        was granted.  Called from the CONSUMER's context once the first
        block arrives, so the span reaches the hop's sink like the
        caller's own; the moments are the scheduler's.  A request that
        was never granted a slot (shed, expired or abandoned in the
        queue) ends now, cancelled."""
        if request.granted_at is None:
            span.end(status="cancelled", blocked_on=request.blocked_on)
        else:
            span.end(
                at=request.granted_at, blocked_on=request.blocked_on,
                bucket=request.wave_bucket, wave_rows=request.wave_rows,
            )

    async def _consume(
        self, request: GenRequest, queue_span: "Span | None" = None
    ) -> AsyncIterator[int]:
        """Drain a queued request's tokens; abandoning the iterator flags
        cancellation for the scheduler to reap (both lanes share this)."""
        done = False
        stats, account = self.stats, request.account
        try:
            while True:
                asked = time.perf_counter()
                item = await request.out.get()
                if queue_span is not None:
                    self._end_queue_span(queue_span, request)
                    queue_span = None
                if item is _DONE:
                    done = True
                    self._raise_terminal(request)
                    return
                if type(item) is tuple:  # one dispatch's token block
                    landed, item = item
                    # (booked, not annotated: the take is a microsecond, and
                    # ``_deliver_batch`` carries ``engine.deliver``)
                    taken = time.perf_counter()
                    stats.stream_blocks += 1
                    stats.stream_deliver_wait_s += taken - landed
                    if account is not None:
                        account.take(landed, taken - landed, taken - asked)
                    for token in item:
                        if token is _DONE:
                            done = True
                            self._raise_terminal(request)
                            return
                        yield token
                    continue
                yield item
        finally:
            if queue_span is not None:
                self._end_queue_span(queue_span, request)
            if not done:
                request.cancelled = True
                self._cancel_dirty = True
                self._wake.set()

    # ------------------------------------------------------------ scheduler
    async def _serve(self) -> None:
        stats = self.stats
        # the loop's own spans (one a dispatch) belong to no hop: were the
        # engine started inside one, its sink must not collect them for ever
        detach_spans()
        self._beat = self._loop.call_later(
            HEARTBEAT_S, self._heartbeat, time.perf_counter() + HEARTBEAT_S)
        try:
            while self._running:
                stats.enter(REAP)
                if self._chaos is not None:
                    self._chaos("tick")
                self._drain_deferred_cancels()
                self._check_deadlines()
                self._check_orphans()
                self._check_stalls()
                self._reap_cancelled()
                if self._ragged:
                    # ragged unified waves: ONE scheduler lane — the pass
                    # forms/advances the admission wave and the decode
                    # rows through a single fused dispatch per tick
                    progressed = await self._ragged_pass()
                    progressed |= await self._advance_long()
                    if not progressed:
                        self._wake.clear()
                        if (
                            not self._pending and not self._carry
                            and not self._long_pending and self._long is None
                        ):
                            self._empty_at = None  # an idle engine is not a bubble
                            stats.enter(IDLE)
                            await self._wake.wait()
                    continue
                if self.runtime.chunked_prefill:
                    progressed = await self._admit_chunked()
                else:
                    progressed = await self._admit()
                progressed |= await self._advance_long()
                if self._active:
                    await self._offload(
                        self._spec_decode_tick
                        if self._drafter is not None
                        else self._decode_tick
                    )
                elif self._pend is not None:
                    # every participant retired/cancelled while a dispatch
                    # was still in flight: land it (discarding pad tokens)
                    # so the deferred slot/page frees actually happen
                    await self._offload(self._drain_decode)
                elif not progressed and self._inflight is None:
                    self._wake.clear()
                    if (
                        not self._pending and not self._carry
                        and not self._long_pending and self._long is None
                    ):
                        self._empty_at = None
                        stats.enter(IDLE)
                        await self._wake.wait()
        except Exception as exc:  # noqa: BLE001
            logger.exception("inference engine scheduler crashed")
            # atomicity-ok: the crash rail parks the loop's own run flag —
            # stop() writing False concurrently is the same terminal state
            self._running = False
            # fault postmortem: the ring holds the exact decision sequence
            # that led here — dump it next to the traceback.  Strictly
            # fail-open: a broken journal writer must never mask the
            # original fault or block the teardown below.
            try:
                self._journal.append(
                    flightrec.EV_FAULT, None, -1, 0, 0, repr(exc)
                )
                path = self._journal.dump(reason="fault")
                logger.error("flight-recorder fault dump: %s", path)
            except Exception:  # noqa: BLE001
                logger.exception("flight-recorder fault dump failed")
            self._finish_all()
        finally:
            # the loop has ended: close the open phase and the ledger
            stats.note_blocked(None, 0, stats.enter(None))
            self._beat.cancel()

    @hotpath
    def _heartbeat(self, due: float) -> None:
        """The engine's beat on its loop, every ``HEARTBEAT_S`` while
        ``_serve`` runs: one that comes more than a period late books its
        lateness (``loop_stall_s``, ``loop_stalls``) and journals it, so a
        timeline shows what held the loop beside the dispatches it held
        up: a stall with no sync in it, which no phase and no gap class
        can name.  And the tick's side: a phase open for longer than
        ``long_after`` gives has its annotation re-stamped at every beat
        (``EngineStats.restamp_if_long``), so a capture that ends inside the
        stall holds the phase up to its last beat."""
        now = time.perf_counter()
        late = now - due
        stats = self.stats
        if late > HEARTBEAT_S:
            stats.loop_stalls += 1
            stats.loop_stall_s += late
            self._journal.append(flightrec.EV_LOOP_STALL, None, -1, int(late * 1000.0))
        stats.restamp_if_long(now)
        self._beat = self._loop.call_later(HEARTBEAT_S, self._heartbeat, now + HEARTBEAT_S)

    async def _offload(self, tick: Any, *args: Any) -> Any:
        """Run one tick on a worker thread.  The phase clock follows the
        one thread of control: ``handoff`` from here to the tick's first
        line, and from its return to the coroutine's next phase."""
        self.stats.enter(HANDOFF)
        return await asyncio.to_thread(self._run_tick, tick, *args)

    def _run_tick(self, tick: Any, *args: Any) -> Any:
        self.stats.enter(PREP)
        try:
            return tick(*args)
        finally:
            self.stats.enter(HANDOFF)

    def _note_admission(self, now: float, attempted: bool) -> None:
        """The admission ledger, once a pass: with a request still queued,
        what stopped this pass's attempt to admit it (``_form_wave`` left
        the reason in ``_held_by``), or that none could be made because a
        wave is in flight.  Length checks only: cancelled entries were
        reaped at the top of the pass."""
        head = self._carry[0] if self._carry else (
            self._pending[0] if self._pending else None
        )
        if head is None:
            reason = None
        elif attempted:
            reason = self._held_by
        else:
            reason = WAVE_IN_FLIGHT
        self.stats.note_blocked(
            reason, len(self._free), now,
            head.started_at if head is not None else None,
        )

    def _drain_deferred_cancels(self) -> None:
        """Re-run cancel matches that lost the snapshot race (serve-loop
        context: the decode tick is not in flight, so the snapshot cannot
        fail again; a pathological re-defer lands in the fresh set and
        retries next pass instead of spinning)."""
        if not self._deferred_cancels:
            return
        pending, self._deferred_cancels = list(self._deferred_cancels), set()
        for corr in pending:
            self.cancel_correlation(corr)

    def _reap_cancelled(self) -> None:
        """Drain cancelled requests: active slots AND still-queued entries.

        Runs on the event loop between device dispatches (the decode thread
        also mutates ``_active``, so cancellation itself only sets a flag).
        Queued entries must be drained here too — leaving them in place
        would keep ``_pending`` non-empty and turn the idle wait in
        ``_serve`` into a busy spin with no suspension point.

        A chunked inflight wave whose members ALL cancelled is aborted
        outright (slots + page reservations released, remaining chunks
        skipped); partially-cancelled waves finish their flight and shed
        the cancelled members at activation.

        The dirty flag keeps this O(1) on the ordinary pass: the full
        scan over active/carry/pending/long only runs after some consumer
        actually set a ``cancelled`` flag since the last reap.
        """
        if not self._cancel_dirty:
            return
        self._cancel_dirty = False
        if self._inflight is not None and all(
            r.cancelled for r in self._inflight["wave"]
        ):
            for request in self._inflight["wave"]:
                self._note_cancel(request)
                if request.slot != -1:
                    self._retire_slot(request)
                request.out.put_nowait(_DONE)
            self._inflight = None
        for request in list(self._active.values()):
            if request.cancelled:
                self._note_cancel(request)
                self._retire_slot(request)
                request.out.put_nowait(_DONE)
        if any(r.cancelled for r in self._carry):
            kept = []
            for request in self._carry:
                if request.cancelled:
                    self._note_cancel(request)
                    request.out.put_nowait(_DONE)
                else:
                    kept.append(request)
            self._carry = kept
        if any(r.cancelled for r in self._pending):
            kept_q: deque[GenRequest] = deque()  # unbounded-ok: rebuild of the shed-bounded queue
            for request in self._pending:
                if request.cancelled:
                    self._note_cancel(request)
                    request.out.put_nowait(_DONE)
                else:
                    kept_q.append(request)
            self._pending = kept_q
        if self._long is not None and self._long["request"].cancelled:
            self._note_cancel(self._long["request"])
            self._long["request"].out.put_nowait(_DONE)
            self._long = None
        if any(r.cancelled for r in self._long_pending):
            kept_l: deque[GenRequest] = deque()  # unbounded-ok: rebuild of the shed-bounded queue
            for request in self._long_pending:
                if request.cancelled:
                    self._note_cancel(request)
                    request.out.put_nowait(_DONE)
                else:
                    kept_l.append(request)
            self._long_pending = kept_l

    def _next_pending(self) -> GenRequest | None:
        while self._carry or self._pending:
            request = (
                self._carry.pop(0) if self._carry else self._pending.popleft()
            )
            if request.cancelled:
                self._note_cancel(request)
                request.out.put_nowait(_DONE)
                continue
            return request
        return None

    def _peek_pending(self) -> GenRequest | None:
        for request in (*self._carry, *self._pending):
            if not request.cancelled:
                return request
        return None

    def _reserve_pages(self, request: GenRequest, bucket: int) -> "int | tuple[int, int]":
        """Pages a request needs for its whole life: the prefill writes whole
        bucket pages, decode grows to (prompt + max_new), capped by the
        sequence limit.  With pools by cache kind, the pair (global pages,
        window pages): the second is the row's RING, or all its positions'
        pages where those are fewer, and never grows with the row."""
        from calfkit_tpu.inference.paged import pages_needed

        rt = self.runtime
        total = min(
            len(request.prompt) + request.max_new_tokens + 1, rt.max_seq_len
        )
        need = min(
            max(
                pages_needed(bucket, rt.page_size),
                pages_needed(total, rt.page_size),
            ),
            rt.pages_per_seq(),
        )
        if self.config.eva:
            # the pages it keeps hold a summary a chunk: of the bucket (a prefill
            # lands whole pages of them) or of its whole life
            entries = self.config.summary_entries
            need = min(pages_needed(max(entries(bucket), entries(total)), rt.page_size),
                       self._pages_per_seq)
        if self._windowed:
            # (a prefill lands whole pages of the row's OWN tokens alone in a
            # ring, so the bucket does not count here)
            return need, min(self._ring_pages, pages_needed(total, rt.page_size))
        return need

    def _free_pages(self, slot: int) -> None:
        """Return a slot's page reservation: of both kinds, where the pools
        come by cache kind."""
        self._page_alloc.free(slot)
        self._ledger.free(slot)
        self._note_pools_in_use()

    def _note_pools_in_use(self) -> None:
        """The two pools' gauges, from the allocators themselves."""
        if self._windowed:
            stats, (g, w) = self.stats, self._page_alloc.by_kind
            stats.kv_pages_global_in_use, stats.kv_pages_window_in_use = g.in_use, w.in_use

    def _plan_prefix_reuse(self, request: GenRequest, bucket: int) -> int:
        """Longest cached, alignment-safe prompt prefix for ``request``
        (0 when caching is off or nothing matches).  Sets reuse_len /
        shared_pages / page_hashes on the request; recomputed fresh on
        every attempt (a carried-back request must not keep stale pages).

        Alignment: reuse must be whole PAGES (sharing granularity) and a
        whole number of CHUNKS (the chunk lane resumes at the reused
        offset), and at least the final chunk always recomputes (the
        first token samples from the last chunk's logits)."""
        request.reuse_len = 0
        request.shared_pages = []
        if self._windowed and self.runtime.prefix_cache and not request.reuse_declined:
            # THE one place a model with window layers declines reuse: a
            # registered page of a window layer is a ring entry that its row
            # writes over as it grows, so no page of such a layer outlives
            # its window for a later prompt to share (``self._prefix`` is
            # None for such a model: nothing is ever registered).  Reuse over
            # window pages needs the chunk lane to rebuild the ring from the
            # global layers' prefix; until then the prompt is prefilled whole
            request.reuse_declined = True
            self.stats.prefix_reuse_declined_window += 1
        if self._prefix is None:
            return 0
        rt = self.runtime
        ps = rt.page_size
        if not request.page_hashes:  # prompt is immutable: hash ONCE
            from calfkit_tpu.inference.paged import chain_hashes

            request.page_hashes = chain_hashes(request.prompt, ps)
        if not request.page_hashes:
            return 0
        matched = self._prefix.lookup(request.page_hashes)
        if not matched:
            return 0
        if self._recurrent:
            # THE one place a model with recurrent layers declines reuse:
            # cached pages hold K and V, not the SSM and conv state at
            # their boundary, and the chunk lane cannot resume at an offset
            # without that state.  Reuse by state snapshot is the mechanism
            # that would lift this; until then the prompt is prefilled whole.
            if not request.reuse_declined:
                request.reuse_declined = True
                self.stats.prefix_reuse_declined_recurrent += 1
            return 0
        chunk = min(rt.prefill_chunk, bucket)
        align = ps * chunk // math.gcd(ps, chunk)
        candidate = min(
            len(matched) * ps,
            len(request.prompt) - 1,  # never reuse the final position
            bucket - chunk,           # at least one chunk recomputes
        )
        reuse = (candidate // align) * align
        if reuse <= 0:
            return 0
        request.reuse_len = reuse
        request.shared_pages = matched[: reuse // ps]
        return reuse

    def _drop_reuse_plan(self, request: GenRequest) -> None:
        """Undo a formation-time acquisition for a request that will NOT
        be served this pass (alloc failure / wave trim) — re-admission
        replans from scratch."""
        if self._prefix is not None and request.shared_pages:
            self._journal.append(
                flightrec.EV_PREFIX_REL, request.corr, request.slot,
                len(request.shared_pages),
            )
            self._prefix.release(request.shared_pages)
            self._ledger.release(request.shared_pages)
        request.reuse_len = 0
        request.shared_pages = []

    def _alloc_with_eviction(
        self, slot: int, n: int, corr: "str | None" = None
    ) -> "list[int] | None":
        pages = self._page_alloc.alloc(slot, n)
        if pages is None:
            # density pressure is an advert signal whether or not the
            # cache can cover the shortfall (ISSUE 19)
            self._ledger.note_stall()
            self.stats.alloc_stalls += 1
        if pages is None and self._prefix is not None:
            # idle cache entries are reclaimable capacity, not a leak;
            # the journal records the SHORTFALL (what evict is asked to
            # reclaim), not the whole allocation request — tagged with
            # the REQUESTING owner, so `ck timeline` explains whose
            # admission forced the eviction
            self._journal.append(
                flightrec.EV_PAGE_EVICT, corr, slot,
                n - self._page_alloc.free_pages,
            )
            freed = self._prefix.evict(
                n - self._page_alloc.free_pages, self._page_alloc,
                ledger=self._ledger,
            )
            self.stats.prefix_evictions += freed
            pages = self._page_alloc.alloc(slot, n)
        return pages

    def _take_slot(self) -> int:
        """A free slot: the one freed last; with pools by cache kind the one
        that has been free LONGEST, as its pages are (``PagesByKind``), so that
        a retired row's tables and pages stand while others are free."""
        return self._free.pop(0) if self._windowed else self._free.pop()

    def _bucket_of(self, prompt_len: int) -> int:
        rt = self.runtime
        return min(
            -(-prompt_len // rt.prefill_chunk) * rt.prefill_chunk,
            rt.max_seq_len,
        )

    @hotpath
    def _form_wave(self) -> "tuple[list[GenRequest], int] | None":
        """Scheduling only (no device work): pop a same-bucket wave, assign
        slots (and, when paged, reserve each request's full page footprint —
        admission control, no mid-flight OOM).  None when nothing can be
        admitted right now."""
        if not self._free or self._peek_pending() is None:
            self._held_by = NO_SLOT
            return None

        def bucket_of(req: GenRequest) -> int:
            return self._bucket_of(len(req.prompt))

        wave: list[GenRequest] = [self._next_pending()]
        wave_bucket = bucket_of(wave[0])
        # ragged mode: occupancy-driven admission — the wave may grow only
        # as wide as the token budget lets a dispatch absorb alongside
        # the CURRENT decode load (never below the head; legacy mode
        # returns the batch width and the cap is inert)
        width_cap = self._ragged_wave_cap(wave_bucket)
        head_reuse = self._plan_prefix_reuse(wave[0], wave_bucket)
        if head_reuse:
            # acquire at FORMATION: a later member's _alloc_with_eviction
            # must never reclaim pages an earlier-planned member still
            # needs (acquired pages are not evictable)
            self._prefix.acquire(wave[0].shared_pages)
            self._ledger.acquire(wave[0].shared_pages)
            self._journal.append(
                flightrec.EV_PREFIX_ACQ, wave[0].corr, -1,
                len(wave[0].shared_pages),
            )
        # what holds the head of the queue once this wave is formed: the
        # reason the loop below stops at, unless a later step (the trims,
        # a short page allocation) sends someone back to the front
        self._held_by = OVER_BUDGET  # the width cap, a bucket or reuse mismatch
        while True:
            if len(wave) >= len(self._free):
                self._held_by = NO_SLOT
                break
            if len(wave) >= self.runtime.max_prefill_wave:
                self._held_by = WAVE_IN_FLIGHT  # the wave is full: the next one
                break
            if (
                len(wave) >= width_cap
                or (peeked := self._peek_pending()) is None
                or bucket_of(peeked) != wave_bucket
            ):
                break
            # one offset per wave: only requests whose reuse TRIMS to the
            # head's length batch together (an identical-prompt burst —
            # the headline workload — batches fully once page 1 lands)
            planned = self._plan_prefix_reuse(peeked, wave_bucket)
            if head_reuse == 0 and planned != 0:
                break
            if head_reuse > 0:
                if planned < head_reuse:
                    break
                peeked.reuse_len = head_reuse
                peeked.shared_pages = peeked.shared_pages[
                    : head_reuse // self.runtime.page_size
                ]
                self._prefix.acquire(peeked.shared_pages)
                self._ledger.acquire(peeked.shared_pages)
                self._journal.append(
                    flightrec.EV_PREFIX_ACQ, peeked.corr, -1,
                    len(peeked.shared_pages),
                )
            wave.append(self._next_pending())
        # wave sizes are power-of-two so each prefill bucket compiles at
        # most log2(max_prefill_wave)+1 jit variants (R in 1,2,4,...)
        # instead of one per width; trimmed requests go to the FRONT
        # carry list, preserving arrival order
        keep = 1
        while keep * 2 <= len(wave):
            keep *= 2
        if keep < len(wave):
            self._held_by = OVER_BUDGET  # the power-of-two trim
        for trimmed in wave[keep:]:  # balance formation-time acquisitions
            self._drop_reuse_plan(trimmed)
        self._carry = wave[keep:] + self._carry
        wave = wave[:keep]
        if self._paged:
            # the tail of an unservable wave waits at the queue front
            granted: list[GenRequest] = []
            for i, request in enumerate(wave):
                slot = self._take_slot()
                need = self._reserve_pages(request, wave_bucket)
                shared = request.shared_pages  # acquired at formation
                if not self._windowed:  # (no reuse over window pages: no shared pages)
                    need -= len(shared)
                pages = self._alloc_with_eviction(slot, need, request.corr)
                if pages is None:
                    self._held_by = NO_PAGES
                    self._free.append(slot)
                    # EVERY carried member's acquisition must be undone,
                    # or its refcount leaks and the pages become
                    # unevictable forever
                    for carried in wave[i:]:
                        self._drop_reuse_plan(carried)
                    self._carry = wave[i:] + self._carry
                    break
                request.slot = slot
                held = len(pages)
                if self._windowed:
                    # the global pages, and beside them the row's ring; the
                    # ledger counts both in pages of equal bytes
                    pages, request.ring_pages = pages
                    held = self._page_alloc.layer_pages(len(pages), len(request.ring_pages))
                    self._note_pools_in_use()
                request.pages = shared + pages
                self._journal.append(
                    flightrec.EV_PAGE_ALLOC, request.corr, slot,
                    len(request.pages), len(shared),
                )
                self._ledger.alloc(
                    slot, held, request.corr, request.run,
                    capacity.lane_kind(request.history),
                )
                granted.append(request)
            wave = granted
            if not wave:
                return None  # pool exhausted: wait for retirements
            # keep jit variants power-of-two after page trimming too
            keep = 1
            while keep * 2 <= len(wave):
                keep *= 2
            for request in wave[keep:]:
                self._journal.append(
                    flightrec.EV_PAGE_FREE, request.corr, request.slot
                )
                self._free_pages(request.slot)
                self._free.append(request.slot)
                request.slot = -1
                request.pages = []
                self._drop_reuse_plan(request)
            self._carry = wave[keep:] + self._carry
            wave = wave[:keep]
        else:
            for request in wave:
                request.slot = self._take_slot()
        self._journal.append(
            flightrec.EV_WAVE_FORM, None, -1, len(wave), wave_bucket
        )
        self._note_granted(wave, wave_bucket)
        return wave, wave_bucket

    def _note_granted(self, wave: "list[GenRequest]", bucket: int) -> None:
        """The moment the wave's requests got their slots: the end of
        their ``engine.queue`` span.  ``blocked_on`` is the ledger reason
        that grew most while the request waited (traced requests only:
        the others carry no mark)."""
        now = time.perf_counter()
        stats = self.stats
        mark: "tuple[float, ...] | None" = None
        for request in wave:
            request.granted_at = now
            request.wave_rows = len(wave)
            request.wave_bucket = bucket
            if request.blocked_at_submit is None:
                continue
            if mark is None:
                mark = stats.blocked_now(now)
            held = [m - b for m, b in zip(mark, request.blocked_at_submit)]
            longest = max(held)
            request.blocked_on = (
                BLOCKED[held.index(longest)][len("blocked_"):-len("_s")]
                if longest > 0.0 else "none"
            )

    def _activate_wave(self, wave: list[GenRequest]) -> None:
        for request in wave:
            # a request can retire DURING its own prefill (first token
            # was a stop, or max_new_tokens == 1): _record_token already
            # freed its slot and set slot = -1 — don't resurrect it.  (A
            # wave whose landing rides a dispatch has no first tokens down
            # yet: such a row is activated, rides the next dispatch, and
            # retires at that landing by the deferred path.)
            if request.slot == -1:
                continue
            if request.cancelled:
                # abandoned while its (chunked) admission was in flight:
                # release the slot + pages instead of activating a corpse
                self._note_cancel(request)
                self._retire_slot(request)
                request.out.put_nowait(_DONE)
                continue
            self._active[request.slot] = request
            self._journal.append(
                flightrec.EV_ADMIT, request.corr, request.slot,
                len(request.prompt), request.reuse_len,
            )
            self._track_retirement(request)
            # device-side retirement inputs for the slot: stop-token row
            # (-1 padded; the submit-time cap guarantees it fits whenever
            # a device-authority path will read it) and hard-bound lens
            row = self._stop_np[request.slot]
            row[:] = -1
            stops = sorted(request.stop_tokens)[: row.shape[0]]
            row[: len(stops)] = stops
            self._hard_end[request.slot] = min(
                len(request.prompt) + request.max_new_tokens - 1,
                self.runtime.max_seq_len - 2,
            )
            self._retire_dev = None  # device copies stale: re-upload at launch
            if self._drafter is not None and request.history is not None:
                self._drafter.admit(request.slot, request.prompt)

    async def _admit(self) -> bool:
        admitted = False
        now = self.stats.enter(ADMIT)
        while (formed := self._form_wave()) is not None:
            wave, wave_bucket = formed
            self._admitting = wave
            try:
                await self._offload(self._prefill_wave, wave, wave_bucket)
            finally:
                self._admitting = []
            self.stats.enter(ADMIT)
            self._activate_wave(wave)
            admitted = True
        self._note_admission(now, True)
        return admitted

    # ------------------------------------------------- long-context lane
    # Prompts that cannot fit a short-lane slot are served one at a time:
    # sequence-parallel ring prefill shards the prompt over an `sp` mesh of
    # ALL the engine's devices, and decode runs context-parallel against
    # the still-sharded prefix (``ring_attention.decode_sp_dispatch``).
    # The lane interleaves with short-lane ticks in ``_serve``: one long
    # dispatch per scheduler pass, so short streams' inter-token latency
    # stays bounded while a long request is in flight.

    def _long_max_prompt(self) -> int:
        rt = self.runtime
        return rt.long_max_prompt or 8 * rt.max_seq_len

    def _sp_mesh(self) -> Any:
        if self._sp_mesh_cache is None:
            from jax.sharding import Mesh

            # blocking-ok: host-side Device-object list (mesh topology),
            # not a device array — nothing syncs; cached after first call
            devices = np.asarray(self.mesh.devices).reshape(-1)
            self._sp_mesh_cache = Mesh(devices, ("sp",))
        return self._sp_mesh_cache

    def _long_fresh_cap(self) -> int:
        """Static size of the carried fresh cache — ONE compile for every
        long request regardless of its max_new_tokens."""
        steps = self.runtime.decode_steps_per_dispatch
        return -(-self.runtime.long_new_cap // steps) * steps

    async def _advance_long(self) -> bool:
        if not self.runtime.long_context:
            return False
        if self._long is not None:
            await self._offload(self._long_decode_tick)
            return True
        if self._long_inflight is not None:
            await self._offload(self._advance_long_prefill)
            return True
        self.stats.enter(ADMIT)
        request = None
        while self._long_pending:
            candidate = self._long_pending.popleft()
            if candidate.cancelled:
                self._note_cancel(candidate)
                candidate.out.put_nowait(_DONE)
                continue
            request = candidate
            break
        if request is None:
            return False
        self._journal.append(
            flightrec.EV_ADMIT_LONG, request.corr, -1, len(request.prompt)
        )
        request.granted_at = time.perf_counter()  # the lane is its slot
        request.wave_rows = 1
        if self.runtime.chunked_prefill:
            # resumable: one chunk per scheduler pass, short decode ticks
            # run between chunks (same latency bound as the short lane)
            self._start_long_inflight(request)
            return True
        self._admitting = [request]
        try:
            await self._offload(self._long_prefill, request)
        finally:
            self._admitting = []
        return True

    def _long_padded(self, n: int) -> int:
        """Pad to power-of-two multiples of lcm(sp, prefill_chunk): the
        sequence must divide over sp, and power-of-two bucketing bounds
        the sp-prefill compile count at log(range) shapes."""
        g = math.lcm(self._sp_mesh().shape["sp"], self.runtime.prefill_chunk)
        units = -(-n // g)
        p2 = 1
        while p2 < units:
            p2 *= 2
        return g * p2

    def _install_long_state(
        self, request: GenRequest, prefix: tuple, n: int, first: int,
        started: float,
    ) -> None:
        """Shared landing for both long-prefill paths: emit the first
        token and stage the decode-phase device state."""
        request.prefill_ms = (time.perf_counter() - started) * 1000.0
        self.stats.prefill_tokens += n
        self.stats.long_requests += 1
        self._observe("prefill_ms", request.prefill_ms)
        ttft_ms = (time.perf_counter() - request.started_at) * 1000.0
        self._observe("ttft_ms", ttft_ms)
        # the long lane's wait is everything before its prefill started
        self._observe("queue_wait_ms", max(0.0, ttft_ms - request.prefill_ms))
        if self._emit_long(request, first, time.perf_counter()):
            return
        cfg = self.config
        cap = self._long_fresh_cap()
        fresh_shape = (cfg.n_layers, 1, cfg.n_kv_heads, cap, cfg.head_dim)
        self._long = dict(
            request=request,
            prefix=prefix,
            prefix_len=n,
            fresh=(
                jnp.zeros(fresh_shape, jnp.float32),
                jnp.zeros(fresh_shape, jnp.float32),
            ),
            t=0,
            cap=cap,
            last=jnp.asarray([first], jnp.int32),
        )

    def _long_prefill(self, request: GenRequest) -> None:
        from calfkit_tpu.inference.ring_attention import (
            prefill_sequence_parallel,
        )

        mesh = self._sp_mesh()
        n = len(request.prompt)
        padded = self._long_padded(n)
        tokens = np.zeros((1, padded), np.int32)
        tokens[0, :n] = request.prompt
        started = self.stats.enter(ENQUEUE)
        last_logits, (k_prefix, v_prefix) = prefill_sequence_parallel(
            self.params, self.config, jnp.asarray(tokens), mesh,
            seq_lens=jnp.asarray([n], jnp.int32),
        )
        self.stats.enter(SYNC)
        first = int(np.asarray(jnp.argmax(last_logits[0])))
        self.stats.enter(FANOUT)
        self._install_long_state(
            request, (k_prefix, v_prefix), n, first, started
        )

    def _start_long_inflight(self, request: GenRequest) -> None:
        """Host-side setup of a resumable chunked long prefill: the SAME
        chunk program as the short lane (`_chunk_jit`), running over a
        sequence-sharded scratch sized for the padded prompt — GSPMD
        shards the chunk's attention over `sp` and inserts the collectives.
        Only chunks covering the true prompt run; padding is never
        touched (it stays zero and masked)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.config
        mesh = self._sp_mesh()
        n = len(request.prompt)
        padded = self._long_padded(n)
        chunk = min(self.runtime.prefill_chunk, padded)
        scratch_shape = (
            cfg.n_layers, 1, cfg.n_kv_heads, padded, cfg.head_dim
        )
        sharding = NamedSharding(mesh, P(None, None, None, "sp", None))
        tokens = np.zeros((1, padded), np.int32)
        tokens[0, :n] = request.prompt
        self._long_inflight = dict(
            request=request,
            tokens=tokens,
            true_len=n,
            chunk=chunk,
            n_chunks=-(-n // chunk),  # only chunks covering the prompt
            idx=0,
            # sharded AT CREATION: an eager zeros would materialize the
            # whole padded scratch on one device first — the exact OOM the
            # sp lane exists to avoid
            scratch=(
                jnp.zeros(scratch_shape, self._k.dtype, device=sharding),
                jnp.zeros(scratch_shape, self._k.dtype, device=sharding),
            ),
            started=time.perf_counter(),
        )

    def _advance_long_prefill(self) -> None:
        """One chunk of the inflight long prefill; land on the last."""
        inf = self._long_inflight
        request = inf["request"]
        if request.cancelled:
            self._note_cancel(request)
            self._long_inflight = None
            # runs on the to_thread worker: queue puts marshal to the loop
            self._loop.call_soon_threadsafe(request.out.put_nowait, _DONE)
            return
        chunk, idx = inf["chunk"], inf["idx"]
        sk, sv = inf["scratch"]
        tok_chunk = jnp.asarray(inf["tokens"][:, idx * chunk:(idx + 1) * chunk])
        self.stats.enter(ENQUEUE)
        sk, sv, logits = self._chunk_jit(chunk, 1)(
            self.params, sk, sv, tok_chunk, jnp.int32(idx * chunk)
        )
        inf["scratch"] = (sk, sv)
        inf["idx"] = idx + 1
        if inf["idx"] < inf["n_chunks"]:
            return
        # last prompt-covering chunk: the final valid position lives here
        n = inf["true_len"]
        local = (n - 1) - (inf["n_chunks"] - 1) * chunk
        self.stats.enter(SYNC)
        first = int(np.asarray(jnp.argmax(logits[0, local])))
        self.stats.enter(FANOUT)
        self._long_inflight = None
        self._install_long_state(
            request, (sk, sv), n, first, inf["started"]
        )

    @hotpath
    def _long_decode_tick(self) -> None:
        """One long-lane pass.  Overlap mode gives the sp lane the same
        launch-next-then-sync-previous treatment as the short lane: the
        dispatch enqueued this pass runs on the mesh while the previous
        block's tokens fan out, so the lane's per-dispatch sync no longer
        serializes host and device.  A stop token found in the landed
        block abandons the already-launched follow-up (its steps count as
        ``overlap_wasted_tokens``; the per-request fresh cache it wrote
        is discarded with the state, so nothing shared is corrupted)."""
        from calfkit_tpu.inference.ring_attention import decode_sp_dispatch

        state = self._long
        request = state["request"]
        pend = state.pop("pend", None)
        launched: "dict | None" = None
        if state["t"] < state["cap"]:
            steps = min(
                self.runtime.decode_steps_per_dispatch,
                state["cap"] - state["t"],
            )
            started = self.stats.enter(ENQUEUE)
            toks, last, fresh = decode_sp_dispatch(
                self.params, self.config, state["last"], state["prefix"],
                jnp.asarray([state["prefix_len"]], jnp.int32),
                state["fresh"], state["t"], self._sp_mesh(), steps,
            )
            state["fresh"] = fresh
            state["last"] = last
            state["t"] += steps
            launched = dict(toks=toks, steps=steps, started=started)
        if self.runtime.overlap_dispatch:
            # double-buffered: the block launched THIS pass lands next
            # pass, with its follow-up already in flight
            state["pend"] = launched
            landing = pend
        else:
            landing = launched
        if landing is None:
            return  # first overlapped pass: launch only
        block = self._sync_host(landing["toks"])[0]  # host sync per dispatch
        now = self.stats.enter(FANOUT)
        start = landing["started"]
        last_sync = state.get("synced_at")
        if last_sync is not None and last_sync > start:
            start = last_sync  # exclusive wall (see _land_decode)
        state["synced_at"] = now
        # NOT decode_dispatches: that counter is mean_occupancy's
        # denominator, and a long dispatch uses the whole mesh, not slots
        self._note_progress()  # sp-lane landing: watchdog progress too
        self.stats.long_dispatches += 1
        self.stats.decode_time_s += now - start
        done = False
        for token in block:
            done = self._emit_long(request, int(token), now)
            if done:
                break
        inflight = state.get("pend")
        if done:
            if inflight is not None:
                # one-dispatch-late retirement, long-lane edition: the
                # pre-launched follow-up block is all pad now
                self.stats.overlap_wasted_tokens += inflight["steps"]
            self._drop_deadline(request)
            self._drop_lease(request)
            self._long = None
        elif state["t"] >= state["cap"] and inflight is None:
            self._drop_deadline(request)
            self._drop_lease(request)
            self._loop.call_soon_threadsafe(request.out.put_nowait, _DONE)
            self._long = None

    def _emit_long(self, request: GenRequest, token: int, landed: float) -> bool:
        """Record one long-lane token (runs on the to_thread worker);
        returns True when the request retired."""
        items: list = []
        done = self._record_token(request, token, items, long=True)
        if items:
            self._loop.call_soon_threadsafe(
                _deliver_batch, [(request.out, (landed, items))]
            )
        return done

    # ------------------------------------------------------- device work
    def _effective_sampling(self, request: GenRequest) -> SamplingParams:
        return request.sampling if request.sampling is not None else self.sampling

    def _wave_arrays(self, wave: list[GenRequest], bucket: int) -> dict:
        """Host-side array prep shared by single-shot and chunked prefill."""
        R = len(wave)
        tokens = np.zeros((R, bucket), np.int32)
        true_lens = np.zeros((R,), np.int32)
        slots = np.zeros((R,), np.int32)
        seeds = np.zeros((R,), np.uint32)
        w_temp = np.zeros((R,), np.float32)
        w_top_k = np.zeros((R,), np.int32)
        w_top_p = np.ones((R,), np.float32)
        sampled = False
        for r, request in enumerate(wave):
            tokens[r, : len(request.prompt)] = request.prompt
            true_lens[r] = len(request.prompt)
            slots[r] = request.slot
            self._admissions += 1
            seeds[r] = (
                request.seed if request.seed is not None else self._admissions
            ) & 0xFFFFFFFF
            params = self._effective_sampling(request)
            w_temp[r] = params.temperature
            w_top_k[r] = params.top_k
            w_top_p[r] = params.top_p
            sampled |= not params.is_greedy
        return dict(
            tokens=tokens, true_lens=true_lens, slots=slots, seeds=seeds,
            w_temp=w_temp, w_top_k=w_top_k, w_top_p=w_top_p, sampled=sampled,
        )

    def _state_kw(self, wstate: Any = None) -> dict:
        """The landing programs' recurrent-state arguments (by name: the
        paged and dense landings differ in what comes before them)."""
        if not self._recurrent:
            return {}
        return {"state": self._state, **({} if wstate is None else {"wstate": wstate})}

    def _wave_state_args(self, inf: dict) -> list:
        """A chunk program's recurrent-state arguments: the wave's state
        so far and its rows' true lengths."""
        if not self._recurrent:
            return []
        return [inf["wstate"], jnp.asarray(inf["arrays"]["true_lens"])]

    def _note_chunk(self, offset: int, chunk: int, bucket: int, true_lens: Any) -> None:
        """Count a launched chunk (host arithmetic from shapes and the rows'
        true lengths, no sync): the positions it computes and those of them
        that hold no prompt token (``EngineStats.chunk_tokens*``) and, for a
        model with window layers, its attention (``chunk_attn_*``)."""
        lens = true_lens.astype(np.int64)  # the host's own array: no device value comes here
        self.stats.chunk_tokens += lens.size * chunk
        self.stats.chunk_tokens_padding += int(
            lens.size * chunk - np.clip(lens - offset, 0, chunk).sum())
        if not self._windowed:
            return
        cfg = self.config
        if cfg.eva:
            # the rows' own positions of this window: each attends the keys of the
            # window up to itself and every summary of the windows before; their
            # complete chunks are pooled
            own = np.clip(lens - offset, 0, chunk)
            self.stats.chunk_attn_pairs_eva_window += int((own * (own + 1) // 2).sum()) * cfg.n_layers
            self.stats.chunk_attn_pairs_eva_summary += (
                int(own.sum()) * cfg.summary_entries(offset) * cfg.n_layers)
            self.stats.eva_chunks_pooled += int((own // cfg.chunk_size).sum()) * cfg.n_layers
            return
        from calfkit_tpu.inference.pallas_attention import chunk_attention_work

        pairs_w, pairs_g, visited, dense = chunk_attention_work(
            offset, chunk, bucket, true_lens, cfg.sliding_window,
            cfg.n_window_layers, cfg.n_global_layers)
        self.stats.chunk_attn_pairs_window += pairs_w
        self.stats.chunk_attn_pairs_global += pairs_g
        self.stats.chunk_attn_key_blocks_visited += visited
        self.stats.chunk_attn_key_blocks_dense += dense

    def _moe_kw(self, inf: "dict | None" = None, decode: bool = True) -> dict:
        """A program's expert-counter arguments, by name (a hybrid's state
        goes by place before them): zeroed counters for its decode steps
        (never donated: the same zeros every dispatch) and, for a chunk of
        the wave ``inf``, the wave's counters so far with its rows' true
        lengths.  Called once for every chunk it enqueues, so the chunk's
        form is counted here, from the shapes alone."""
        if not self._moe:
            return {}
        kw = {"moe": self._moe_zero} if decode else {}
        if inf is not None:
            if dense_form(len(inf["wave"]) * inf["chunk"], self.config):
                self.stats.moe_dense_chunks += 1
            else:
                self.stats.moe_grouped_chunks += 1
            kw["wmoe"] = inf["wmoe"]
            if not self._recurrent:
                kw["true_lens"] = jnp.asarray(inf["arrays"]["true_lens"])
        return kw

    def _note_state_landed(self, landed: list) -> None:
        if landed:
            self._state = landed[0]

    def _keep_carried(self, came_back: list) -> Any:
        """What a decode program returns after ``done``: the slots'
        recurrent state, kept here, then its expert counters, handed back
        (they ride the pend to its landing)."""
        came_back = list(came_back)
        if self._recurrent:
            self._state = came_back.pop(0)
        return came_back.pop(0) if self._moe else None

    def moe_expert_counts(self) -> "np.ndarray | None":
        """[expert layers, held experts] int64: the REAL tokens each held
        expert of each layer was sent since the engine started (a copy; None
        without routed experts).  Which experts are hot, layer by layer; the
        scalar ``moe_*`` counters are sums over it."""
        return None if self._moe_counts is None else self._moe_counts.copy()

    def window_ring(self, layer: int = 0, values: bool = False) -> "jax.Array | None":
        """The keys of ONE window layer as every slot's ring of pages holds
        them now, ``[slots, K, ring pages x page, hd]`` (entry ``r`` of a row:
        the newest position ``p = r`` mod the ring's tokens that the row has
        written), or None for a model without window layers.  A retired
        row's ring stands until a wave lands in its slot or its pages are
        taken again, which is after the other free slots and pages have gone
        round (both are granted oldest-first): what a check of what the
        served rows LEFT BEHIND reads
        (``benchmarks/architectures/cohere2-moe-swa.py``).  Read through
        ``model.gather_window_paged``, which takes the pool's stored rows
        (``model.make_page_pool``) apart: positions by ``hd``, whatever the
        stored form."""
        if not self._windowed:
            return None
        side = self._v if values else self._k  # (``values``: the V side in place of the K)
        return M.gather_window_paged(
            side[1][layer], self._tables[1], self._ring_pages, self.config.head_dim)

    def global_keys(self, slot: int, layer: int = 0, values: bool = False) -> "jax.Array | None":
        """The keys of ONE global layer (its index among the global layers) as
        ONE slot's global pages hold them now, ``[K, pages a sequence x page,
        hd]``, position ``p`` at entry ``p``; None for a model without window
        layers.  An EVA stack's pages of this kind hold its SUMMARIES: entry
        ``j`` the pooled key (``values``: the pooled value) of chunk ``j``.
        One slot at a time: every slot's would be a copy of the whole pool.  Stands after retirement as ``window_ring`` does, and is read
        out of the stored pool the same way."""
        if not self._windowed:
            return None
        table = self._tables[0][slot:slot + 1]
        side = self._v if values else self._k
        return M.gather_window_paged(
            side[0][layer], table, table.shape[1], self.config.head_dim)[0]

    def recurrent_state(self) -> "tuple[jax.Array, jax.Array] | None":
        """The slots' recurrent state as it stands, ``(matrix [layers, slots,
        ..], conv [layers, taps - 1, slots, channels])`` on the device (None
        for a model without recurrent layers).  A slot keeps the state its
        last sequence left until a wave lands in it, so a finished
        sequence's state can be read back and compared.  Read it on an IDLE
        engine: a dispatch in flight holds the arrays donated."""
        return self._state

    def _note_moe(self, counts: Any, hit: Any, absent: Any = 0, in_held_groups: Any = 0,
                  decode_steps: int = 0) -> None:
        """Fold one dispatch's expert counters (already on their way to the
        host with what the landing syncs) into the stats; ``absent`` is
        there where the experts are held by share, ``in_held_groups`` where
        the gate chooses by group, ``decode_steps`` the steps of a decode
        dispatch (a wave's chunks: 0)."""
        counts = np.asarray(counts)  # blocking-ok: computed before the sync that just landed
        self._moe_counts += counts
        stats = self.stats
        stats.moe_assignments += int(counts.sum())
        stats.moe_assignments_absent += int(absent)
        stats.moe_rows_in_held_groups += int(in_held_groups)
        stats.moe_expert_tokens_max += int(counts.max(axis=1).sum())
        stats.moe_expert_tokens_mean += float(counts.mean(axis=1).sum())
        if decode_steps:
            stats.moe_experts_hit += int(hit)
            if self._moe_step_impl != "xla":
                stats.moe_step_kernel_steps += decode_steps

    def _sampling_state_args(self, arrays: dict) -> list:
        return [
            self._slot_keys,
            self._temp,
            self._top_k,
            self._top_p,
            jnp.asarray(arrays["seeds"]),
            jnp.asarray(arrays["w_temp"]),
            jnp.asarray(arrays["w_top_k"]),
            jnp.asarray(arrays["w_top_p"]),
        ]

    def _paged_wave_args(self, wave: list[GenRequest], bucket: int) -> list:
        from calfkit_tpu.inference.paged import TRASH_PAGE, table_row

        R = len(wave)
        page = self.runtime.page_size
        pmax = self._pages_per_seq
        eva = self.config.eva
        # pages of the bucket's tokens, or (an EVA stack) every page of summaries
        # a row can hold: its scratch is sized for the longest prompt, and what
        # lies past the row's reservation goes to the trash page
        npg = pmax if eva else bucket // page
        page_rows = np.zeros((R, pmax), np.int32)
        scatter_ids = np.zeros((R, npg), np.int32)
        for r, request in enumerate(wave):
            page_rows[r] = table_row(request.pages, pmax)
            # prefill writes whole bucket pages; reservation covers them
            scatter_ids[r] = page_rows[r, :npg]
            if request.reuse_len:
                # reused pages are SHARED read-only: route their scatter
                # writes to the trash page (the scratch region is a copy
                # of what they already hold anyway)
                scatter_ids[r, : request.reuse_len // self.runtime.page_size] = (
                    TRASH_PAGE
                )
        if not self._windowed:
            return [self._tables, jnp.asarray(page_rows), jnp.asarray(scatter_ids)]
        # pools by kind: beside the global rows, each row's RING and where the
        # scratch's pages of the window layers land in it: page j of the
        # prompt in entry j % ring, and only the last ``ring`` pages that hold
        # the row's own tokens (an older page would be written over by a
        # newer one in the same scatter; a page past the prompt is padding)
        # (an EVA stack's scratch holds the exact keys of the prompt's LAST chunk
        # alone: its pages from ``first`` on, and its windows before are closed)
        ring = self._ring_pages
        first = (bucket - self.config.window_size) // page if eva else 0
        ring_rows = np.zeros((R, ring), np.int32)
        ring_ids = np.full((R, bucket // page - first), TRASH_PAGE, np.int32)
        for r, request in enumerate(wave):
            ring_rows[r] = table_row(request.ring_pages, ring)
            last = (len(request.prompt) - 1) // page
            self.stats.window_pages_given_back += max(0, last + 1 - ring)
            landing = np.arange(max(first, last - len(request.ring_pages) + 1), last + 1)
            ring_ids[r, landing - first] = ring_rows[r, landing % ring]
            if eva:
                self.stats.eva_windows_closed += len(request.prompt) // self.config.window_size
        return [self._tables, (jnp.asarray(page_rows), jnp.asarray(ring_rows)),
                (jnp.asarray(scatter_ids), jnp.asarray(ring_ids))]

    def _land_wave(
        self, wave: list[GenRequest], true_lens: np.ndarray,
        firsts: np.ndarray, elapsed_ms: float, landed: float,
    ) -> None:
        """Host side of the wave landing: stats and the first-token
        emission — batched into ONE event-loop marshal for the whole wave.
        The device-side last/lens scatter happens inside the prefill jit
        (``_finalize_wave_math``); the host's mirror of the lens followed
        its enqueue (:meth:`_mirror_wave_lens`).  Where the landing rode a
        dispatch the wave's rows are already active and in the NEXT
        dispatch: a row that retires here (a first token that is a stop,
        ``max_new_tokens == 1``) takes ``_retire_slot``'s deferred path,
        and that dispatch's column for it is discarded.  ``landed``: the
        moment ``_landed`` booked the sync, which rides each block."""
        deliveries: list = []
        self._note_progress()  # a wave landing is watchdog progress
        self._observe("prefill_ms", elapsed_ms)
        self._journal.append(
            flightrec.EV_WAVE_LAND, None, -1, len(wave), int(elapsed_ms)
        )
        now = time.perf_counter()
        for r, request in enumerate(wave):
            if request.slot == -1:
                continue
            request.prefill_ms = elapsed_ms
            self.stats.prefill_tokens += int(true_lens[r])
            # per-request latency attribution: the wave lands the first
            # token, so submit→now IS the TTFT; what precedes the prefill
            # work is queue wait.  O(wave), never per token.
            ttft_ms = (now - request.started_at) * 1000.0
            self._observe("ttft_ms", ttft_ms)
            self._observe("queue_wait_ms", max(0.0, ttft_ms - elapsed_ms))
            items: list = []
            self._record_token(request, int(firsts[r]), items)
            if items:
                deliveries.append((request.out, (landed, items)))
        if deliveries:
            self._loop.call_soon_threadsafe(_deliver_batch, deliveries)

    def _mirror_wave_lens(self, wave: list[GenRequest], true_lens: np.ndarray) -> None:
        """The host's mirror of the lens a landing program was just handed
        to write: the prompt occupies [0, true_len), decode inserts from
        true_len.  It follows the ENQUEUE, as ``_stage_pend``'s advance does:
        the rows of a wave whose landing rides a dispatch are in the next
        launch's window arithmetic before their first tokens are down."""
        for r, request in enumerate(wave):
            if request.slot != -1:
                self._host_lens[request.slot] = int(true_lens[r])

    def _wave_landed(self, landing: dict, firsts: np.ndarray, wmoe: Any, now: float) -> None:
        """What follows the sync that brought a chunked wave's first tokens
        down, ONE copy for the landing that was a sync of its own and the
        one that rode a dispatch's (``landing``: what ``_finalize_inflight``
        kept; ``now``: the moment ``_landed`` booked that sync): the wave's
        expert counters, first-token delivery and prefix registration."""
        if wmoe:  # the wave's chunks ran before the program just synced
            self._note_moe(*wmoe)
        wave = landing["wave"]
        self._land_wave(
            wave, landing["true_lens"], firsts, (now - landing["started"]) * 1000.0, now)
        if self._prefix is not None:
            for request in wave:
                self._register_prefix_pages(request)

    def _prefill_wave(self, wave: list[GenRequest], bucket: int) -> None:
        R = len(wave)
        arrays = self._wave_arrays(wave, bucket)
        started = time.perf_counter()
        fn = self._prefill_jit(bucket, R, arrays["sampled"])
        args = [
            self.params,
            self._k,
            self._v,
            self._last,
            self._lens,
            jnp.asarray(arrays["tokens"]),
            jnp.asarray(arrays["slots"]),
            jnp.asarray(arrays["true_lens"]),
            *self._sampling_state_args(arrays),
        ]
        if self._paged:
            args += self._paged_wave_args(wave, bucket)
        self.stats.enter(ENQUEUE, self._enq_seq + 1)
        (
            self._k, self._v, tables, self._last, self._lens,
            self._slot_keys, self._temp, self._top_k, self._top_p, firsts,
            *landed,
        ) = fn(*args, **self._state_kw(), **({"moe": self._moe_zero} if self._moe else {}))
        self._note_chunk(0, bucket, bucket, arrays["true_lens"])
        seq = self._enq_seq
        moe = landed.pop() if self._moe else None
        self._note_state_landed(landed)
        if self._paged:
            self._tables = tables
        self._mirror_wave_lens(wave, arrays["true_lens"])
        # sync BEFORE timing: with async dispatch, fn() returns before the
        # device runs — prefill_ms must be real latency, not enqueue time
        self._sync_began = self.stats.enter(SYNC, seq)
        firsts = np.asarray(firsts)
        if moe is not None:
            self._note_moe(*moe)
        landed = self._landed(seq, wave=True)
        self._land_wave(wave, arrays["true_lens"], firsts, (landed - started) * 1000.0, landed)

    # --------------------------------------------------- chunked admission
    async def _admit_chunked(self) -> bool:
        """One scheduler pass of chunked admission: start an inflight wave
        if none, then advance it by ONE chunk (finalizing on the last).  A
        decode tick runs between passes, so active streams' inter-token
        latency is bounded by one chunk instead of a whole bucket.  This
        is the LEGACY (bifurcated) lane — with ragged waves on, the chunk
        instead rides the decode dispatch (:meth:`_ragged_pass`)."""
        now = self.stats.enter(ADMIT)
        attempted = self._inflight is None
        if attempted:
            formed = self._form_wave()
            if formed is not None:
                self._start_inflight_wave(*formed)
        self._note_admission(now, attempted)
        if self._inflight is None:
            return False
        finished = await self._offload(self._advance_inflight)
        if finished:
            self.stats.enter(ADMIT)
            wave = self._inflight["wave"]
            self._inflight = None
            self._activate_wave(wave)
        return True

    def _start_inflight_wave(
        self, wave: "list[GenRequest]", bucket: int
    ) -> None:
        """Stage a formed wave for chunked advancement: allocate (or
        prefix-seed) the scratch and record the chunk cursor.  Shared by
        the legacy chunked lane and the ragged unified lane."""
        chunk = min(self.runtime.prefill_chunk, bucket)
        cfg = self.config
        R = len(wave)
        scratch_shape = (cfg.n_kv_layers, R, cfg.cache_heads, bucket)
        reuse = wave[0].reuse_len  # uniform across the wave
        if reuse:
            # seed the scratch with the cached prefix K/V (each row's
            # pages gathered from the pool) and resume the chunk loop
            # at the reused offset — the chunk jit's offset is data,
            # so no new compile per reuse length
            npg_r = reuse // self.runtime.page_size
            ids = np.asarray(
                [request.pages[:npg_r] for request in wave], np.int32
            )
            scratch = self._seed_scratch_jit(bucket, npg_r, R)(
                self._k, self._v, jnp.asarray(ids)
            )
            self.stats.prefix_hits += len(wave)
            self.stats.prefix_reused_tokens += reuse * len(wave)
        elif cfg.eva:  # the prompt's summaries and ONE chunk's exact keys (eva.py)
            from calfkit_tpu.inference.eva import make_scratch

            # (sized for the longest prompt whatever the bucket: the chunk programs
            # have ONE shape, and a chunk walks the key blocks its row has)
            scratch = make_scratch(cfg, R, self.runtime.max_seq_len, _pool_dtype(self._k))
        else:
            scratch = M.cache_sides(self.config, scratch_shape, _pool_dtype(self._k))
        self._inflight = dict(
            wave=wave, bucket=bucket, chunk=chunk,
            n_chunks=-(-bucket // chunk), idx=reuse // chunk,
            arrays=self._wave_arrays(wave, bucket),
            scratch=scratch,
            # the wave's recurrent state, carried from chunk to chunk
            # beside the scratch (a fresh sequence's: zeros)
            wstate=make_recurrent_state(cfg, R) if self._recurrent else None,
            wmoe=self._moe_zero,  # the wave's expert counters (None without experts)
            started=time.perf_counter(),
        )

    def _advance_inflight(self, ride: "dict | None" = None) -> bool:
        """Run one chunk of the inflight wave in its OWN device invocation
        (the legacy lane, and the ragged lane's fallback when the token
        budget refuses absorption); finalize after the last.  ``ride``: the
        pend of a decode dispatch staged in THIS tick, for the wave's
        landing to ride (:meth:`_finalize_inflight`).  Returns True when the
        wave landed, or will with ``ride``'s landing."""
        inf = self._inflight
        chunk = inf["chunk"]
        R = len(inf["wave"])
        idx = inf["idx"]
        sk, sv = inf["scratch"]
        tok_chunk = jnp.asarray(
            inf["arrays"]["tokens"][:, idx * chunk:(idx + 1) * chunk]
        )
        started = self.stats.enter(ENQUEUE, self._enq_seq + 1)
        if self._empty_at is not None:  # the chunk is the first program after a drain
            self._observe_gap(started)
        sk, sv, logits, *wstate = self._chunk_jit(chunk, R)(
            self.params, sk, sv, tok_chunk, jnp.int32(idx * chunk),
            *self._wave_state_args(inf), **self._moe_kw(inf, decode=False),
        )
        self._note_chunk(idx * chunk, chunk, inf["bucket"], inf["arrays"]["true_lens"])
        inf["scratch"] = (sk, sv)
        if self._recurrent:
            inf["wstate"] = wstate.pop(0)
        if self._moe:
            inf["wmoe"] = wstate.pop(0)
        inf["idx"] = idx + 1
        self._journal.append(
            flightrec.EV_PREFILL_CHUNK, None, -1, inf["idx"], inf["n_chunks"]
        )
        if inf["idx"] < inf["n_chunks"]:
            return False
        return self._finalize_inflight(logits, ride)

    def _finalize_inflight(self, logits: Any, ride: "dict | None" = None) -> bool:
        """The chunked wave's landing (last chunk done): the finalize jit,
        then the wave's first tokens — shared by the legacy and ragged
        lanes.  ``logits`` is the final chunk's output, passed through
        (never stored on the inflight dict — a [R, chunk, vocab] buffer
        pinned between ticks would double transient logits HBM on
        large-vocab configs).

        ``ride`` is the pend of a dispatch staged in THIS tick (the fused
        launch that carried the last chunk, or the plain decode launch its
        own invocation followed).  With one, NO sync here: what the landing
        needs hangs on that pend, the first tokens come down in the same
        ``_sync_host`` as its token block one tick later
        (:meth:`_land_decode`), with the next dispatch already queued, and
        the serve loop activates the wave meanwhile — its rows decode from
        the ``last`` the finalize program wrote, pure device dataflow, as
        ``done_prev`` is.  A sync here would return onto an EMPTY device and
        leave it so for a whole dispatch's worth of host work.  Without one
        (a wave onto an engine with no active rows, the legacy chunked lane,
        the drafter's lockstep lane) there is nothing to keep the device
        busy behind the landing, and it stays the one host sync per wave."""
        inf = self._inflight
        wave, bucket = inf["wave"], inf["bucket"]
        arrays = inf["arrays"]
        R = len(wave)
        sk, sv = inf["scratch"]
        fn = self._finalize_jit(bucket, R, arrays["sampled"])
        args = [
            self._k, self._v, sk, sv, self._last, self._lens,
            jnp.asarray(arrays["slots"]),
            jnp.asarray(arrays["true_lens"]),
            logits,
            *self._sampling_state_args(arrays),
        ]
        if self._paged:
            args += self._paged_wave_args(wave, bucket)
        # the landing's inputs count as its enqueue: the launch that came
        # just before it left the clock in that phase
        self.stats.enter(ENQUEUE, self._enq_seq + 1)
        (
            self._k, self._v, tables, self._last, self._lens,
            self._slot_keys, self._temp, self._top_k, self._top_p, firsts,
            *landed,
        ) = fn(*args, **self._state_kw(inf["wstate"]))
        seq = self._enq_seq
        self._note_state_landed(landed)
        if self._paged:
            self._tables = tables
        self._mirror_wave_lens(wave, arrays["true_lens"])
        landing = dict(
            wave=wave, true_lens=arrays["true_lens"], firsts=firsts, seq=seq,
            started=inf["started"], wmoe=inf["wmoe"],
        )
        if ride is not None:
            ride["landing"] = landing
            # the finalize program writes these rows' slots and pages: until
            # it is proved, a row that retires frees them at ``ride``'s landing
            ride["slot_set"].update(r.slot for r in wave if r.slot != -1)
            # the dispatch whose landing brings the wave's first tokens
            self._unproved[-1]["wave_landed"] = 1
            return True
        self._sync_began = self.stats.enter(SYNC, seq)
        # blocking-ok: the designated LANDING sync of a wave with no dispatch
        # to ride — nothing else will bring its first tokens to the host for
        # delivery and real TTFT attribution
        firsts = np.asarray(firsts)  # sync before timing (real latency)
        self._wave_landed(landing, firsts, landing["wmoe"], self._landed(seq, wave=True))
        return True

    # ------------------------------------------------- ragged unified waves
    # (ISSUE 6; arXiv:2604.15464) ONE scheduler lane: each pass enqueues a
    # single fused dispatch that advances the active decode rows AND the
    # inflight admission wave's next prefill chunk.  The last on-TPU bench
    # measured mean_batch_occupancy 0.365 — nearly two thirds of every
    # decode dispatch was idle compute; the ragged wave spends exactly
    # that slack on prefill, under an explicit token budget.

    async def _ragged_pass(self) -> bool:
        """One pass of the unified lane: form a wave when none is in
        flight (width capped by the token budget — occupancy-driven
        admission), then advance decode + chunk through one fused tick.
        Returns False only when there was nothing at all to do."""
        progressed = False
        now = self.stats.enter(ADMIT)
        attempted = self._inflight is None
        if attempted:
            formed = self._form_wave()
            if formed is not None:
                self._start_inflight_wave(*formed)
                progressed = True
        self._note_admission(now, attempted)
        if (
            self._active or self._inflight is not None
            or self._pend is not None
        ):
            finished = await self._offload(self._ragged_tick)
            if finished:
                self.stats.enter(ADMIT)
                wave = self._inflight["wave"]
                self._inflight = None
                self._activate_wave(wave)
            progressed = True
        return progressed

    @hotpath
    def _ragged_tick(self) -> bool:
        """One tick of the unified lane (decode-thread context): launch
        the fused (or decode-only) dispatch, then land the previous one —
        the same double-buffered shape as :meth:`_decode_tick`, with the
        admission wave riding the launch and its first tokens the landing.
        Returns True when the inflight wave's finalize program is enqueued
        (the serve loop activates the wave): its first tokens are down
        already where there was no dispatch to ride, and come down with
        THIS tick's dispatch, at the next tick's landing, where there was."""
        if self._drafter is not None:
            # speculation stays lockstep (the host drafter needs landed
            # history to propose), so there is no launch to fuse the
            # chunk into — the wave still rides THIS lane, one scheduler
            # pass, advancing right after the verify sync
            if self._active:
                self._spec_decode_tick()
            if self._inflight is not None:
                return self._advance_inflight()
            return False
        if self._chaos is not None and self._active:
            self._chaos("dispatch")
        pend = self._pend
        finished = False
        if self._active:
            finished = self._launch_ragged()
        else:
            self._pend = None
            if self._inflight is not None:
                finished = self._advance_inflight()
        if pend is not None:
            deliveries = self._land_decode(pend)
            if not self._active:
                # the landing retired every participant: drain the
                # follow-up before a consumer can observe completion
                # (the same invariant _decode_tick keeps)
                self._drain_decode()
            if deliveries:
                self._loop.call_soon_threadsafe(_deliver_batch, deliveries)
        return finished

    def _absorb_fits(self) -> bool:
        """May THIS dispatch absorb the inflight wave's next chunk?  The
        budget arithmetic lives in :mod:`calfkit_tpu.inference.ragged`."""
        inf = self._inflight
        return inf is not None and ragged_math.fits_budget(
            self._ragged_budget, len(self._active),
            self.runtime.decode_steps_per_dispatch,
            len(inf["wave"]), inf["chunk"],
        )

    def _ragged_wave_cap(self, bucket: int) -> int:
        """Admission-width bound at FORMATION time: how many prefill rows
        the budget lets a dispatch absorb alongside the current decode
        load.  Uses the wave's ACTUAL per-dispatch chunk —
        min(prefill_chunk, bucket) — so short-bucket waves are not
        admitted narrower than the budget allows (the same chunk
        ``_absorb_fits`` later charges).  Legacy mode returns the batch
        width (no extra bound)."""
        if not self._ragged:
            return self.runtime.max_batch_size
        return ragged_math.wave_width_cap(
            self._ragged_budget, len(self._active),
            self.runtime.decode_steps_per_dispatch,
            min(self.runtime.prefill_chunk, bucket),
        )

    def _launch_ragged(self) -> bool:
        """Enqueue ONE dispatch for this tick — fused decode+chunk when a
        wave is in flight and the token budget admits it, else plain
        decode (with the over-budget chunk advancing in its own
        invocation so admission never starves).  NO host sync anywhere on
        this path, the landing of a wave whose last chunk it carries
        included: the fused outputs, and that wave's first tokens, ride
        ``self._pend`` to the next tick's landing exactly like a plain
        overlapped launch."""
        inf = self._inflight
        if inf is None or not self._absorb_fits():
            self._launch_decode()
            if inf is not None:
                return self._advance_inflight(self._pend)
            return False
        args, window, steps, sampled = self._decode_args()
        if steps < self.runtime.decode_steps_per_dispatch:
            self.stats.short_dispatches += 1
        chunk, idx = inf["chunk"], inf["idx"]
        R = len(inf["wave"])
        sk, sv = inf["scratch"]
        tok_chunk = jnp.asarray(
            inf["arrays"]["tokens"][:, idx * chunk:(idx + 1) * chunk]
        )
        started, queued = self._open_launch(steps)
        self._journal.append(
            flightrec.EV_RAGGED_WAVE, None, -1, len(self._active), R
        )
        # a model with recurrent layers: the slots' state goes in after the
        # chunk's arguments and comes back after ``done``; the wave's state
        # comes back last
        program = self._ragged_jit(window, steps, sampled, chunk, R)
        res = list(program(
            *args, sk, sv, tok_chunk, jnp.int32(idx * chunk),
            *_some(self._state), *self._wave_state_args(inf), **self._moe_kw(inf),
        ))
        seq = self._note_launch("ragged", program, steps, started, queued, R, R * chunk)
        self._note_chunk(idx * chunk, chunk, inf["bucket"], inf["arrays"]["true_lens"])
        if self._moe:
            inf["wmoe"] = res.pop()
        if self._recurrent:
            inf["wstate"] = res.pop()
        moe = self._keep_carried([res.pop(7) for _ in range(self._recurrent + self._moe)])
        (
            self._k, self._v, self._last, self._lens, toks, n_valid, done,
            sk, sv, logits,
        ) = res
        inf["scratch"] = (sk, sv)
        inf["idx"] = idx + 1
        self._journal.append(
            flightrec.EV_PREFILL_CHUNK, None, -1, inf["idx"], inf["n_chunks"]
        )
        self.stats.prefill_absorbed_tokens += R * chunk
        self.stats.unified_dispatches += 1
        self._stage_pend(toks, n_valid, done, steps, started, seq, extra_rows=R, moe=moe)
        if inf["idx"] == inf["n_chunks"]:
            return self._finalize_inflight(logits, self._pend)
        return False

    def _register_prefix_pages(self, request: GenRequest) -> None:
        """After landing: publish the request's freshly-written
        full-prompt pages into the prefix cache.  Ownership transfers
        from the allocator (so retirement can't free shared pages under
        later readers); the owning slot holds a reference until it
        retires.  Decode never writes these pages: its first write lands
        at position prompt_len, which lives past every registered page."""
        if request.slot == -1:  # retired during its own prefill
            return
        ps = self.runtime.page_size
        full = len(request.prompt) // ps
        if len(request.page_hashes) < full:
            # safety net only: _plan_prefix_reuse hashes every planned
            # request, so this recompute should be unreachable — but
            # registration must never index past a stale hash list
            from calfkit_tpu.inference.paged import chain_hashes

            request.page_hashes = chain_hashes(request.prompt, ps)
        reused = len(request.shared_pages)
        fresh: list[int] = []
        fresh_hashes: list = []
        for i in range(reused, full):
            page = request.pages[i]
            if self._prefix.register(request.page_hashes[i], page):
                fresh.append(page)
                fresh_hashes.append(request.page_hashes[i])
            # else: another request registered this chain position first;
            # this duplicate page stays private (slot-held, freed at
            # retire) — but LATER positions must still register: agent
            # fleets share a scaffold/system page 0 across sessions, and
            # stopping at the first collision used to mean only the
            # FIRST session's chain ever entered the cache (every other
            # session re-prefilled its whole prompt forever).  Chain
            # hashing keeps mixed-origin chains content-correct: equal
            # hash ⇒ equal page content ⇒ lookup may stitch them.
        if fresh:
            self._page_alloc.transfer_out(request.slot, fresh)
            self._prefix.acquire(fresh)
            # ownership transition mirrored in the ledger: the fresh
            # pages leave the slot's private count and enter chain
            # ownership at refcount 1 (this request's own reference)
            self._ledger.transfer(request.slot, fresh, fresh_hashes)
            request.shared_pages = request.shared_pages + fresh

    @hotpath
    def _decode_tick(self) -> None:
        """One scheduler tick of the short decode lane.

        Overlapped mode (``runtime.overlap_dispatch``, the default):
        enqueue dispatch N+1 FIRST, then sync + fan out dispatch N — the
        device computes N+1 while the host does N's bookkeeping, so the
        inter-dispatch device-idle bubble collapses to the launch-enqueue
        cost.  Lockstep mode is the reference oracle: launch, sync, fan
        out, with the host as the retirement authority."""
        if self._chaos is not None:
            self._chaos("dispatch")
        if not self.runtime.overlap_dispatch:
            self._decode_tick_lockstep()
            return
        pend = self._pend
        if self._active:
            self._launch_decode()
        else:
            self._pend = None
        if pend is not None:
            deliveries = self._land_decode(pend)
            if not self._active:
                # the landing retired every participant: the dispatch
                # launched moments ago is all zombies.  Land it NOW,
                # before any consumer can observe completion — a caller
                # whose generate() returned must find slots/pages fully
                # accounted (the lockstep invariant, kept under overlap)
                self._drain_decode()
            if deliveries:
                self._loop.call_soon_threadsafe(_deliver_batch, deliveries)

    def _drain_decode(self) -> None:
        """Land an in-flight dispatch whose participants have all retired
        or cancelled (nothing live left to launch for)."""
        pend, self._pend = self._pend, None
        if pend is not None:
            deliveries = self._land_decode(pend)
            if deliveries:
                self._loop.call_soon_threadsafe(_deliver_batch, deliveries)

    def _sync_host(self, arrays: Any, seq: "int | None" = None) -> Any:
        """THE designated device→host sync point of the dispatch loop —
        scripts/lint_hotpath.py bans blocking syncs everywhere else in the
        overlap-critical functions, so the double-buffering can't silently
        regress to one-sync-per-launch.  The phase clock reads ``sync``
        from here until the caller enters ``fanout`` (:meth:`_landed`, where
        ``seq``, the number of the program that made ``arrays``, is booked
        as proved; the long lane's stream keeps no such account)."""
        self._sync_began = self.stats.enter(SYNC, seq)
        if isinstance(arrays, tuple):
            # blocking-ok: THE designated sync point (see docstring)
            return tuple(np.asarray(a) for a in arrays)
        # blocking-ok: THE designated sync point (see docstring)
        return np.asarray(arrays)

    def _note_eva_steps(self, steps: int) -> None:
        """Count a decode dispatch of an EVA stack at launch (host arithmetic
        over the active rows' lengths, no sync): what each step's query has to
        read of its row's two caches, the chunks the dispatch's tokens complete
        and the windows they close."""
        cfg, stats = self.config, self.stats
        W, c = cfg.window_size, cfg.chunk_size
        lens = self._host_lens[list(self._active)].astype(np.int64)
        q = lens[:, None] + np.arange(steps)[None, :]  # [rows, steps] the queries' positions
        stats.decode_eva_window_tokens_read += int((q % W + 1).sum()) * cfg.n_layers
        stats.decode_eva_summaries_read += int((q // W).sum()) * (W // c) * cfg.n_layers
        stats.eva_chunks_pooled += int(((lens + steps) // c - lens // c).sum()) * cfg.n_layers
        stats.eva_windows_closed += int(((lens + steps) // W - lens // W).sum())

    def _decode_args(self) -> "tuple[list, int, int, bool]":
        """Assemble one decode dispatch's host-side inputs (shared by the
        overlap launch and the lockstep tick): returns (args, window,
        steps, sampled).  Pure host work — no device sync."""
        active_mask = np.zeros((self.runtime.max_batch_size,), bool)
        needed = 1
        page = self.runtime.page_size
        live_pages = live_rows = 0
        window_tokens = global_tokens = 0
        for slot in self._active:
            active_mask[slot] = True
            needed = max(needed, self._host_lens[slot])
            pages = -(-int(self._host_lens[slot]) // page)
            live_pages += pages
            live_rows += pages > 0
            if self._windowed and not self.config.eva:
                global_tokens += int(self._host_lens[slot])
                window_tokens += min(int(self._host_lens[slot]), self.config.sliding_window)
        # the ring covers in-dispatch growth; the window only needs to cover
        # what's already in the main cache
        window = self._window_bucket(int(needed))
        # admissions waiting AND a retirement in reach? shorten the dispatch
        # so the freed slot (and the waiter's prefill) isn't gated behind a
        # full tick; under saturation with no retirement near, full ticks
        # keep dispatch overhead amortized
        full = self.runtime.decode_steps_per_dispatch
        # length check only: this runs on the decode thread, and iterating
        # the deque (as _peek_pending does) races event-loop appends
        pending = bool(self._carry) or bool(self._pending)
        steps = (
            self._short_steps()
            if pending and self._retirement_near(full)
            else full
        )
        sampled = any(
            not self._effective_sampling(r).is_greedy
            for r in self._active.values()
        )
        if self._paged:
            self.stats.decode_pages_live += live_pages * steps
            self.stats.decode_rows_live += live_rows * steps
            self.stats.decode_pages_window += (
                self.runtime.max_batch_size * -(-window // page) * steps
            )
            if self.config.eva:
                self._note_eva_steps(steps)
            elif self._windowed:  # (a step later reads a token more a row: not counted)
                cfg = self.config
                self.stats.decode_window_tokens_read += window_tokens * steps * cfg.n_window_layers
                self.stats.decode_global_tokens_read += global_tokens * steps * cfg.n_global_layers
        prev = self._pend
        done_prev = prev["done_dev"] if prev is not None else self._done_zero
        stop_table, hard_end = self._retire_args()
        args = [self.params, self._k, self._v]
        if self._paged:
            args.append(self._tables)
        args += [
            self._last,
            self._lens,
            jnp.asarray(active_mask),
            done_prev,
            stop_table,
            hard_end,
            self._slot_keys,
            self._temp,
            self._top_k,
            self._top_p,
        ]
        return args, window, steps, sampled

    def _retire_args(self) -> "tuple[Any, Any]":
        """Device copies of the per-slot stop table + hard-bound lens —
        admission-time constants, re-uploaded only after an activation
        rewrote them (the launch path pays no per-dispatch transfer)."""
        if self._retire_dev is None:
            self._retire_dev = (
                jnp.asarray(self._stop_np), jnp.asarray(self._hard_end)
            )
        return self._retire_dev

    def _observe_gap(self, now: float) -> None:
        """The dispatch-gap bubble, observed immediately BEFORE each jit
        enqueue (``now``: the moment the clock entered ``enqueue``, after
        args prep — the device is idle through that prep too, so
        observing at tick entry would under-report): the host-side span
        since the sync that left the device known empty, which also adds up
        in ``starved_s``; zero while a program is still queued (the device
        never idled).  "Queued" is the device's queue (``_enq_seq`` against
        ``_done_seq``), not what the host has yet to land: the landing sync
        of a wave with no dispatch to ride proves every program before it;
        one that rides a dispatch is that dispatch's own landing and finds
        the next one queued.  Reset across idle periods — an empty engine
        waiting for work is not a bubble."""
        empty_at = self._empty_at
        if empty_at is None:
            self._observe("dispatch_gap_ms", 0.0)
        else:
            self._empty_at = None
            self.stats.starved_s += now - empty_at
            self._observe("dispatch_gap_ms", (now - empty_at) * 1000.0)

    def _open_launch(self, steps: int) -> "tuple[float, int]":
        """The clock's switch to ``enqueue`` for a dispatch's launch, ONE
        copy for the four launches: the moment, and how many programs stand
        enqueued and unproved before this one.  The gap is observed and the
        journal told here, immediately before the jit call."""
        queued = self._enq_seq - self._done_seq
        started = self.stats.enter(ENQUEUE, self._enq_seq + 1)
        self._observe_gap(started)
        self._journal.append(
            flightrec.EV_DISPATCH_LAUNCH, None, self._enq_seq + 1, steps, len(self._active)
        )
        return started, queued

    def _landed(self, seq: int, wave: bool = False) -> float:
        """The clock's switch from ``sync`` to ``fanout`` after a designated
        sync on the output of program ``seq``; returns the moment.  Books
        what the sync proved: ``_done_seq``, the ``engine.dispatch`` span of
        every dispatch up to ``seq`` (ended HERE: its own landing, or an
        earlier sync on a later program) and, where nothing is queued
        behind it, a drain.  ``wave``: the sync brought an admission wave's
        first tokens down (``seq`` its finalize program): a drain where it
        was a sync of its own, a deferred landing where it rode a dispatch's
        and found the next one queued.  A sync on a program already proved
        (the host landing a dispatch that a wave's landing sync covered)
        proves nothing new."""
        now = self.stats.enter(FANOUT)
        if seq <= self._done_seq:
            return now
        self._done_seq = seq
        unproved = self._unproved
        if unproved and unproved[0]["seq"] <= seq:
            wait_ms = (now - self._sync_began) * 1000.0
            while unproved and unproved[0]["seq"] <= seq:
                self._end_dispatch_span(unproved.popleft(), now, seq, wait_ms)
        if seq == self._enq_seq and (wave or self._active):
            # nothing queued behind it, and work to go on with: a drain
            self._empty_at = now
            self.stats.pipeline_drains += 1
            self.stats.pipeline_drains_wave += wave
        elif wave:
            self.stats.wave_landings_deferred += 1
        return now

    def _end_dispatch_span(self, d: dict, now: float, by: int, wait_ms: float) -> None:
        """One ``engine.dispatch`` span, recorded whole at the sync that
        proved the dispatch complete (``by``: that sync's program; dispatches
        that share it ended together, and only their sum is known).  A root
        span: a dispatch serves many requests.  ``exclusive_ms`` is the wall
        this dispatch alone occupied: its end less the later of its enqueue
        and the previous dispatch's end."""
        began = d.pop("started")
        exclusive_ms = (now - max(began, self._proved_at)) * 1000.0
        self._proved_at = now
        if not TRACER.enabled:
            return
        TRACER.start_span(
            "engine.dispatch", kind="engine", emitter=self._emitter, attrs=d, at=began,
        ).end(at=now, proved_by=by, wait_ms=wait_ms, exclusive_ms=exclusive_ms)

    def _note_launch(
        self, kind: str, program: "_Program", steps: int, started: float,
        queued_behind: int, chunk_rows: int = 0, chunk_tokens: int = 0,
    ) -> int:
        """A dispatch was just enqueued: open its ``engine.dispatch`` span
        (plain numbers, kept until the sync that proves it) and return its
        number.  ``queued_behind``: programs enqueued and not proved
        complete BEFORE this one (0: the device was known empty)."""
        seq = self._enq_seq
        built = getattr(program, "built_seq", None) == seq  # (a test's stand-in has none)
        self._unproved.append(dict(
            seq=seq, kind=kind, steps=steps, rows=len(self._active),
            **({"kv_pages_global_in_use": self.stats.kv_pages_global_in_use,
                "kv_pages_window_in_use": self.stats.kv_pages_window_in_use}
               if self._windowed else {}),
            chunk_rows=chunk_rows, chunk_tokens=chunk_tokens, wave_landed=0,
            first_use=int(built), build_ms=program.built_s * 1000.0 if built else 0.0,
            queued_behind=queued_behind,
            enqueue_ms=(time.perf_counter() - started) * 1000.0, started=started,
        ))
        return seq

    def _launch_decode(self) -> None:
        """Enqueue the next decode dispatch — NO host sync.  The previous
        dispatch's device-side done mask rides in as ``done_prev``, so a
        row that retired in the still-in-flight block is frozen out of
        this one by pure device dataflow (its slot and pages stay held
        until that block lands: one-dispatch-late retirement)."""
        args, window, steps, sampled = self._decode_args()
        if steps < self.runtime.decode_steps_per_dispatch:
            self.stats.short_dispatches += 1
        started, queued = self._open_launch(steps)
        program = self._decode_jit(window, steps, sampled)
        (
            self._k, self._v, self._last, self._lens, toks, n_valid, done,
            *state,
        ) = program(*args, *_some(self._state), **self._moe_kw())
        seq = self._note_launch("decode", program, steps, started, queued)
        self._stage_pend(
            toks, n_valid, done, steps, started, seq, moe=self._keep_carried(state))

    def _stage_pend(
        self, toks: Any, n_valid: Any, done: Any, steps: int,
        started: float, seq: int, extra_rows: int = 0, moe: Any = None,
    ) -> None:
        """Record a just-enqueued dispatch as the in-flight pend (host
        lens advance + the landing's snapshot) — ONE copy shared by the
        plain and fused launches, so the two lanes' retirement
        bookkeeping cannot drift.  ``extra_rows`` counts absorbed
        prefill rows (occupancy participants landed with the dispatch)."""
        if self._windowed:
            page, ring = self.runtime.page_size, self._ring_pages
            for slot in self._active:  # pages the rows' rings start anew in this dispatch
                old = (int(self._host_lens[slot]) - 1) // page
                self.stats.window_pages_given_back += max(
                    0, (int(self._host_lens[slot]) + steps - 1) // page - max(old, ring - 1))
        for slot in self._active:
            self._host_lens[slot] += steps
        self._pend = dict(
            toks_dev=toks,
            n_valid_dev=n_valid,
            done_dev=done,
            steps=steps,
            started=started,
            seq=seq,  # its number on the device's queue
            participants=list(self._active.items()),
            slot_set=set(self._active.keys()),
            deferred=[],
            extra_rows=extra_rows,
            moe_dev=moe,  # (counts, hit) of a model with routed experts
            # a wave whose finalize program was enqueued straight behind
            # this dispatch: what its landing needs (_finalize_inflight)
            landing=None,
        )

    def _land_decode(self, pend: dict) -> "list[tuple[asyncio.Queue, list]]":
        """Host side of a landed dispatch: ONE sync for the token block
        plus the device-computed retirement arrays, then batched fan-out.
        The device is the retirement authority here — ``n_valid`` bounds
        each row's delivery, ``done`` retires it.  Rows whose requests
        retired or cancelled while this dispatch was in flight are pad
        columns: discarded (counted as ``overlap_wasted_tokens``), with
        their deferred slot/page frees released now that nothing in
        flight can touch them.  Returns the deliveries — the CALLER posts
        them, possibly after draining an all-zombie follow-up, so a
        consumer never observes completion before accounting settles.

        A wave's landing riding this dispatch (``pend["landing"]``) comes
        down in the SAME sync, on its finalize program, and is fanned out
        FIRST: the wave's rows are in the dispatch after this one, so a
        request's first token is recorded and delivered before any later
        one of its own."""
        landing, moe_dev = pend["landing"], pend["moe_dev"] or ()
        arrays = (pend["toks_dev"], pend["n_valid_dev"], pend["done_dev"], *moe_dev)
        seq = pend["seq"]
        if landing is not None:
            arrays += (landing["firsts"], *(landing["wmoe"] or ()))
            seq = landing["seq"]
        block, n_valid, done, *rest = self._sync_host(arrays, seq)
        if moe_dev:
            self._note_moe(*rest[:len(moe_dev)], decode_steps=pend["steps"])
        now = self._landed(seq, wave=landing is not None)
        if landing is not None:
            firsts, *wmoe = rest[len(moe_dev):]
            self._wave_landed(landing, firsts, wmoe, now)
        # exclusive wall: the launch happened before the PREVIOUS sync
        # returned, so clip to the span this dispatch alone occupied —
        # decode_time_s must keep approximating device-busy time, not
        # double-count the overlapped bookkeeping
        start = pend["started"]
        if self._last_sync_t is not None and self._last_sync_t > start:
            start = self._last_sync_t
        self._last_sync_t = now
        steps = pend["steps"]
        # occupancy participants: decode rows PLUS any prefill rows the
        # ragged scheduler absorbed into this dispatch (they hold slots;
        # a bifurcated schedule would have burned a whole extra dispatch
        # on them) — mean_occupancy is the unified-wave fill metric
        self._note_dispatch(
            now - start, steps,
            n_rows=len(pend["participants"]) + pend.get("extra_rows", 0),
        )
        deliveries: list = []  # (queue, block): a block is (its landing, its tokens)
        block_cols = np.ascontiguousarray(block.T)  # [B, steps]
        wasted = 0
        for slot, request in pend["participants"]:
            if self._active.get(slot) is not request:
                # one-dispatch-late retirement: the row retired (or its
                # consumer cancelled) while this block was in flight — the
                # whole column is pad, and nothing may reach its queue
                wasted += steps
                continue
            count = int(n_valid[slot])
            items: list = block_cols[slot][:count].tolist()
            request.generated += count
            self.stats.decode_tokens += count
            if done[slot]:
                self._retire_slot(request)
                items.append(_DONE)
            if items:
                deliveries.append((request.out, (now, items)))
        if wasted:
            self.stats.overlap_wasted_tokens += wasted
        self._journal.append(
            flightrec.EV_DISPATCH_LAND, None, pend["seq"], steps, wasted
        )
        self._free_deferred(pend)
        if not self._active:
            self._last_sync_t = None  # idle boundary, not a bubble
        return deliveries

    def _free_deferred(self, pend: dict) -> None:
        """Release the slots/pages of requests that retired while ``pend``
        was in flight.  Deferred to the landing so an in-flight dispatch
        can never write through a freshly-reallocated page (and shared
        prefix pages stay referenced while a dispatch still reads them)."""
        for slot, shared, corr in pend["deferred"]:
            if self._prefix is not None and shared:
                self._journal.append(
                    flightrec.EV_PREFIX_REL, corr, slot, len(shared)
                )
                self._prefix.release(shared)
                self._ledger.release(shared)
            if self._paged:
                self._journal.append(flightrec.EV_PAGE_FREE, corr, slot)
                self._free_pages(slot)
            self._free.append(slot)
            self._journal.append(flightrec.EV_SLOT_FREE, corr, slot)

    @hotpath
    def _decode_tick_lockstep(self) -> None:
        """The lockstep reference path: launch, sync, fan out — with the
        HOST as the retirement authority (arbitrary-size stop sets).  The
        overlapped path must produce byte-identical token streams; keep
        this oracle intact."""
        args, window, steps, sampled = self._decode_args()
        started, queued = self._open_launch(steps)
        program = self._decode_jit(window, steps, sampled)
        (
            self._k, self._v, self._last, self._lens, toks, _n_valid, _done,
            *state,
        ) = program(*args, *_some(self._state), **self._moe_kw())
        seq = self._note_launch("decode", program, steps, started, queued)
        moe = self._keep_carried(state)
        for slot in self._active:
            self._host_lens[slot] += steps
        block = self._sync_host(toks, seq)  # [steps, B] — THE host sync per dispatch
        if moe is not None:
            self._note_moe(*moe, decode_steps=steps)
        self._last_sync_t = self._landed(seq)
        elapsed = self._last_sync_t - started
        self._note_dispatch(elapsed, steps)
        self._journal.append(flightrec.EV_DISPATCH_LAND, None, seq, steps, 0)
        if steps < self.runtime.decode_steps_per_dispatch:
            self.stats.short_dispatches += 1
        # fan tokens out with ONE event-loop marshal per dispatch: a
        # call_soon_threadsafe per token costs ~65 us of loop machinery
        # each, so bookkeeping runs here on the decode thread and the
        # queue puts cross threads as a single batch.  The common case —
        # no stop token in the block, bound not yet reached — ships the
        # whole column as one C-level tolist() with no per-token Python
        # loop.
        deliveries: list = []
        landed = self._last_sync_t
        block_cols = np.ascontiguousarray(block.T)  # [B, steps]
        for slot, request in list(self._active.items()):
            toks: list = block_cols[slot].tolist()
            # steps until a hard bound — the SAME formula the retire heap
            # predicts with (one authority, no drift)
            bound = max(0, self._retirement_bound(request))
            if not request.stop_tokens or not request.stop_tokens.intersection(toks):
                if bound > steps:
                    request.generated += steps
                    self.stats.decode_tokens += steps
                    deliveries.append((request.out, (landed, toks)))
                else:
                    # bound falls inside this block: deliver up to it, retire
                    items = toks[:bound]
                    request.generated += bound
                    self.stats.decode_tokens += len(items)
                    self._retire_slot(request)
                    items.append(_DONE)
                    deliveries.append((request.out, (landed, items)))
                continue
            # a stop token is present: per-token authority loop
            items = []
            for token in toks:
                if self._record_token(request, token, items):
                    break
            if items:
                deliveries.append((request.out, (landed, items)))
        if not self._active:
            self._last_sync_t = None
        if deliveries:
            self._loop.call_soon_threadsafe(_deliver_batch, deliveries)

    def _note_dispatch(
        self, elapsed: float, clock_steps: int,
        tokens_per_row: float | None = None,
        n_rows: int | None = None,
    ) -> None:
        """Per-dispatch clock + stats shared by the plain decode tick and
        the speculative verify tick — ONE copy of the occupancy/clock
        accounting so the two modes cannot drift.

        ``tokens_per_row`` is the latency denominator when it differs from
        the clock: a verify dispatch advances the clock by 1 but emits
        each row's accepted prefix, so its inter-token latency is wall
        over MEAN EMITTED per row, not wall over 1.  ``n_rows`` pins the
        occupancy numerator to the dispatch's actual participant count
        (under overlap the landing runs after newer admissions changed
        ``_active``)."""
        with self._retire_lock:
            self._decode_clock += clock_steps
        self._note_progress()  # every landed dispatch is watchdog progress
        self.stats.decode_dispatches += 1
        self.stats.decode_time_s += elapsed
        rows = n_rows if n_rows is not None else len(self._active)
        occupancy = rows / self.runtime.max_batch_size
        self.stats.occupancy_sum += occupancy
        self.stats.occupancy_hist[min(3, int(occupancy * 4))] += 1
        # latency telemetry: TWO O(1) observes per dispatch — inter-token
        # latency is dispatch wall over tokens-per-row, never a per-token
        # loop (the hot-path allocation budget is zero)
        denom = tokens_per_row if tokens_per_row else clock_steps
        # capacity timeline (ISSUE 19): one numeric sample per dispatch
        # landing — every input is an O(1) attribute read or two
        # multiply-adds (the analytic HBM roofline), appended lock-free
        if self._capacity_on:
            self._sampler.append(
                self._ledger.pages_in_use,
                self._page_alloc.free_pages if self._paged else 0,
                self._ledger.prefix_resident_pages,
                rows,
                len(self._pending),
                float(denom) * rows,
                capacity.hbm_bytes_per_token(
                    self._hbm_constants, self._hbm_ctx, max(rows, 1)
                ) + self._hbm_state,
            )
        self._observe("decode_dispatch_ms", elapsed * 1000.0)
        # the advert's many-router tiebreak signal (ISSUE 10 satellite):
        # one multiply-add per dispatch, folded here so both lanes and
        # the spec tick feed the same EWMA
        self.stats.note_dispatch_ewma(elapsed * 1000.0)
        self._observe("inter_token_ms", elapsed * 1000.0 / max(1.0, denom))
        self._update_active_gauge()
        self._sync_metric_counters()

    def _update_active_gauge(self) -> None:
        """The process gauge sums across live engines (last-writer-wins
        would let an idle engine zero out a busy one's count).  Called per
        dispatch AND per retirement — without the retirement update an
        idle engine would pin its final in-flight count forever.  The
        running check sits INSIDE the lock so stop()'s pop (which runs
        after _running flips) can never interleave between the check and
        the insert and leave a stale re-inserted entry."""
        with _ACTIVE_LOCK:
            if not self._running:
                return
            _ACTIVE_BY_ENGINE[id(self)] = len(self._active)
            total = sum(_ACTIVE_BY_ENGINE.values())
        self.metrics["active_requests"].set(total)

    def _observe(self, key: str, value: float) -> None:
        """One latency observation, recorded twice (both O(1)): the
        process-shared instrument feeds the /metrics exposition, the
        per-engine one feeds this engine's advert percentiles."""
        self.metrics[key].observe(value)
        self.latency[key].observe(value)

    def _sync_metric_counters(self) -> None:
        """Fold cumulative stats into the process-registry counters as
        increments (called per dispatch + at snapshot time; at most one
        dispatch of lag, O(1) work).  Locked: the decode thread (via
        _note_dispatch) and the event-loop heartbeat (via stats_snapshot)
        both run this — an unlocked read-inc-write would double-count."""
        m, counted, stats = self.metrics, self._counted, self.stats
        with self._counted_lock:
            for key in _SYNCED_FIELDS:
                value = getattr(stats, key)
                if value != counted[key]:
                    m[key].inc(value - counted[key])
                    counted[key] = value
            if self._windowed:
                m["kv_pages_global_in_use"].set(stats.kv_pages_global_in_use)
                m["kv_pages_window_in_use"].set(stats.kv_pages_window_in_use)

    @hotpath
    def _spec_decode_tick(self) -> None:
        """One speculative wave: draft up to k tokens per active request
        (host-side n-gram lookup or the draft model), verify all of them
        plus the next position in ONE target dispatch, emit each row's
        accepted prefix + correction token.  Replaces ``_decode_tick``
        when ``RuntimeConfig.speculative`` is set; everything downstream
        (fan-out batching, deferred frees) is shared.

        Speculation stays LOCKSTEP even when ``overlap_dispatch`` is on:
        the host-side drafter needs the landed tokens of dispatch N to
        propose for N+1, so there is nothing correct to pre-launch.  The
        per-row retirement authority still moves to the device (the
        verify jit returns n_valid/done via the same
        ``sampler.retire_mask_slots``), keeping one classification code
        path across both modes.
        """
        spec = self._spec
        B = self.runtime.max_batch_size
        active_mask = np.zeros((B,), bool)
        max_len = 1
        for slot in self._active:
            active_mask[slot] = True
            max_len = max(max_len, int(self._host_lens[slot]))
        window = self._window_bucket(max_len)
        # wave-width ceiling: k drafts + 1 correction, shrunk so no row's
        # chunk can write past max_seq (a clamped dynamic_update_slice
        # would slide BACKWARD over valid history — unlike the dense
        # decode ring, where overshoot only ever lands beyond a retiring
        # row's valid length)
        cap = max(1, min(spec.k + 1, self.runtime.max_seq_len - max_len))
        # draft FIRST, then size the wave to the longest actual proposal:
        # ticks where the drafter finds nothing dispatch a 1-wide verify
        # (a plain decode step), not a k+1-wide one
        proposals: dict[int, list[int]] = {}
        max_nd = 0
        if cap > 1:
            entries = [
                (slot, request.history)
                for slot, request in self._active.items()
            ]
            for (slot, _), proposal in zip(
                entries, self._drafter.propose(entries)
            ):
                proposal = proposal[: cap - 1]
                proposals[slot] = proposal
                max_nd = max(max_nd, len(proposal))
        S = min(cap, max_nd + 1)
        drafts = np.zeros((B, S - 1), np.int32)
        ndraft = np.zeros((B,), np.int32)
        for slot, proposal in proposals.items():
            drafts[slot, : len(proposal)] = proposal
            ndraft[slot] = len(proposal)
        sampled = any(
            not self._effective_sampling(r).is_greedy
            for r in self._active.values()
        )
        started, queued = self._open_launch(S)  # drafting was prep too
        args = [self.params, self._k, self._v]
        if self._paged:
            args.append(self._tables)
        args += [
            self._last,
            self._lens,
            jnp.asarray(active_mask),
            jnp.asarray(drafts),
            jnp.asarray(ndraft),
            *self._retire_args(),
            self._slot_keys,
            self._temp,
            self._top_k,
            self._top_p,
        ]
        program = self._verify_jit(window, S, sampled)
        (
            self._k, self._v, self._last, self._lens, out_toks, emitted,
            n_valid, done,
        ) = program(*args)
        seq = self._note_launch("verify", program, S, started, queued)
        out_toks, emitted, n_valid, done = self._sync_host(
            (out_toks, emitted, n_valid, done), seq
        )  # [B, S] + retirement arrays — THE host sync
        self._last_sync_t = self._landed(seq)
        elapsed = self._last_sync_t - started
        # clock: one verify forward ≈ one decode step of wall time; the
        # heap horizon only drives the non-spec short-dispatch lever, so
        # a coarse clock is fine here.  Inter-token latency, however, must
        # divide by what each row actually EMITTED (accepted prefix +
        # correction), or acceptance would inflate the reported latency.
        n_active = len(self._active)
        self._note_dispatch(
            elapsed, 1,
            tokens_per_row=float(emitted.sum()) / n_active if n_active else 1.0,
        )
        # spec stays lockstep, so the verify sync IS the landing: one
        # event carries the wave's draft offer vs what actually emitted
        self._journal.append(
            flightrec.EV_SPEC_TICK, None, -1, int(ndraft.sum()),
            int(emitted.sum()),
        )
        deliveries: list = []
        landed = self._last_sync_t
        for slot, request in list(self._active.items()):
            count = int(emitted[slot])
            self._host_lens[slot] += count
            self.stats.spec_proposed += int(ndraft[slot])
            self.stats.spec_accepted += count - 1
            self.stats.spec_emitted += count
            self.stats.spec_rows += 1
            # device retirement authority: deliver the classified prefix,
            # retire on the device-computed done flag (same math as
            # _record_token's loop, computed once on device)
            valid = int(n_valid[slot])
            items: list = out_toks[slot, :valid].tolist()
            if request.history is not None:
                request.history.extend(items)
            request.generated += valid
            self.stats.decode_tokens += valid
            if done[slot]:
                self._retire_slot(request)
                items.append(_DONE)
            if items:
                deliveries.append((request.out, (landed, items)))
        if not self._active:
            self._last_sync_t = None
        if deliveries:
            self._loop.call_soon_threadsafe(_deliver_batch, deliveries)

    def _retire_slot(self, request: GenRequest) -> None:
        """Reclaim a short-lane request's slot + page reservation and drop
        the retire-heap's reference.  Bookkeeping runs BEFORE any _DONE
        signal reaches the consumer: once completion is observable, the
        slot is already free (no window where a finished request still
        occupies ``_active``).

        Overlap: when a launched-but-not-landed dispatch still covers this
        slot, the RESOURCE frees (page reservation, shared-page refcounts,
        the free-list slot) defer to that dispatch's landing — an in-flight
        dispatch must never find its pages re-allocated under it, nor its
        shared prefix pages evicted while it still reads them.  Everything
        observable (``_active``, the retire heap, the gauge) updates now."""
        self._drop_deadline(request)
        self._drop_lease(request)
        self._active.pop(request.slot, None)
        if self._drafter is not None and request.slot != -1:
            self._drafter.retire(request.slot)
        pend = self._pend
        if pend is not None and request.slot in pend["slot_set"]:
            # one-dispatch-late retirement: observable state updates now,
            # resource frees ride to the in-flight dispatch's landing —
            # the journal records BOTH moments (RETIRE_DEFER here, the
            # slot/page frees in _free_deferred)
            self._journal.append(
                flightrec.EV_RETIRE_DEFER, request.corr, request.slot,
                request.generated,
            )
            # the deferred tuple carries the OWNER (corr): the landing's
            # frees must attribute to the request whose pages they are,
            # in the journal and the capacity ledger alike (ISSUE 19)
            pend["deferred"].append(
                (request.slot, request.shared_pages, request.corr)
            )
            request.shared_pages = []
            request.slot = -1
            self._untrack_retirement(request)
            self._update_active_gauge()
            return
        self._journal.append(
            flightrec.EV_RETIRE, request.corr, request.slot, request.generated
        )
        if self._paged:
            if self._prefix is not None and request.shared_pages:
                # shared pages return to the CACHE (refcount), never to
                # the free list while other readers may hold them
                self._journal.append(
                    flightrec.EV_PREFIX_REL, request.corr, request.slot,
                    len(request.shared_pages),
                )
                self._prefix.release(request.shared_pages)
                self._ledger.release(request.shared_pages)
                request.shared_pages = []
            self._journal.append(
                flightrec.EV_PAGE_FREE, request.corr, request.slot
            )
            self._free_pages(request.slot)
        self._free.append(request.slot)
        self._journal.append(
            flightrec.EV_SLOT_FREE, request.corr, request.slot
        )
        request.slot = -1
        self._untrack_retirement(request)
        self._update_active_gauge()

    def _record_token(
        self, request: GenRequest, token: int, items: list, *,
        long: bool = False,
    ) -> bool:
        """THE retirement authority (VERDICT r3 weak #3: this logic used to
        live in three divergent copies).  Every generated token — prefill
        first token, short-lane decode fan-out slow path, long lane — flows
        through here: bump ``generated``, classify stop/exhaustion, reclaim
        the slot on retirement.  Appends deliverable tokens (and the _DONE
        sentinel) to ``items``; the caller owns marshalling ``items`` to
        the event loop.  Returns True when the request retired."""
        request.generated += 1
        hit_stop = token in request.stop_tokens
        if not hit_stop:
            items.append(token)
            self.stats.decode_tokens += 1
            if request.history is not None:  # speculation: drafter context
                request.history.append(token)
        if long:
            # the long lane has no slot and its sequence room is the
            # statically-sized fresh cache, enforced by long_new_cap
            done = hit_stop or request.generated >= request.max_new_tokens
            if done:
                # the short lane's RETIRE rides _retire_slot; the long
                # lane holds no slot, so its retirement is recorded here
                self._journal.append(
                    flightrec.EV_RETIRE, request.corr, -1, request.generated
                )
        else:
            # exhaustion == the retire heap's bound formula reaching zero
            # (one authority: heap prediction and actual retirement agree)
            done = hit_stop or self._retirement_bound(request) <= 0
            if done:
                self._retire_slot(request)
        if done:
            items.append(_DONE)
        return done
