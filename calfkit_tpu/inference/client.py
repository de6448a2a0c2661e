"""JaxLocalModelClient — the ModelClient that replaces remote HTTPS APIs.

This is the seam swap (reference: SURVEY.md §3.3 "THE SEAM THE TPU BACKEND
REPLACES"): `Agent(model=JaxLocalModelClient(...))` and every model turn runs
on the local device mesh through the continuous-batching engine.

Message rendering uses the HF chat template when a checkpoint tokenizer is
available, else a deterministic plain template.  Tool calling rides a JSON
grammar: the model is instructed to emit ``{"tool_name": ..., "args": ...}``
objects; responses are scanned for them (configurable via
``tool_call_parser``).
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, AsyncIterator, Callable

from calfkit_tpu.engine.model_client import (
    ModelClient,
    ModelRequestParameters,
    ModelSettings,
    ResponseDone,
    ResumeOffset,
    StreamEvent,
    TextDelta,
)
from calfkit_tpu.exceptions import InferenceError
from calfkit_tpu.models.capability import ToolDef
from calfkit_tpu.models.messages import (
    ModelMessage,
    ModelRequest,
    ModelResponse,
    RetryPart,
    SystemPart,
    TextOutput,
    ToolCallOutput,
    ToolReturnPart,
    Usage,
    UserPart,
)
from calfkit_tpu.models.payload import render_parts_as_text
from calfkit_tpu.observability import capacity as _capacity

ToolCallParser = Callable[[str], tuple[str, list[ToolCallOutput]]]

def default_tool_call_parser(text: str) -> tuple[str, list[ToolCallOutput]]:
    """Extract ``{"tool_name": ..., "args": {...}}`` objects (arbitrarily
    nested args) from the text; returns (remaining_text, calls)."""
    decoder = json.JSONDecoder()
    calls: list[ToolCallOutput] = []
    kept: list[str] = []
    i = 0
    while i < len(text):
        start = text.find("{", i)
        if start == -1:
            kept.append(text[i:])
            break
        obj = None
        try:
            obj, consumed = decoder.raw_decode(text, start)
        except ValueError:
            pass
        if isinstance(obj, dict) and isinstance(obj.get("tool_name"), str):
            args = obj.get("args", {})
            calls.append(
                ToolCallOutput(
                    tool_call_id=f"local_{int(time.time()*1000)}_{len(calls)}",
                    tool_name=obj["tool_name"],
                    args=args if isinstance(args, dict) else {},
                )
            )
            kept.append(text[i:start])
            i = consumed
        else:
            kept.append(text[i : start + 1])
            i = start + 1
    return "".join(kept).strip(), calls


def render_messages(
    messages: list[ModelMessage],
    params: ModelRequestParameters,
) -> str:
    """Deterministic chat rendering (the fallback template)."""
    lines: list[str] = []
    system: list[str] = []
    for message in messages:
        if isinstance(message, ModelRequest):
            if message.instructions:
                system.append(message.instructions)
            for part in message.parts:
                if isinstance(part, SystemPart):
                    system.append(part.content)
                elif isinstance(part, UserPart):
                    content = (
                        part.content
                        if isinstance(part.content, str)
                        else render_parts_as_text(part.content)
                    )
                    author = f" ({part.author})" if part.author else ""
                    lines.append(f"<|user|>{author}\n{content}")
                elif isinstance(part, ToolReturnPart):
                    lines.append(
                        f"<|tool_result|> {part.tool_name}: "
                        f"{json.dumps(part.content, default=str)}"
                    )
                elif isinstance(part, RetryPart):
                    lines.append(f"<|user|>\n[retry] {part.content}")
        else:  # ModelResponse
            text = message.text()
            calls = message.tool_calls()
            body = text
            for call in calls:
                args = call.args if isinstance(call.args, str) else json.dumps(call.args)
                body += f'\n{{"tool_name": "{call.tool_name}", "args": {args}}}'
            lines.append(f"<|assistant|>\n{body.strip()}")

    tools = params.all_tools()
    if tools:
        tool_block = "\n".join(
            f"- {t.name}: {t.description}\n  parameters: "
            f"{json.dumps(t.parameters_schema)}"
            for t in tools
        )
        system.append(
            "You can call tools by replying with a JSON object "
            '{"tool_name": "<name>", "args": {...}} on its own line.\n'
            f"Available tools:\n{tool_block}"
        )
    header = f"<|system|>\n{chr(10).join(system)}\n" if system else ""
    return header + "\n".join(lines) + "\n<|assistant|>\n"


def _pools_by_kind(stats: Any) -> dict:
    """The capacity breakdown's ``by_kind`` of a model with window layers
    (nothing for any other): each pool's pages reserved by live rows and in all."""
    if not stats.kv_pages_window_total:
        return {}
    return {"by_kind": {
        kind: {"pages_in_use": getattr(stats, f"kv_pages_{kind}_in_use"),
               "pages_total": getattr(stats, f"kv_pages_{kind}_total")}
        for kind in ("global", "window")}}


class JaxLocalModelClient(ModelClient):
    """Local inference over a JAX device mesh.

    Construction is cheap; device work (param init / checkpoint load,
    engine start) happens on first request or explicit :meth:`start`.
    """

    def __init__(
        self,
        *,
        checkpoint: str | None = None,
        config: Any = None,  # ModelConfig | preset name | None (from ckpt)
        runtime: Any = None,  # RuntimeConfig
        tokenizer: Any = None,
        sampling: Any = None,
        engine: Any = None,  # pre-built InferenceEngine (tests)
        tool_call_parser: ToolCallParser = default_tool_call_parser,
        max_new_tokens: int = 512,
        seed: int = 0,
        draft_checkpoint: str | None = None,  # speculative draft weights
        draft_params: Any = None,
    ):
        self._checkpoint = checkpoint
        self._config_spec = config
        self._runtime = runtime
        self._tokenizer = tokenizer
        self._sampling = sampling
        self._engine = engine
        self._parser = tool_call_parser
        self._max_new_tokens = max_new_tokens
        self._seed = seed
        self._draft_checkpoint = draft_checkpoint
        self._draft_params = draft_params
        self._start_lock: asyncio.Lock | None = None

    @property
    def model_name(self) -> str:
        if self._engine is not None:
            return self._engine.config.name
        if isinstance(self._config_spec, str):
            return self._config_spec
        if self._config_spec is not None:
            return self._config_spec.name
        return self._checkpoint or "jax-local"

    # ------------------------------------------------------------- startup
    async def start(self) -> None:
        def ready() -> bool:
            return (
                self._engine is not None
                and getattr(self._engine, "_running", False)
                and self._tokenizer is not None
            )

        if ready():
            return
        if self._start_lock is None:
            self._start_lock = asyncio.Lock()
        async with self._start_lock:
            if ready():
                return
            if self._engine is None:
                self._engine = await asyncio.to_thread(self._build_engine)
            await self._engine.start()
            if self._tokenizer is None:
                self._tokenizer = self._default_tokenizer()

    def _build_engine(self) -> Any:
        from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine
        from calfkit_tpu.inference.sharding import make_mesh, param_shardings

        runtime = self._runtime or RuntimeConfig()
        draft_params = self._draft_params
        if self._draft_checkpoint is not None and draft_params is None:
            if runtime.speculative is None or runtime.speculative.draft is None:
                # same loudness as the engine's draft_params validation: a
                # draft checkpoint that silently never loads would leave
                # the user speculating on the wrong drafter
                raise InferenceError(
                    "draft_checkpoint given but RuntimeConfig.speculative"
                    ".draft is unset — set SpecConfig(draft=<ModelConfig>)"
                )
            # the draft model loads through the SAME loader/sharding path
            # as the target (its own, smaller, config)
            from calfkit_tpu.inference.loader import load_params as _load

            draft_cfg = runtime.speculative.draft
            draft_params = _load(
                self._draft_checkpoint,
                draft_cfg,
                param_shardings(
                    draft_cfg, make_mesh(tp=runtime.tp, dp=runtime.dp)
                ),
            )
        params = None
        if self._checkpoint is not None:
            from calfkit_tpu.inference.loader import config_from_hf, load_params
            from calfkit_tpu.inference.tokenizer import HFTokenizer

            config = config_from_hf(self._checkpoint)
            mesh = make_mesh(tp=runtime.tp, dp=runtime.dp)
            shardings = param_shardings(config, mesh)
            if runtime.quantization in ("int8", "int4"):
                from calfkit_tpu.inference.quant import quantize_shardings

                shardings = quantize_shardings(
                    shardings,
                    bits=8 if runtime.quantization == "int8" else 4,
                )
            params = load_params(
                self._checkpoint,
                config,
                shardings,
                quantize=runtime.quantization,
            )
            if self._tokenizer is None:
                self._tokenizer = HFTokenizer(self._checkpoint)
            return InferenceEngine(
                config, runtime, params=params, mesh=mesh,
                sampling=self._sampling, seed=self._seed,
                draft_params=draft_params,
            )
        if isinstance(self._config_spec, str):
            config = preset(self._config_spec)
        elif self._config_spec is not None:
            config = self._config_spec
        else:
            raise InferenceError(
                "JaxLocalModelClient needs a checkpoint path or a config"
            )
        return InferenceEngine(
            config, runtime, sampling=self._sampling, seed=self._seed,
            draft_params=draft_params,
        )

    def _default_tokenizer(self) -> Any:
        from calfkit_tpu.inference.tokenizer import ByteTokenizer

        return ByteTokenizer()

    async def stop(self) -> None:
        if self._engine is not None:
            await self._engine.stop()

    def ready(self) -> "tuple[bool, str]":
        """Readiness probe for ``MetricsServer.set_readiness``: True only
        once the engine is BUILT (weights placed) and its scheduler task
        is running — distinct from liveness (``/healthz``), which is true
        from process start.  Cheap enough to call per scrape."""
        engine = self._engine
        if engine is None:
            return False, "engine not built (weights not loaded)"
        if not getattr(engine, "_running", False):
            return False, "engine not started"
        if getattr(engine, "_wedged", False):
            # the dispatch-progress watchdog tripped (ISSUE 9): the engine
            # is alive but the device grant is hung — /readyz flips false
            # and the heartbeat advert follows, so routers place nothing
            # new here and outstanding placements are declared dead
            return False, (
                "engine wedged: no dispatch progress for "
                f"{engine.runtime.watchdog_stall_s:.1f}s with work pending"
            )
        return True, "engine running"

    def stats_snapshot(self, *, window: bool = False) -> dict:
        """Live serving metrics (for the control-plane engine-stats advert);
        safe before start (zeros) — construction is intentionally cheap.

        ``window=True`` additionally reports per-interval rates since the
        PREVIOUS window=True call (``EngineStats.snapshot_and_delta`` —
        single-consumer by design: the heartbeat advert passes it; ad-hoc
        pollers must not, or they steal the heartbeat's intervals)."""
        engine = self._engine
        if engine is None:
            # engine builds lazily on first request: report the CONFIGURED
            # shape — with the SAME key set as the live branch (zeros for
            # the counters) so control-plane consumers never KeyError on a
            # cold engine
            from calfkit_tpu.inference.config import RuntimeConfig

            runtime = self._runtime or RuntimeConfig()  # mirror _build_engine
            return {
                "model_name": self.model_name,
                "platform": "",
                "tokens_per_second": 0.0,
                "mean_occupancy": 0.0,
                "active_requests": 0,
                "pending_requests": 0,
                "free_slots": runtime.max_batch_size,
                "max_batch_size": runtime.max_batch_size,
                "kv_layout": runtime.kv_layout,
                "prefill_tokens": 0,
                "decode_tokens": 0,
                "decode_dispatches": 0,
                "overlap_dispatch": runtime.overlap_dispatch,
                "overlap_wasted_tokens": 0,
                # ragged unified waves: the EFFECTIVE setting (the flag
                # engages only with chunked prefill + overlap dispatch)
                "ragged_waves": bool(
                    runtime.ragged_waves and runtime.chunked_prefill
                    and runtime.overlap_dispatch
                ),
                "prefill_absorbed_tokens": 0,
                "unified_dispatches": 0,
                "tokens_per_dispatch": 0.0,
                # overload protection: same key set as the live branch
                "max_pending": runtime.max_pending,
                "shed_requests": 0,
                "expired_requests": 0,
                # multi-tenant QoS (ISSUE 20): per-class splits of the
                # shed/expired counters plus per-class queued depth — the
                # routing tiebreak and `ck stats` per-class columns; same
                # key set as the live branch
                "interactive_shed": 0,
                "batch_shed": 0,
                "interactive_expired": 0,
                "batch_expired": 0,
                "interactive_pending": 0,
                "batch_pending": 0,
                "cancelled_requests": 0,
                "cancel_propagated": 0,
                "delivery_stalled": 0,
                # caller liveness (ISSUE 10) + router tiebreak: same key
                # set as the live branch
                "orphaned_requests": 0,
                "dispatch_ewma_ms": 0.0,
                # wedge watchdog (ISSUE 9): same key set as the live branch
                "wedged": False,
                "watchdog_trips": 0,
                "watchdog_faulted": 0,
                "flightrec": {"appended": 0, "dropped": 0, "dumped": 0},
                # capacity observatory (ISSUE 19): same key set as the
                # live branch — the CONFIGURED pool shape, zero occupancy
                "pages_total": (
                    runtime.pool_pages() - 1
                    if runtime.kv_layout == "paged"
                    else 0
                ),
                "pages_in_use": 0,
                "prefix_resident_pages": 0,
                "evictions_window": 0,
                "alloc_stalls": 0,
                "capacity": _capacity.PageLedger(
                    runtime.pool_pages() - 1
                    if runtime.kv_layout == "paged"
                    else 0
                ).breakdown(),
                "capacity_samples": {
                    "appended": 0, "dropped": 0, "dumped": 0,
                },
            }
        import jax

        stats = engine.stats
        rt = engine.runtime
        # multi-tenant QoS (ISSUE 20): per-class QUEUED depth for the
        # advert (cancelled entries excluded — a flagged shed victim
        # still sits in the deque until reaped, and advertising it as
        # depth would double-penalize the replica that just made room)
        queued = [*engine._pending, *engine._carry, *engine._long_pending]
        interactive_pending = sum(
            1 for r in queued if not r.cancelled and r.priority != "batch"
        )
        batch_pending = sum(
            1 for r in queued if not r.cancelled and r.priority == "batch"
        )
        snapshot = {
            "model_name": engine.config.name,
            "platform": jax.devices()[0].platform,
            "tokens_per_second": round(stats.tokens_per_second, 1),
            "mean_occupancy": round(stats.mean_occupancy, 4),
            "active_requests": len(engine._active),
            # admitted but not yet holding a slot: active + pending is the
            # fleet router's queue-depth load signal (ISSUE 7)
            "pending_requests": (
                len(engine._pending) + len(engine._carry)
                + len(engine._long_pending)
            ),
            "free_slots": len(engine._free),
            "max_batch_size": rt.max_batch_size,
            "kv_layout": rt.kv_layout,
            "prefill_tokens": stats.prefill_tokens,
            "decode_tokens": stats.decode_tokens,
            "decode_dispatches": stats.decode_dispatches,
            # overlapped execution: whether double-buffered dispatch is on,
            # and the pad tokens one-dispatch-late retirement discarded
            "overlap_dispatch": rt.overlap_dispatch,
            "overlap_wasted_tokens": stats.overlap_wasted_tokens,
            # ragged unified waves (ISSUE 6): whether the fused
            # prefill+decode lane is live, the chunk tokens it absorbed
            # into decode dispatches, and tokens processed per dispatch
            # (decode + absorbed — the win is measured, not asserted)
            "ragged_waves": engine._ragged,
            "prefill_absorbed_tokens": stats.prefill_absorbed_tokens,
            "unified_dispatches": stats.unified_dispatches,
            "tokens_per_dispatch": round(stats.mean_tokens_per_dispatch, 3),
            # overload protection (ISSUE 5): admission sheds, deadline
            # expiries, reaped consumer cancels (mesh-propagated subset),
            # and max_out_blocks stall-cancels
            "max_pending": rt.max_pending,
            "shed_requests": stats.shed_requests,
            "expired_requests": stats.expired_requests,
            # multi-tenant QoS (ISSUE 20): per-class shed/expired splits
            # and the per-class queued depth computed above
            "interactive_shed": stats.interactive_shed,
            "batch_shed": stats.batch_shed,
            "interactive_expired": stats.interactive_expired,
            "batch_expired": stats.batch_expired,
            "interactive_pending": interactive_pending,
            "batch_pending": batch_pending,
            "cancelled_requests": stats.cancelled_requests,
            "cancel_propagated": stats.cancel_propagated,
            "delivery_stalled": stats.delivery_stalled,
            # caller liveness (ISSUE 10): runs reaped because their
            # caller's lease lapsed — the `ck stats` ORPHANS column
            "orphaned_requests": stats.orphaned_requests,
            # per-dispatch latency EWMA: the advert's many-router
            # tiebreak signal (PowerOfTwoChoices breaks depth ties on it)
            "dispatch_ewma_ms": round(stats.dispatch_ewma_ms, 3),
            # wedge watchdog (ISSUE 9): whether the dispatch-progress
            # watchdog currently declares the engine wedged (the advert's
            # ready flag follows it) plus its lifetime trip/fault counts
            "wedged": engine._wedged,
            "watchdog_trips": stats.watchdog_trips,
            "watchdog_faulted": stats.watchdog_faulted,
            # flight-recorder ring accounting: overflow (dropped) must be
            # an observable signal, never silent truncation
            "flightrec": engine._journal.counts(),
            # capacity observatory (ISSUE 19): the advert's headroom
            # scalars (top-level so **snapshot reaches EngineStatsRecord)
            # + the full by-owner/by-chain attribution breakdown and the
            # sampler's ring accounting.  evictions_window is refined to
            # the heartbeat interval below when window=True.
            "pages_total": engine._ledger.pages_total,
            "pages_in_use": engine._ledger.pages_in_use,
            "prefix_resident_pages": engine._ledger.prefix_resident_pages,
            "evictions_window": stats.prefix_evictions,
            "alloc_stalls": stats.alloc_stalls,
            # (pools by cache kind: the one ledger above counts both in a layer's
            # pages; "by_kind" reads the two pools apart, in their own pages)
            "capacity": {**engine._ledger.breakdown(), **_pools_by_kind(stats)},
            "capacity_samples": engine._sampler.counts(),
        }
        try:
            # latency percentiles ride the advert for free: the registry's
            # fixed-bucket histograms already hold them.  Best-effort —
            # metrics must never fault the heartbeat.
            engine._sync_metric_counters()
            m = engine.latency  # per-ENGINE histograms: node-attributable
            snapshot["latency_ms"] = {
                name: round(m[hist].percentile(q), 3)
                for hist, label in (
                    ("ttft_ms", "ttft"),
                    ("inter_token_ms", "inter_token"),
                    ("queue_wait_ms", "queue_wait"),
                    ("prefill_ms", "prefill"),
                    ("dispatch_gap_ms", "dispatch_gap"),
                )
                for q, name in ((0.5, f"{label}_p50"), (0.99, f"{label}_p99"))
            }
            # per-interval rates since the previous heartbeat (the
            # windowing story for occupancy_hist + counters) — consumed
            # only when the single designated consumer asks
            if window:
                snapshot["window"] = engine.stats.snapshot_and_delta()[1]
                # the advert's eviction signal is PER-INTERVAL (lifetime
                # cumulative flattens toward the mean as uptime grows)
                snapshot["evictions_window"] = snapshot["window"].get(
                    "prefix_evictions", 0
                )
        except Exception:  # noqa: BLE001 - telemetry stays best-effort
            pass
        if rt.speculative is not None:
            snapshot["speculative"] = {
                "k": rt.speculative.k,
                "drafter": (
                    "draft-model" if rt.speculative.draft is not None
                    else "ngram"
                ),
                "spec_proposed": stats.spec_proposed,
                "spec_accepted": stats.spec_accepted,
                "acceptance_rate": round(stats.acceptance_rate, 4),
                "tokens_per_dispatch": round(stats.tokens_per_dispatch, 3),
            }
        if engine._paged:
            snapshot["free_pages"] = engine._page_alloc.free_pages
            if engine._prefix is not None:
                snapshot["prefix_cached_pages"] = engine._prefix.size
                snapshot["prefix_hits"] = stats.prefix_hits
                snapshot["prefix_reused_tokens"] = stats.prefix_reused_tokens
        try:  # accelerator memory pressure, where the backend reports it
            mem = jax.local_devices()[0].memory_stats() or {}
            if "bytes_in_use" in mem:
                snapshot["hbm_gb_in_use"] = round(
                    mem["bytes_in_use"] / 1e9, 3
                )
        except Exception:  # noqa: BLE001 - stats stay best-effort
            pass
        return snapshot

    # ------------------------------------------------------------- request
    async def request(
        self,
        messages: list[ModelMessage],
        settings: ModelSettings | None = None,
        params: ModelRequestParameters | None = None,
    ) -> ModelResponse:
        async for event in self.request_stream(messages, settings, params):
            if isinstance(event, ResponseDone):
                return event.response
        raise InferenceError("stream ended without a terminal response")

    async def request_stream(
        self,
        messages: list[ModelMessage],
        settings: ModelSettings | None = None,
        params: ModelRequestParameters | None = None,
    ) -> AsyncIterator[StreamEvent]:
        await self.start()
        params = params or ModelRequestParameters()
        settings = settings or ModelSettings()
        tokenizer = self._tokenizer
        prompt_text = render_messages(messages, params)
        prompt = [tokenizer.bos_id, *tokenizer.encode(prompt_text)]
        max_new = settings.max_tokens or self._max_new_tokens

        # decode-from-offset resume (ISSUE 10): the delivered prefix of a
        # failed-over stream enters as PREFILL — appended to the prompt,
        # so the survivor's prefix cache absorbs the shared prompt pages
        # and the chunk lane prefills only the continuation — and decode
        # produces ONLY the remaining budget.  The caller-side ledger
        # then dedupes nothing, because nothing is re-generated; under
        # greedy decode the continuation is byte-exact with an unkilled
        # run (round-trip tokenizers; BPE re-tokenization drift is
        # documented in docs/robustness.md).
        resume_tokens: list[int] = []
        prior = ""
        if settings.resume_text:
            resume_tokens = list(tokenizer.encode(settings.resume_text))
            prior = tokenizer.decode(resume_tokens)
            prompt = prompt + resume_tokens
            max_new = max(0, max_new - len(resume_tokens))

        def terminal(full_text: str, generated_tokens: int) -> ResponseDone:
            # ONE terminal builder for both exits (the resumed
            # spent-budget short-circuit below and the normal tail):
            # parser gating, parts assembly, and usage accounting must
            # not fork.  Resume usage semantics (documented in
            # docs/robustness.md): output_tokens counts what THIS
            # engine generated — a resumed run's delivered prefix is
            # input (it entered via prefill and was billed as output by
            # the attempt that generated it), so summing attempts never
            # double-counts the answer.
            remaining, calls = (
                self._parser(full_text)
                if params.tool_defs or params.output_tool
                else (full_text, [])
            )
            parts: list[Any] = []
            if remaining:
                parts.append(TextOutput(text=remaining))
            parts.extend(calls)
            return ResponseDone(
                ModelResponse(
                    parts=parts,
                    usage=Usage(
                        input_tokens=len(prompt),
                        output_tokens=generated_tokens,
                    ),
                    model_name=self.model_name,
                )
            )

        if settings.resume_text and max_new <= 0:
            # the delivered prefix already spent the whole token budget:
            # nothing to decode — the resumed stream is just its terminal
            yield ResumeOffset(len(prior))
            yield terminal(prior, 0)
            return

        # per-request sampling: each provided knob overrides that knob of
        # the engine default (top_p alone must NOT force greedy by zeroing
        # temperature); the engine batches mixed settings row-wise
        sampling = None
        if (
            settings.temperature is not None
            or settings.top_p is not None
            or settings.top_k is not None
        ):
            from calfkit_tpu.inference.sampler import SamplingParams

            base = self._engine.sampling
            temperature = (
                settings.temperature
                if settings.temperature is not None
                else base.temperature
            )
            if temperature <= 0.0 and settings.temperature is None and (
                settings.top_p is not None or settings.top_k is not None
            ):
                # filtering was requested but the default is greedy: sample
                # at T=1 so top_p/top_k actually apply
                temperature = 1.0
            sampling = SamplingParams(
                temperature=temperature,
                top_k=settings.top_k if settings.top_k is not None else base.top_k,
                top_p=settings.top_p if settings.top_p is not None else base.top_p,
            )
        stops = [s for s in settings.stop_sequences if s]
        # stop sequences cut host-side on decoded text; hold back enough of
        # the tail that a sequence spanning an emission boundary is never
        # already streamed when it completes
        holdback = max((len(s) for s in stops), default=1) - 1

        def first_stop(text: str) -> int:
            hits = [i for s in stops if (i := text.find(s)) != -1]
            return min(hits) if hits else -1

        # trace spans: the node kernel (or any caller) that set the trace
        # contextvar gets engine.generate with prefill/decode children;
        # untraced callers pay one contextvar read
        from calfkit_tpu.observability.trace import TRACER, current_context

        trace_parent = current_context.get()
        gen_span = prefill_span = decode_span = None
        if trace_parent is not None:
            gen_span = TRACER.start_span(
                "engine.generate",
                parent=trace_parent,
                kind="engine",
                emitter=f"engine/{self.model_name}",
                attrs={
                    "model": self.model_name,
                    "prompt_tokens": len(prompt),
                    "max_new_tokens": max_new,
                },
            )
            prefill_span = TRACER.start_span(
                "engine.prefill", parent=gen_span.context, kind="engine",
                emitter=gen_span.emitter,
            )

        # the stream's stage account: the engine's side books each block it
        # hands over (its dispatch's landing, the wait since), this side the
        # seconds making a text delta (``emit``) and the seconds suspended in
        # the consumer at the yield (``backpressure``), one clock read an
        # event a stage.  The totals go to the engine's ``stream_*`` counters
        # whether or not anyone traces; the request's own are what its
        # ``engine.decode`` span ends with
        import jax

        from calfkit_tpu.inference.engine import EMIT, StreamAccount

        account = StreamAccount()
        stats = self._engine.stats
        events = 0
        emit_s = backpressure_s = wait_before_s = 0.0
        started = time.perf_counter()
        generated: list[int] = []
        # a resumed stream's deltas begin past the already-delivered
        # prefix: everything before ``emitted`` chars is prefill, never
        # re-emitted (the ResumeOffset event tells consumers so)
        emitted = len(prior)
        stopped_at = -1
        ttft_ms = 0.0
        _EMIT_EVERY = 4  # re-decode cadence: bounds detokenize cost
        # the delivery's mesh deadline rides the same contextvar channel as
        # the trace: the node kernel set it from x-mesh-deadline, so the
        # engine enforces the caller's ABSOLUTE budget (reject expired at
        # admission, reap on expiry) with no per-layer arithmetic; the
        # caller's liveness lease (ISSUE 10) rides the identical channel
        # so the engine registers this run for the orphan reaper
        from calfkit_tpu import leases, qos
        from calfkit_tpu.cancellation import current_deadline

        if resume_tokens:
            yield ResumeOffset(len(prior))
        token_stream = self._engine.generate(
            prompt,
            max_new_tokens=max_new,
            stop_tokens=frozenset({tokenizer.eos_id}),
            sampling=sampling,
            seed=settings.seed,
            # the flight recorder joins on the same id the trace does, so
            # ``ck timeline <correlation-id>`` works from any log line
            corr=trace_parent.trace_id if trace_parent is not None else None,
            # run identity (ISSUE 19): the node kernel's x-mesh-run
            # contextvar, so the page ledger attributes HBM by run
            run=_capacity.current_run.get(),
            deadline=current_deadline.get(),
            lease=leases.current_lease.get(),
            # priority class (ISSUE 20): the node kernel's x-mesh-priority
            # contextvar — generate() resolves None/corrupt to the default
            # class via the one degradation law (qos.resolve_priority)
            priority=qos.current_priority.get(),
            # the queue wait is measured where it happens: the engine ends
            # an engine.queue span under this request's prefill span
            trace=prefill_span.context if prefill_span is not None else None,
            account=account,
        )
        stream_exc: BaseException | None = None
        try:
            async for token in token_stream:
                generated.append(token)
                if len(generated) == 1:
                    # the first token IS the TTFT moment — right after
                    # prefill; the decode phase starts here
                    decode_started = time.perf_counter()
                    ttft_ms = (decode_started - started) * 1000.0
                    wait_before_s = account.block_wait_s  # the first block's: prefill
                    if prefill_span is not None:
                        prefill_span.end(ttft_ms=round(ttft_ms, 3))
                        prefill_span = None
                        decode_span = TRACER.start_span(
                            "engine.decode", parent=gen_span.context,
                            kind="engine", emitter=gen_span.emitter,
                            # the dispatches that carried this request's
                            # decode are those numbered past this one
                            attrs={"first_seq": getattr(self._engine, "proved_seq", 0)},
                            at=decode_started,
                        )
                # the first token is emitted immediately; later ones batch
                # on the re-decode cadence
                if len(generated) % _EMIT_EVERY and len(generated) != 1:
                    continue
                # emit only the prefix that can't change: a trailing
                # replacement char may be a multi-byte sequence completing
                # (resume: the full text includes the prefilled prefix so
                # stop sequences spanning the resume boundary still cut)
                emit_at = time.perf_counter()
                with jax.profiler.TraceAnnotation(EMIT):
                    text = tokenizer.decode(resume_tokens + generated).rstrip("�")
                    if stops:
                        stopped_at = first_stop(text)
                        if stopped_at != -1:
                            break
                        text = text[: len(text) - holdback] if holdback else text
                    delta = TextDelta(text[emitted:]) if len(text) > emitted else None
                yield_at = time.perf_counter()
                emit_s += yield_at - emit_at
                if delta is not None:
                    yield delta
                    events += 1
                    backpressure_s += time.perf_counter() - yield_at
                    emitted = len(text)
        except BaseException as exc:
            # captured locally, NOT via sys.exc_info() in the finally:
            # exc_info also reports exceptions merely being HANDLED in an
            # enclosing frame (this generator's frames resume inside the
            # consumer's stack), which would mark clean streams as errors
            stream_exc = exc
            raise
        finally:
            # the stream has ended: its last token arrived in the block that
            # carried the end, so THIS is the moment the decode phase ends
            # (closing below takes the engine's lock and a scheduler pass)
            ended = time.perf_counter()
            last_seq = getattr(self._engine, "proved_seq", 0)
            # a break above abandons the stream; close NOW (not at GC) so
            # the engine reclaims the slot at its next tick
            await token_stream.aclose()
            # span status tells the truth about HOW the stream ended: an
            # in-flight exception (engine fault) is error, a consumer
            # abandoning the generator is cancelled, a break/return is ok
            status = (
                None if stream_exc is None
                else "cancelled"
                if isinstance(stream_exc, (GeneratorExit, asyncio.CancelledError))
                else "error"
            )
            if prefill_span is not None:  # zero tokens: no decode phase
                prefill_span.end(status=status)
            stats.stream_events += events
            stats.stream_emit_s += emit_s
            stats.stream_backpressure_s += backpressure_s
            if decode_span is not None:
                # the three stages are the span's children in all but name:
                # its duration less them is what the per-token iteration
                # costs.  The landings are offsets from the span's start (the
                # first block landed BEFORE its first token was consumed)
                decode_span.end(
                    status=status, at=ended, generated_tokens=len(generated),
                    last_seq=last_seq,
                    blocks=account.blocks, events=events,
                    first_landed_ms=round((account.first_landed - decode_started) * 1e3, 3),
                    last_landed_ms=round((account.last_landed - decode_started) * 1e3, 3),
                    deliver_wait_ms=round(account.deliver_wait_s * 1e3, 3),
                    deliver_wait_max_ms=round(account.deliver_wait_max_s * 1e3, 3),
                    block_wait_ms=round((account.block_wait_s - wait_before_s) * 1e3, 3),
                    emit_ms=round(emit_s * 1e3, 3),
                    backpressure_ms=round(backpressure_s * 1e3, 3),
                )
            if gen_span is not None:
                gen_span.end(
                    status=status,
                    generated_tokens=len(generated),
                    ttft_ms=round(ttft_ms, 3),
                )
        elapsed = time.perf_counter() - started

        full_text = tokenizer.decode(resume_tokens + generated)
        if stops and stopped_at == -1:
            stopped_at = first_stop(full_text)
        if stopped_at != -1:
            full_text = full_text[:stopped_at]
        if len(full_text) > emitted:
            yield TextDelta(full_text[emitted:])  # flush the tail
        yield terminal(full_text, len(generated))
