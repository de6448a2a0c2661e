"""Chaos scenarios (ISSUE 5): deterministic, scripted failure drills.

Each scenario injects an exact failure through the harness seams in
``tests/_chaos.py`` (engine ``_chaos`` hook, ``InMemoryMesh.chaos``
publish hook, the virtual deadline clock) and asserts the THREE
robustness invariants end to end:

1. failures surface as TYPED faults/exceptions (never silent hangs);
2. engine resources — slots, pages, shared-prefix refs — free within a
   BOUNDED number of ticks of the failure;
3. the flight recorder's timeline stays parseable and records the
   decision sequence (CANCEL/EXPIRE/SHED → frees, FAULT at a crash).

Catalog: caller-timeout storm (100 scripted runs), 2x admission
oversubscription, mid-stream engine fault, broker drop during return,
expired-on-arrival at a hop, engine deadline reap (queued AND active),
worker drain + bounded retry, and the max_out_blocks delivery stall.
"""

import asyncio
import threading
import time

import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu import cancellation, leases, protocol  # noqa: E402
from calfkit_tpu.client import Client  # noqa: E402
from calfkit_tpu.client.caller import RetryPolicy  # noqa: E402
from calfkit_tpu.engine import TestModelClient  # noqa: E402
from calfkit_tpu.exceptions import (  # noqa: E402
    ClientTimeoutError,
    DeadlineExceededError,
    EngineOverloadedError,
    NodeFaultError,
    RunOrphanedError,
    exception_for,
)
from calfkit_tpu.fleet import FleetRouter  # noqa: E402
from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.client import JaxLocalModelClient  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.mesh import InMemoryMesh  # noqa: E402
from calfkit_tpu.models.error_report import FaultTypes  # noqa: E402
from calfkit_tpu.nodes import Agent  # noqa: E402
from calfkit_tpu.observability import flightrec  # noqa: E402
from calfkit_tpu.worker import Worker  # noqa: E402

from tests._chaos import (  # noqa: E402
    BrokerChaos,
    ChaosScript,
    FleetTopology,
    ServingStubModel,
    assert_engine_drained,
    settle,
    virtual_clock,
)

CFG = preset("debug")


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _rt(**over):
    kw = dict(
        max_batch_size=4, max_seq_len=128, prefill_chunk=16,
        decode_steps_per_dispatch=4, page_size=16,
    )
    kw.update(over)
    return RuntimeConfig(**kw)


async def _collect(engine, prompt, n, **kw):
    """Consume a generate() stream to completion (or typed failure)."""
    return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]


def _journal_events(engine):
    return flightrec.parse_dump(engine._journal.dump_lines())


def _drained(engine, total_free_pages=None):
    """Settle predicate mirroring assert_engine_drained: the decode thread
    nulls ``_pend`` BEFORE _free_deferred returns the slot/pages, so the
    free list (and page pool) must be part of the condition — settling on
    the queues alone observes a state that is consistent one tick later."""
    return (
        not engine._active
        and engine._pend is None
        and engine._inflight is None
        and not engine._admitting
        and not engine._pending
        and not engine._carry
        and len(engine._free) == engine.runtime.max_batch_size
        and (
            total_free_pages is None
            or engine._page_alloc is None
            or engine._page_alloc.free_pages == total_free_pages
        )
    )


class TestCallerTimeoutStorm:
    """The acceptance scenario: a dead caller's work actually stops."""

    async def test_storm_100_runs_zero_leaked_slots(self, params):
        """100 scripted runs: one active + one queued request per run,
        both cancelled through the mesh fan-out entry point
        (``cancellation.propagate_cancel`` — what a ``cancel`` record
        reaching ANY node in the process invokes).  After every run the
        engine must be byte-for-byte drained: all slots free, all pages
        back, nothing queued.  Every 20th run the flight-recorder
        timeline is checked to end CANCEL → … → SLOT_FREE."""
        runtime = _rt(
            max_batch_size=1, kv_layout="paged", overlap_dispatch=True,
            flightrec_events=1 << 15,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            for run in range(100):
                corr_a = f"storm-{run}-active"
                corr_b = f"storm-{run}-queued"
                # gate the 2nd decode dispatch (ISSUE 11 flake fix): the
                # real decode thread races the cancel in host time, and
                # on a fast host a 64-token run could RETIRE before the
                # cancel landed — the scripted block pins every run
                # mid-generation until both cancels are flagged, so the
                # reap (not completion) is the only way out, every run
                gate = threading.Event()
                engine._chaos = ChaosScript().block_at("dispatch", 2, gate)
                try:
                    task_a = asyncio.create_task(
                        _collect(engine, [1, 2, 3 + run % 5], 64, corr=corr_a)
                    )
                    await settle(
                        lambda: engine._active,
                        message=f"run {run}: request never admitted",
                    )
                    task_b = asyncio.create_task(
                        _collect(engine, [7, 8], 64, corr=corr_b)
                    )
                    await settle(
                        lambda: len(engine._pending) + len(engine._carry)
                        == 1,
                        message=f"run {run}: second request never queued",
                    )
                    # the caller timed out: the mesh cancel fans out to
                    # every registered engine.  Both propagations run in
                    # ONE loop step — the queued entry cannot slip into
                    # admission between them.
                    flagged = cancellation.propagate_cancel(corr_a)
                    flagged += cancellation.propagate_cancel(corr_b)
                    assert flagged == 2, (
                        f"run {run}: fan-out flagged {flagged}"
                    )
                finally:
                    # ALWAYS release the pinned dispatch: a failed assert
                    # above must surface as the assert, not as a decode
                    # thread parked on gate.wait() hanging the whole run
                    gate.set()
                ticks = await settle(
                    lambda: _drained(engine, total_free),
                    message=f"run {run}: engine not drained after cancel",
                )
                assert ticks < 400
                assert_engine_drained(engine, total_free)
                # plain consumer-cancel ends the stream without error
                await task_a
                await task_b
                if run % 20 == 0:
                    events = _journal_events(engine)
                    tl = flightrec.timeline_events(events, corr_a)
                    names = [e["event"] for e in tl]
                    assert "CANCEL" in names, names
                    assert "SLOT_FREE" in names, names
                    assert names.index("CANCEL") < (
                        len(names) - 1 - names[::-1].index("SLOT_FREE")
                    ), f"CANCEL did not precede the final SLOT_FREE: {names}"
                    # the queued request never held a slot: its timeline
                    # is submit → cancel, nothing leaked to free
                    tl_b = flightrec.timeline_events(events, corr_b)
                    b_names = [e["event"] for e in tl_b]
                    assert "CANCEL" in b_names, b_names
            assert engine.stats.cancelled_requests == 200
            assert engine.stats.cancel_propagated == 200
            # the engine still serves after the storm
            assert len(await _collect(engine, [9], 8)) == 8
        finally:
            await engine.stop()

    async def test_client_timeout_cancels_engine_end_to_end(self, params):
        """client → mesh → worker node → engine: after a REAL
        ``ClientTimeoutError``, the cancel record crosses the mesh and
        the engine frees the request's slot and pages within bounded
        ticks.  The virtual clock is FROZEN so the engine-side deadline
        reaper cannot race the cancel — propagation is the only path
        that can reclaim the request."""
        runtime = _rt(
            max_batch_size=2, decode_steps_per_dispatch=1,
            kv_layout="paged", overlap_dispatch=True,
            flightrec_events=1 << 14,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        # throttle decode (runs OFF the event loop, in to_thread) so the
        # generation deterministically outlives the client timeout on any
        # CPU: >= 10ms per emitted token vs a 0.3s budget for 100 tokens
        throttle = ChaosScript()

        def pace(point):
            throttle(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = pace
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=100
        )
        with virtual_clock():
            mesh = InMemoryMesh()
            agent = Agent("slow", model=model)
            async with Worker([agent], mesh=mesh, owns_transport=True):
                client = Client.connect(mesh)
                handle = await client.agent("slow").start(
                    "take your time", timeout=0.3
                )
                with pytest.raises(ClientTimeoutError):
                    await handle.result()
                # the timeout published the cancel; it must reach THIS
                # engine and free everything within bounded ticks
                await settle(
                    lambda: engine.stats.cancel_propagated >= 1,
                    message="mesh cancel never reached the engine",
                )
                await settle(
                    lambda: _drained(engine, total_free),
                    message="engine did not drain after the mesh cancel",
                )
                assert_engine_drained(engine, total_free)
                assert engine.stats.expired_requests == 0  # frozen clock
                events = _journal_events(engine)
                tl = flightrec.timeline_events(
                    events, handle.correlation_id
                )
                names = [e["event"] for e in tl]
                assert "CANCEL" in names, names
                await client.close()


class TestOversubscription:
    async def test_2x_oversubscription_sheds_typed(self, params):
        """2x the engine's admission capacity arrives at once: the
        excess is refused with a typed, attributed
        ``EngineOverloadedError`` at submit (no device work), the
        admitted requests complete in full, and the journal carries one
        SHED per refusal."""
        runtime = _rt(
            max_batch_size=2, max_pending=2, overlap_dispatch=True,
            flightrec_events=1 << 12,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[
                    _collect(engine, [1 + i], 8, corr=f"over-{i}")
                    for i in range(8)
                ],
                return_exceptions=True,
            )
            shed = [r for r in results if isinstance(r, EngineOverloadedError)]
            served = [r for r in results if isinstance(r, list)]
            assert len(shed) + len(served) == 8
            assert shed, "2x oversubscription produced no sheds"
            assert served, "oversubscription shed everything"
            for exc in shed:
                assert exc.lane == "short"
                assert exc.limit == 2
                assert exc.pending >= 2
            for stream in served:
                assert len(stream) == 8, "an admitted request was starved"
            assert engine.stats.shed_requests == len(shed)
            sheds = [
                e for e in _journal_events(engine) if e["event"] == "SHED"
            ]
            assert len(sheds) == len(shed)
            # a shed is O(1) bookkeeping: the engine serves on
            assert len(await _collect(engine, [9], 8)) == 8
        finally:
            await engine.stop()

    async def test_shed_keeps_typed_code_across_the_mesh(self, params):
        """An engine shed crossing the agent's model-call wrap
        (``engine/turn.py``) must keep its ``mesh.overloaded`` code —
        not flatten into ``mesh.model_error`` — or caller-side retry
        can never classify it (regression: the wrap predates the
        authoritative error-type table)."""
        runtime = _rt(
            max_batch_size=1, max_pending=1, decode_steps_per_dispatch=1
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=32
        )
        mesh = InMemoryMesh()
        async with Worker(
            [Agent("svc", model=model)], mesh=mesh, owns_transport=True
        ):
            client = Client.connect(mesh)
            results = await asyncio.gather(
                *[
                    client.agent("svc").execute(f"p{i}", timeout=120)
                    for i in range(6)
                ],
                return_exceptions=True,
            )
            served = [r for r in results if not isinstance(r, BaseException)]
            faults = [r for r in results if isinstance(r, BaseException)]
            assert served, "oversubscription shed everything"
            assert faults, "2x oversubscription never shed over the mesh"
            for exc in faults:
                assert isinstance(exc, NodeFaultError), repr(exc)
                assert exc.report.error_type == FaultTypes.OVERLOADED, (
                    exc.report.error_type
                )
                assert RetryPolicy.retriable(exc)
            assert engine.stats.shed_requests == len(faults)
            await client.close()
        await engine.stop()


class TestMultiTenantQos:
    """ISSUE 20 chaos drills: the rate-limit admission gate and the
    priority-ordered shed, each with the three robustness invariants
    (typed faults, bounded resource free, auditable decision trail)."""

    async def test_rate_limited_tenant_storm_typed_and_drained(self, params):
        """A single tenant storms past its admission budget: the excess
        is refused at the NODE KERNEL with the typed RETRIABLE
        ``mesh.rate_limited`` fault (carrying tenant id + retry hint),
        the admitted calls complete in full, and the engine drains with
        zero leaked slots or pages — a refused call never touched the
        engine at all."""
        from calfkit_tpu.qos import TenantRateLimiter

        runtime = _rt(max_batch_size=4, max_pending=8)
        engine = InferenceEngine(CFG, runtime, params=params)
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=8
        )
        mesh = InMemoryMesh()
        # negligible refill over the test's wall time: exactly the burst
        # (2 calls) is admitted, everything after is refused
        limiter = TenantRateLimiter(rate_per_s=0.0001, burst=2)
        async with Worker(
            [Agent("svc", model=model)], mesh=mesh, owns_transport=True,
            qos=limiter,
        ):
            client = Client.connect(mesh)
            results = await asyncio.gather(
                *[
                    client.agent("svc").execute(f"p{i}", timeout=120)
                    for i in range(6)
                ],
                return_exceptions=True,
            )
            served = [r for r in results if not isinstance(r, BaseException)]
            faults = [r for r in results if isinstance(r, BaseException)]
            assert len(served) == 2, "burst admitted more than its budget"
            assert len(faults) == 4, "storm excess was not refused"
            for exc in faults:
                assert isinstance(exc, NodeFaultError), repr(exc)
                assert exc.report.error_type == FaultTypes.RATE_LIMITED, (
                    exc.report.error_type
                )
                # the budget refills on a known schedule: backoff-and-
                # retry is the right caller response, so the fault MUST
                # classify retriable
                assert RetryPolicy.retriable(exc)
                assert exc.report.data.get("tenant_id") == client.client_id
                assert float(exc.report.data["retry_after_s"]) > 0.0
            # a refused call never reached the engine: no shed, no
            # journal entry, and the engine drains clean
            assert engine.stats.shed_requests == 0
            await settle(
                lambda: _drained(engine), message="engine never drained"
            )
            assert_engine_drained(engine)
            await client.close()
        await engine.stop()

    async def test_interactive_preempts_queued_batch_never_reverse(
        self, params
    ):
        """The shed-order law, end to end at the engine: with the short
        lane full of batch work, arriving interactive submits evict
        QUEUED batch requests (typed retriable EngineOverloadedError
        with the full lane/pending/limit detail) and run in their
        place.  Zero interactive sheds while any batch request was
        sheddable — and the journal carries one SHED per eviction."""
        runtime = _rt(
            max_batch_size=2, max_pending=2, overlap_dispatch=True,
            flightrec_events=1 << 12,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            batch = [
                asyncio.ensure_future(
                    _collect(
                        engine, [1 + i], 32,
                        corr=f"bulk-{i}", priority="batch",
                    )
                )
                for i in range(2)
            ]
            # stage the backlog: let the first pair claim the slots
            # BEFORE queueing the next pair, or all four race into the
            # queue and bounded admission sheds the tail at submit
            await settle(
                lambda: len(engine._active) == 2,
                message="batch pair never went active",
            )
            batch += [
                asyncio.ensure_future(
                    _collect(
                        engine, [3 + i], 32,
                        corr=f"bulk-{2 + i}", priority="batch",
                    )
                )
                for i in range(2)
            ]
            # 2 batch active, 2 batch queued — the victim pool
            await settle(
                lambda: len(engine._pending) == 2,
                message="batch backlog never queued",
            )
            interactive = await asyncio.gather(
                *[
                    _collect(
                        engine, [9 + i], 8,
                        corr=f"chat-{i}", priority="interactive",
                    )
                    for i in range(2)
                ],
                return_exceptions=True,
            )
            batch_results = await asyncio.gather(
                *batch, return_exceptions=True
            )
            # every interactive request completed — none were shed
            for stream in interactive:
                assert isinstance(stream, list), repr(stream)
                assert len(stream) == 8
            victims = [
                r for r in batch_results
                if isinstance(r, EngineOverloadedError)
            ]
            assert len(victims) == 2, (
                "each interactive arrival must evict one queued batch "
                f"request, got {batch_results!r}"
            )
            for exc in victims:
                # the eviction carries the SAME typed detail a
                # shed-at-submit would (the drive-by uniformity law)
                assert exc.lane == "short"
                assert exc.limit == 2
                assert exc.pending >= 2
                # crossing the mesh this types as mesh.overloaded, which
                # is retriable — the caller's RetryPolicy re-drives the
                # preempted batch work
                from calfkit_tpu.exceptions import (
                    FAULT_TYPE_BY_EXCEPTION,
                    RETRIABLE_FAULT_TYPES,
                )

                assert (
                    FAULT_TYPE_BY_EXCEPTION[type(exc)]
                    in RETRIABLE_FAULT_TYPES
                )
            assert engine.stats.shed_requests == 2
            assert engine.stats.batch_shed == 2
            assert engine.stats.interactive_shed == 0, (
                "an interactive request was shed while batch work was "
                "sheddable — the shed-order law is broken"
            )
            sheds = [
                e for e in _journal_events(engine) if e["event"] == "SHED"
            ]
            assert len(sheds) == 2
            assert {e["corr"] for e in sheds} <= {f"bulk-{i}" for i in range(4)}
            await settle(
                lambda: _drained(engine), message="engine never drained"
            )
            assert_engine_drained(engine)
        finally:
            await engine.stop()


class TestMidStreamFault:
    async def test_injected_dispatch_fault_dumps_and_terminates(
        self, params, tmp_path, monkeypatch
    ):
        """A fault on the 3rd decode dispatch: consumers' streams
        terminate (no hang), the scheduler stops, and the fault dump is
        parseable JSONL whose final event is FAULT."""
        monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path))
        runtime = _rt(overlap_dispatch=True)
        engine = InferenceEngine(CFG, runtime, params=params)
        engine._chaos = ChaosScript().fail_at(
            "dispatch", 3, RuntimeError("injected mid-stream chaos fault")
        )
        await engine.start()
        got = await _collect(engine, [1, 2, 3], 64, corr="chaos-fault")
        assert len(got) < 64, "the injected fault never fired"
        await settle(lambda: not engine._running)
        dumps = sorted(tmp_path.glob("*.jsonl"))
        assert dumps, "no fault dump was written"
        events = flightrec.parse_dump(
            dumps[-1].read_text().splitlines()
        )
        assert events, "fault dump is not parseable"
        assert events[-1]["event"] == "FAULT"
        assert "chaos fault" in events[-1].get("note", "")
        assert any(e["event"] == "DISPATCH_LAUNCH" for e in events)
        await engine.stop()  # teardown after a crash is clean


class TestBrokerDropDuringReturn:
    async def test_dropped_return_times_out_and_publishes_cancel(self):
        """The broker loses the agent's return record: the caller gets a
        typed ``ClientTimeoutError`` (bounded wait, no hang) and its
        timeout publishes a ``cancel`` record that reaches in-process
        cancellation targets through the node."""
        mesh = InMemoryMesh()
        chaos = BrokerChaos().drop(kind="return")
        mesh.chaos = chaos
        seen_cancels: list[str] = []

        class _Target:
            def cancel_correlation(self, corr: str) -> int:
                seen_cancels.append(corr)
                return 0

        target = _Target()
        cancellation.register_cancel_target(target)
        agent = Agent(
            "echo",
            model=TestModelClient(custom_output_text="ok", call_tools="none"),
        )
        async with Worker([agent], mesh=mesh, owns_transport=True):
            client = Client.connect(mesh)
            handle = await client.agent("echo").start("hi", timeout=0.4)
            with pytest.raises(ClientTimeoutError):
                await handle.result()
            assert [kind for _, kind in chaos.dropped] == ["return"]
            # the publish is fire-and-forget off the timeout rail: settle
            # on it rather than asserting synchronously after the raise
            await settle(
                lambda: chaos.kinds_seen("cancel") >= 1,
                message="the timeout did not publish a mesh cancel",
            )
            await settle(
                lambda: handle.correlation_id in seen_cancels,
                message="the cancel record never fanned out at the node",
            )
            await client.close()


class TestConsumerCancelShortCircuit:
    async def test_cancel_record_never_reaches_consumer_fn(self):
        """A ``cancel``-kind record on a consumer's topic is a control
        record: it must fan out to cancellation targets — never run the
        user's fn, which the dispatcher's EXPRESS path would otherwise
        execute inline on the intake pull task."""
        from calfkit_tpu.mesh.transport import Record
        from calfkit_tpu.nodes import ConsumerNode

        seen_cancels: list[str] = []

        class _Target:
            def cancel_correlation(self, corr: str) -> int:
                seen_cancels.append(corr)
                return 1

        target = _Target()
        cancellation.register_cancel_target(target)
        calls: list = []
        node = ConsumerNode(
            lambda ctx: calls.append(ctx), name="watch", topics=["t.obs"]
        )
        await node._handle_delivery(
            Record(
                topic="t.obs",
                value=b"",
                key=b"task-1",
                headers={
                    protocol.HDR_KIND: "cancel",
                    protocol.HDR_CORRELATION: "corr-express",
                    protocol.HDR_TASK: "task-1",
                },
            )
        )
        assert calls == [], "consumer fn ran for a control record"
        assert seen_cancels == ["corr-express"]


class TestCancelTombstone:
    async def test_cancelled_before_delivery_faults_fast(self):
        """A cancel that lands while the call record is still in flight
        (queued behind a busy lane, on the wire) leaves a tombstone; the
        admission gate hits it and faults typed ``mesh.cancelled``
        instead of executing a full run for a caller that left."""
        mesh = InMemoryMesh()
        chaos = BrokerChaos()
        mesh.chaos = chaos
        ran: list[str] = []

        def _tap(topic: str, headers: dict) -> None:
            # the cancel "overtakes" the call deterministically: the
            # tombstone is recorded the instant the call crosses the
            # broker, before its delivery executes
            if headers.get(protocol.HDR_KIND) == "call" and "svc" in topic:
                cancellation.propagate_cancel(
                    headers.get(protocol.HDR_CORRELATION, "")
                )

        chaos.on_publish = _tap
        agent = Agent(
            "svc",
            model=TestModelClient(custom_output_text="ok", call_tools="none"),
            before_node=[lambda ctx: ran.append(ctx.correlation_id) and None],
        )
        async with Worker([agent], mesh=mesh, owns_transport=True):
            client = Client.connect(mesh)
            with pytest.raises(NodeFaultError) as ei:
                await client.agent("svc").execute("x", timeout=5)
            assert ei.value.report.error_type == FaultTypes.CANCELLED
            # deliberate abandonment is NOT retriable
            assert not RetryPolicy.retriable(ei.value)
            assert ran == [], "agent body ran for a cancelled run"
            await client.close()


class TestCancelForwarding:
    async def test_cancel_follows_the_run_downstream(self):
        """The cancel record is re-published along the run's path: the
        agent's kernel remembers which topics it sent the run's calls to
        and forwards the cancel there — an engine in ANOTHER process is
        only reachable through its topic, never through the in-process
        registry.  Scripted: cancel lands while the tool executes; the
        tool's input topic must see a cancel record exactly once."""
        from calfkit_tpu.nodes import agent_tool

        mesh = InMemoryMesh()
        chaos = BrokerChaos()
        mesh.chaos = chaos
        started = asyncio.Event()
        release = asyncio.Event()

        @agent_tool
        async def probe(q: str) -> str:
            """Parks until released.

            Args:
                q: ignored.
            """
            started.set()
            await release.wait()
            return "done"

        agent = Agent(
            "svc", model=TestModelClient(), tools=[probe],
        )
        tool_topic = protocol.tool_input_topic("probe")
        async with Worker([agent, probe], mesh=mesh, owns_transport=True):
            client = Client.connect(mesh)
            handle = await client.agent("svc").start("go")
            await asyncio.wait_for(started.wait(), 10)
            assert chaos.seen.count((tool_topic, "cancel")) == 0
            await handle.cancel()
            await settle(
                lambda: (tool_topic, "cancel") in chaos.seen,
                message="cancel was never forwarded to the tool's topic",
            )
            # idempotent: a duplicate cancel record forwards nothing
            # (the downstream entry was popped by the first)
            agent_topic = next(
                t for t, k in chaos.seen if k == "call" and "svc" in t
            )
            await mesh.publish(
                agent_topic,
                b"",
                key=b"dup",
                headers={
                    protocol.HDR_KIND: "cancel",
                    protocol.HDR_CORRELATION: handle.correlation_id,
                },
            )
            release.set()
            # the agent's final return proves its topic's pull advanced
            # past the duplicate cancel (same pull task, in order)
            await settle(
                lambda: chaos.kinds_seen("return") >= 2,
                message="run never settled after release",
            )
            assert chaos.seen.count((tool_topic, "cancel")) == 1
            await client.close()


class TestDeadlineExpiry:
    async def test_expired_on_arrival_faults_typed(self):
        """The clock jumps past the deadline while the call is on the
        wire (scripted at the broker): the receiving hop records a typed
        ``mesh.deadline_exceeded`` fault instead of executing."""
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()

            def jump(topic, headers):
                if headers.get(protocol.HDR_KIND) == "call":
                    clock.advance(60)

            chaos.on_publish = jump
            mesh.chaos = chaos
            agent = Agent(
                "late",
                model=TestModelClient(
                    custom_output_text="never", call_tools="none"
                ),
            )
            async with Worker([agent], mesh=mesh, owns_transport=True):
                client = Client.connect(mesh)
                with pytest.raises(NodeFaultError) as ei:
                    await client.agent("late").execute("hi", timeout=30)
                assert (
                    ei.value.report.error_type
                    == FaultTypes.DEADLINE_EXCEEDED
                )
                # the wire code maps back to the canonical local type
                assert (
                    exception_for(FaultTypes.DEADLINE_EXCEEDED)
                    is DeadlineExceededError
                )
                await client.close()

    async def test_engine_reaps_expired_queued_and_active(self, params):
        """One active and one queued request, both deadlined: advancing
        the virtual clock expires BOTH through the cancellation path —
        typed ``DeadlineExceededError`` at each consumer, all resources
        freed, EXPIRE events journaled."""
        with virtual_clock() as clock:
            runtime = _rt(
                max_batch_size=1, kv_layout="paged", overlap_dispatch=True,
                flightrec_events=1 << 12,
            )
            engine = InferenceEngine(CFG, runtime, params=params)
            total_free = engine._page_alloc.free_pages
            await engine.start()
            try:
                task_a = asyncio.create_task(
                    _collect(
                        engine, [1, 2, 3], 64, corr="exp-active",
                        deadline=clock.now + 5,
                    )
                )
                await settle(lambda: engine._active)
                task_b = asyncio.create_task(
                    _collect(
                        engine, [4, 5], 64, corr="exp-queued",
                        deadline=clock.now + 5,
                    )
                )
                await settle(
                    lambda: len(engine._pending) + len(engine._carry) == 1
                )
                clock.advance(10)
                with pytest.raises(DeadlineExceededError):
                    await task_a
                with pytest.raises(DeadlineExceededError):
                    await task_b
                await settle(lambda: _drained(engine, total_free))
                assert_engine_drained(engine, total_free)
                assert engine.stats.expired_requests == 2
                expires = [
                    e for e in _journal_events(engine)
                    if e["event"] == "EXPIRE"
                ]
                assert len(expires) == 2
                # an expiry-driven reap is not a consumer cancel
                assert engine.stats.cancelled_requests == 0
                # un-deadlined work still serves
                assert len(await _collect(engine, [9], 8)) == 8
            finally:
                await engine.stop()

    async def test_expired_at_engine_admission(self, params):
        """An already-expired submit is refused before ANY device work."""
        with virtual_clock() as clock:
            engine = InferenceEngine(CFG, _rt(), params=params)
            await engine.start()
            try:
                with pytest.raises(DeadlineExceededError, match="expired"):
                    await _collect(
                        engine, [1, 2], 8, deadline=clock.now - 1
                    )
                assert engine.stats.expired_requests == 1
            finally:
                await engine.stop()


class TestWorkerDrain:
    async def test_drain_refuses_new_calls_typed_and_retriable(self):
        """Drain mode: readiness flips false, NEW calls fault with the
        typed, retriable ``mesh.overloaded`` code, and the caller-side
        bounded retry actually re-publishes (and stays bounded)."""
        mesh = InMemoryMesh()
        chaos = BrokerChaos()
        mesh.chaos = chaos
        agent = Agent(
            "svc",
            model=TestModelClient(custom_output_text="ok", call_tools="none"),
        )
        worker = Worker([agent], mesh=mesh, owns_transport=True)
        async with worker:
            client = Client.connect(mesh)
            result = await client.agent("svc").execute("a", timeout=5)
            assert result.output == "ok"
            assert worker.ready()[0] is True

            worker.drain()
            assert worker.ready()[0] is False
            assert worker.draining

            with pytest.raises(NodeFaultError) as ei:
                await client.agent("svc").execute("b", timeout=5)
            assert ei.value.report.error_type == FaultTypes.OVERLOADED
            assert RetryPolicy.retriable(ei.value)

            # bounded retry with backoff: exactly `attempts` publishes,
            # then the typed fault surfaces (still draining)
            calls_before = chaos.kinds_seen("call")
            with pytest.raises(NodeFaultError):
                await client.agent("svc").execute(
                    "c", timeout=5,
                    retry=RetryPolicy(attempts=3, base_delay=0.01),
                )
            assert chaos.kinds_seen("call") - calls_before == 3
            await client.close()


class TestDeliveryStall:
    async def test_stalled_consumer_is_stall_cancelled(self, params):
        """A consumer that stops draining accumulates at most
        ``max_out_blocks`` undrained blocks before the scheduler
        stall-cancels the request; resuming surfaces a typed
        ``EngineOverloadedError`` and nothing leaked."""
        runtime = _rt(
            max_out_blocks=2, kv_layout="paged", overlap_dispatch=True
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            agen = engine.generate(
                [1, 2, 3], max_new_tokens=100, corr="stall"
            )
            first = await agen.__anext__()
            assert isinstance(first, int)
            # the consumer stalls; the engine keeps decoding until the
            # delivery bound trips the stall-cancel
            await settle(
                lambda: engine.stats.delivery_stalled >= 1,
                message="stall was never detected",
            )
            with pytest.raises(EngineOverloadedError, match="max_out_blocks"):
                async for _ in agen:
                    pass
            await settle(lambda: _drained(engine, total_free))
            assert_engine_drained(engine, total_free)
            # a healthy consumer is unaffected
            assert len(await _collect(engine, [9], 8)) == 8
        finally:
            await engine.stop()


class TestRaggedWaveCancellation:
    async def test_cancel_request_packed_into_mixed_wave(self, params):
        """ISSUE 6 chaos: cancel a request while its prefill chunk is
        riding a MIXED ragged dispatch (decode rows + its admission
        wave fused into one invocation).  The corpse must shed at
        activation, its co-wave survivor must stream in full, the
        decoding bystanders must be untouched, and no slot or page may
        leak — the unified lane keeps the bifurcated lane's cancel
        semantics."""
        runtime = _rt(
            kv_layout="paged", chunked_prefill=True, ragged_waves=True,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            # two decoding bystanders keep the fused lane busy
            bystanders = [
                asyncio.create_task(_collect(engine, [1 + i], 24))
                for i in range(2)
            ]
            await settle(lambda: len(engine._active) == 2)
            # same bucket (48 → 3 chunks): both join one admission wave
            # that must be ABSORBED into the bystanders' decode dispatches
            doomed = asyncio.create_task(
                _collect(engine, list(range(1, 44)), 16, corr="doomed")
            )
            survivor = asyncio.create_task(
                _collect(engine, list(range(100, 143)), 16)
            )
            await settle(
                lambda: engine._inflight is not None
                and len(engine._inflight["wave"]) == 2
                and engine.stats.unified_dispatches >= 1,
                message="no mixed (decode+chunk) wave ever formed",
            )
            doomed.cancel()
            with pytest.raises(asyncio.CancelledError):
                await doomed
            assert len(await survivor) == 16
            assert [len(s) for s in await asyncio.gather(*bystanders)] == [
                24, 24,
            ]
            await settle(lambda: _drained(engine, total_free))
            assert_engine_drained(engine, total_free)
            assert engine.stats.prefill_absorbed_tokens > 0
            # the journal shows the fused lane ran and the cancel reaped
            names = {e["event"] for e in _journal_events(engine)}
            assert "RAGGED_WAVE" in names
            assert "CANCEL" in names
            # the lane still admits mixed waves afterwards
            assert len(await _collect(engine, list(range(1, 44)), 8)) == 8
        finally:
            await engine.stop()


class TestFleetChaos:
    """Multi-worker topologies (ISSUE 7): replica failover, drain
    handoff, and shed-retry storms run deterministically — fast real
    heartbeats, virtual-clock staleness, per-replica delivery ledgers,
    and the engine no-leak oracle where real engines serve."""

    @staticmethod
    def _engine_fleet(params, n, **rt_over):
        """n real engines wrapped as agent models (debug preset)."""
        engines, models = [], []
        for _ in range(n):
            runtime = _rt(**rt_over)
            engine = InferenceEngine(CFG, runtime, params=params)
            engines.append(engine)
            models.append(
                JaxLocalModelClient(
                    config=CFG, runtime=runtime, engine=engine,
                    max_new_tokens=24,
                )
            )
        return engines, models

    @staticmethod
    async def _eligible(router, n, message):
        """Boot adverts say ready=False by design (a booting worker
        must not draw traffic): wait for the first post-boot beat."""
        await router.start()
        await settle(
            lambda: len(router.registry.eligible("svc")) == n,
            message=message,
        )

    async def test_draining_replica_gets_zero_new_calls(self, params):
        """Drain one of two replicas mid-generation: the in-flight run
        completes ON the draining replica, every subsequent call lands
        on the other one (zero NEW deliveries to the drained worker),
        and both engines drain leak-free."""
        with virtual_clock():
            mesh = InMemoryMesh()
            engines, models = self._engine_fleet(params, 2)
            async with FleetTopology(mesh, models) as fleet:
                low = fleet.index_of_lowest_key()
                # pace the replica the first (depth-tied) pick lands on,
                # so its run is still decoding when the drain hits
                slow = ChaosScript()

                def pace(point):
                    slow(point)
                    if point == "dispatch":
                        time.sleep(0.02)

                engines[low]._chaos = pace
                router = FleetRouter(
                    mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(mesh, router=router)
                await self._eligible(router, 2, "fleet never became routable")

                inflight = asyncio.create_task(
                    client.agent("svc").execute("long haul", timeout=60)
                )
                await settle(
                    lambda: engines[low]._active,
                    message="the in-flight run never reached the engine",
                )
                assert fleet.calls_delivered(low) == 1

                fleet.workers[low].drain()
                assert fleet.workers[low].ready()[0] is False
                await settle(
                    lambda: [
                        r.instance_id
                        for r in router.registry.eligible("svc")
                    ] == [fleet.instance_id(1 - low)],
                    message="drain never reached the registry",
                )
                # the run is still in flight on the draining replica
                assert engines[low]._active, "paced run finished too early"

                for i in range(4):
                    result = await client.agent("svc").execute(
                        f"post-drain {i}", timeout=60
                    )
                    assert result.output
                # zero NEW calls on the drained replica; all four on the
                # survivor — and the in-flight run finished normally
                assert fleet.calls_delivered(low) == 1
                assert fleet.calls_delivered(1 - low) == 4
                assert (await inflight).output
                await settle(lambda: _drained(engines[low]))
                assert_engine_drained(engines[low])
                assert_engine_drained(engines[1 - low])
                assert engines[low].stats.shed_requests == 0
                await client.close()
            for engine in engines:
                await engine.stop()
            await mesh.stop()

    async def test_shed_retried_on_a_different_replica(self, params):
        """A prefix-affinity storm on one tightly-bounded home replica
        (capacity 2: one slot + max_pending 1): the overflow sheds
        typed, every shed is retried against the OTHER replica (the
        shed source is excluded from the retry's placement), every run
        ultimately succeeds, and the home replica's topic saw exactly
        the first attempts — a shed retry NEVER re-picks its shed
        source."""
        with virtual_clock():
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            # asymmetric capacity, so the scenario is deterministic for
            # ANY shed count: replica 0 sheds its overflow, replica 1
            # has the headroom to absorb every retry without shedding
            engines, models = [], []
            for max_pending in (1, 8):
                runtime = _rt(
                    max_batch_size=1, max_pending=max_pending,
                    decode_steps_per_dispatch=1,
                )
                engine = InferenceEngine(CFG, runtime, params=params)
                engines.append(engine)
                models.append(
                    JaxLocalModelClient(
                        config=CFG, runtime=runtime, engine=engine,
                        max_new_tokens=24,
                    )
                )
            home = 0
            async with FleetTopology(mesh, models) as fleet:
                router = FleetRouter(
                    mesh, "prefix-affinity",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(mesh, router=router)
                await self._eligible(router, 2, "fleet never became routable")

                # find a session prompt (>= one 64-char affinity page,
                # small enough to fit max_seq_len 128 with scaffolding)
                # whose rendezvous home is the BOUNDED replica — the
                # search is over session ids, exactly how real sessions
                # scatter across homes
                from calfkit_tpu.fleet import affinity_key_for

                candidates = [
                    f"session-{i:02d}: shared preamble " * 3
                    for i in range(64)
                ]
                assert all(
                    affinity_key_for(p) is not None for p in candidates
                ), "candidate prompts are below one affinity page"
                prompt = next(
                    p
                    for p in candidates
                    if (picked := router.select("svc", prompt_text=p))
                    is not None
                    and picked.instance_id == fleet.instance_id(home)
                )
                # pace the home so the storm overlaps one generation
                slow = ChaosScript()

                def pace(point):
                    slow(point)
                    if point == "dispatch":
                        time.sleep(0.01)

                engines[home]._chaos = pace

                results = await asyncio.gather(
                    *[
                        client.agent("svc").execute(
                            prompt, timeout=60,
                            retry=RetryPolicy(attempts=3, base_delay=0.01),
                        )
                        for _ in range(4)
                    ]
                )
                assert all(r.output for r in results)
                sheds = engines[home].stats.shed_requests
                assert sheds >= 1, "the storm never overflowed the home"
                assert engines[1 - home].stats.shed_requests == 0
                home_topic = fleet.agents[home].replica_topic()
                other_topic = fleet.agents[1 - home].replica_topic()
                home_calls = chaos.seen.count((home_topic, "call"))
                other_calls = chaos.seen.count((other_topic, "call"))
                # affinity homed all four first attempts; every shed
                # retried on the OTHER replica and nowhere else
                assert home_calls == 4, (home_calls, other_calls, sheds)
                assert other_calls == sheds, (home_calls, other_calls, sheds)
                assert fleet.calls_delivered(1 - home) == sheds
                await settle(lambda: _drained(engines[home]))
                await settle(lambda: _drained(engines[1 - home]))
                assert_engine_drained(engines[home])
                assert_engine_drained(engines[1 - home])
                await client.close()
            for engine in engines:
                await engine.stop()
            await mesh.stop()

    async def test_stale_heartbeat_excluded_until_readvertise(self):
        """A replica whose heartbeat loop wedges keeps serving nothing
        NEW once the virtual clock passes stale_after; the moment it
        re-advertises (fresh stamp) it is routable again.  Pure routing
        scenario — scripted stub models, ledgers as the oracle."""
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            models = [ServingStubModel(text=f"r{i}") for i in range(2)]
            async with FleetTopology(mesh, models) as fleet:
                router = FleetRouter(
                    mesh, "least-loaded",
                    stale_after=fleet.config.stale_after,
                )
                client = Client.connect(mesh, router=router)
                await self._eligible(router, 2, "fleet never became routable")

                low = fleet.index_of_lowest_key()
                # depth-tied least-loaded picks the lowest key: pin it
                result = await client.agent("svc").execute("warm", timeout=10)
                assert result.output == f"r{low}"
                assert fleet.calls_delivered(low) == 1

                # the lowest-key replica's heartbeat wedges; time passes
                fleet.wedge_heartbeat(low)
                clock.advance(fleet.config.stale_after + 1)
                await settle(
                    lambda: [
                        r.instance_id
                        for r in router.registry.eligible("svc")
                    ] == [fleet.instance_id(1 - low)],
                    message="the wedged replica never went stale "
                    "(is the survivor re-stamping?)",
                )
                for i in range(3):
                    result = await client.agent("svc").execute(
                        f"while-stale {i}", timeout=10
                    )
                    assert result.output == f"r{1 - low}"
                assert fleet.calls_delivered(low) == 1  # nothing new

                # recovery: one fresh advert restores eligibility and
                # the depth-tied pick returns to the lowest key
                await fleet.resume_heartbeat(low)
                await settle(
                    lambda: len(router.registry.eligible("svc")) == 2,
                    message="re-advertising did not restore eligibility",
                )
                result = await client.agent("svc").execute("back", timeout=10)
                assert result.output == f"r{low}"
                assert fleet.calls_delivered(low) == 2
                await client.close()
            await mesh.stop()


class TestFailoverChaos:
    """In-flight failure recovery (ISSUE 9): hard replica death driven
    through FleetTopology's process-death seam (kill = stop consuming +
    stop heartbeating + publishes vanish, no drain), recovery supervised
    by the gateway's FailoverPolicy under the virtual clock."""

    @staticmethod
    def _failover_client(mesh, fleet, **policy_over):
        from calfkit_tpu.fleet import FailoverPolicy, FleetRouter

        kw = dict(probe_interval=0.02, max_failovers=2)
        kw.update(policy_over)
        router = FleetRouter(
            mesh, "least-loaded", stale_after=fleet.config.stale_after
        )
        client = Client.connect(
            mesh, router=router, failover=FailoverPolicy(**kw)
        )
        return router, client

    async def test_kill_mid_stream_completes_contiguous(self):
        """THE acceptance scenario: hard-kill a replica mid-stream.  The
        request completes on the survivor, the caller observes ONE
        contiguous stream (concatenated token deltas == the terminal
        output: no duplicated, no missing text), and — after the zombie
        resumes — the old correlation is tombstoned so the orphaned run
        never executes twice.  StreamingStubModel pins exactly how much
        text the caller saw before the death."""
        from calfkit_tpu.models.node_result import InvocationResult
        from tests._chaos import StreamingStubModel

        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            models = [
                StreamingStubModel(text="alpha beta gamma delta")
                for _ in range(2)
            ]
            async with FleetTopology(
                mesh, models, agent_kwargs={"stream_tokens": True}
            ) as fleet:
                low = fleet.index_of_lowest_key()
                models[1 - low].release.set()  # only the victim pauses
                victim_topic = fleet.agents[low].replica_topic()
                victim_corrs: list = []

                def note(topic, headers):
                    if (
                        topic == victim_topic
                        and headers.get(protocol.HDR_KIND) == "call"
                    ):
                        victim_corrs.append(
                            headers.get(protocol.HDR_CORRELATION)
                        )

                chaos.on_publish = note
                router, client = self._failover_client(mesh, fleet)
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )

                token_texts: list = []
                result = None
                killed = False
                async for item in client.agent("svc").stream(
                    "tell me a story", timeout=60
                ):
                    if isinstance(item, InvocationResult):
                        result = item
                        continue
                    if getattr(item.step, "kind", "") != "token":
                        continue
                    token_texts.append(item.step.text)
                    if not killed:
                        # the first delivered tokens ("alpha ") are on
                        # the wire; the replica dies NOW, mid-stream
                        killed = True
                        fleet.kill(low)
                        clock.advance(fleet.config.stale_after + 1)
                assert killed, "the stream never delivered a first token"
                assert result is not None
                assert result.output == "alpha beta gamma delta"
                # contiguity law: what streamed is exactly the answer —
                # no duplicated "alpha ", no missing words
                assert "".join(token_texts) == result.output
                # the call was placed once on each replica (original +
                # failover re-dispatch, marked for the advert), and the
                # orphan was cancelled toward the dead replica's topic
                assert fleet.calls_delivered(low) == 1
                assert fleet.calls_delivered(1 - low) == 1
                assert len(victim_corrs) == 1
                assert (victim_topic, "cancel") in chaos.seen
                assert fleet.agents[1 - low]._failover_requests == 1
                # zombie returns: the buffered cancel replays FIRST
                # (express law) and tombstones the orphaned correlation
                models[low].release.set()
                await fleet.resume(low)
                await settle(
                    lambda: cancellation.was_cancelled(victim_corrs[0]),
                    message="the zombie never tombstoned the orphan",
                )
                await client.close()
            await mesh.stop()

    async def test_kill_mid_run_real_engines_no_leaks(self, params):
        """The engine-oracle half of the acceptance: hard-kill a replica
        while its REAL engine is decoding the run.  The survivor serves
        the re-dispatch, the caller gets a result well inside its
        deadline, and BOTH engines — including the corpse, whose
        in-flight compute keeps burning into dropped publishes — drain
        with zero leaked slots or pages."""
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            engines, models = TestFleetChaos._engine_fleet(params, 2)
            async with FleetTopology(mesh, models) as fleet:
                low = fleet.index_of_lowest_key()
                # pace the victim so the kill lands mid-generation
                slow = ChaosScript()

                def pace(point):
                    slow(point)
                    if point == "dispatch":
                        time.sleep(0.02)

                engines[low]._chaos = pace
                router, client = self._failover_client(mesh, fleet)
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )
                call = asyncio.create_task(
                    client.agent("svc").execute("long haul", timeout=60)
                )
                await settle(
                    lambda: engines[low]._active,
                    message="the run never reached the victim engine",
                )
                fleet.kill(low)
                clock.advance(fleet.config.stale_after + 1)
                result = await call
                assert result.output
                assert fleet.calls_delivered(low) == 1
                assert fleet.calls_delivered(1 - low) == 1
                victim_topic = fleet.agents[low].replica_topic()
                assert (victim_topic, "cancel") in chaos.seen
                # the corpse finishes its abandoned decode into dropped
                # publishes and must STILL free everything
                await settle(lambda: _drained(engines[low]))
                await settle(lambda: _drained(engines[1 - low]))
                assert_engine_drained(engines[low])
                assert_engine_drained(engines[1 - low])
                await client.close()
            for engine in engines:
                await engine.stop()
            await mesh.stop()

    async def test_kill_mid_prefill_reissues_whole_call(self, params):
        """Kill the placed replica before ANY token was delivered (the
        mid-prefill shape): execute() re-issues the whole call on the
        survivor under the remaining deadline and returns its answer."""
        del params

        class BlockedStubModel(ServingStubModel):
            def __init__(self, **kw):
                super().__init__(**kw)
                self.release = asyncio.Event()

            async def request(self, messages, settings=None, params=None):
                await self.release.wait()
                return await super().request(messages, settings, params)

        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            models = [BlockedStubModel(text=f"r{i}") for i in range(2)]
            async with FleetTopology(mesh, models) as fleet:
                low = fleet.index_of_lowest_key()
                models[1 - low].release.set()  # only the victim blocks
                router, client = self._failover_client(mesh, fleet)
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )
                call = asyncio.create_task(
                    client.agent("svc").execute("prefill me", timeout=60)
                )
                await settle(
                    lambda: fleet.calls_delivered(low) == 1,
                    message="the call never reached the victim",
                )
                fleet.kill(low)
                clock.advance(fleet.config.stale_after + 1)
                result = await call
                assert result.output == f"r{1 - low}"
                assert fleet.calls_delivered(1 - low) == 1
                victim_topic = fleet.agents[low].replica_topic()
                assert (victim_topic, "cancel") in chaos.seen
                models[low].release.set()  # unblock for clean teardown
                await client.close()
            await mesh.stop()

    async def test_zombie_replica_never_executes_orphaned_run(self):
        """A call lands on a replica that is ALREADY dead (killed before
        consuming it).  Failover completes the run elsewhere; when the
        zombie resumes consuming, the buffered cancel replays FIRST (the
        dispatcher's express law) and the orphaned call faults at the
        admission gate — the zombie executes nothing."""
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            models = [ServingStubModel(text=f"r{i}") for i in range(2)]
            async with FleetTopology(mesh, models) as fleet:
                low = fleet.index_of_lowest_key()
                victim_topic = fleet.agents[low].replica_topic()
                victim_corrs: list = []

                def note(topic, headers):
                    if (
                        topic == victim_topic
                        and headers.get(protocol.HDR_KIND) == "call"
                    ):
                        victim_corrs.append(
                            headers.get(protocol.HDR_CORRELATION)
                        )

                chaos.on_publish = note
                router, client = self._failover_client(mesh, fleet)
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )
                # the replica dies FIRST; its advert is still fresh, so
                # the depth-tied pick still places the call on it
                fleet.kill(low)
                call = asyncio.create_task(
                    client.agent("svc").execute("orphan me", timeout=60)
                )
                await settle(
                    lambda: len(victim_corrs) == 1,
                    message="the call never targeted the dead replica",
                )
                clock.advance(fleet.config.stale_after + 1)
                result = await call
                assert result.output == f"r{1 - low}"
                # nothing executed on the corpse: the gate buffered it
                assert fleet.calls_delivered(low) == 0
                assert models[low].replies == 0
                # the zombie resumes: cancel replays first, the orphaned
                # call dies at the admission gate (tombstone), zero turns
                await fleet.resume(low)
                await settle(
                    lambda: cancellation.was_cancelled(victim_corrs[0]),
                    message="the zombie never saw the cancel",
                )
                await settle(
                    lambda: chaos.kinds_seen("fault") >= 1,
                    message="the tombstoned call never faulted",
                )
                assert fleet.calls_delivered(low) == 0
                assert models[low].replies == 0
                await client.close()
            await mesh.stop()

    async def test_stream_fault_fails_open_on_single_replica(self):
        """Review regression: a retriable FAULT mid-stream on a fleet
        with NO alternative replica must not burn the deadline waiting
        for an eligible placement — the faulting replica is alive and
        answering, so the re-dispatch fails open (shared topic) and the
        recovered replica serves the retry within milliseconds."""
        from calfkit_tpu.exceptions import EngineOverloadedError
        from calfkit_tpu.models.node_result import InvocationResult

        class ShedOnceStubModel(ServingStubModel):
            def __init__(self, **kw):
                super().__init__(**kw)
                self.shed_once = True

            async def request(self, messages, settings=None, params=None):
                if self.shed_once:
                    self.shed_once = False
                    raise EngineOverloadedError(
                        "transient shed", lane="short", pending=9, limit=1
                    )
                return await super().request(messages, settings, params)

        with virtual_clock():
            mesh = InMemoryMesh()
            models = [ShedOnceStubModel(text="recovered")]
            async with FleetTopology(mesh, models) as fleet:
                router, client = self._failover_client(mesh, fleet)
                await TestFleetChaos._eligible(
                    router, 1, "the replica never became routable"
                )
                result = None
                async for item in client.agent("svc").stream(
                    "shed me once", timeout=20
                ):
                    if isinstance(item, InvocationResult):
                        result = item
                assert result is not None
                assert result.output == "recovered"
                # both attempts reached the same (only) replica
                assert fleet.calls_delivered(0) == 2
                assert models[0].replies == 1
                await client.close()
            await mesh.stop()

    async def test_hedge_race_first_terminal_wins(self):
        """hedge_after: a slow primary gets a duplicate dispatched on
        the OTHER replica after the latency threshold (virtual clock);
        the first terminal wins and the loser's correlation is
        cancelled."""

        class SlowStubModel(ServingStubModel):
            def __init__(self, **kw):
                super().__init__(**kw)
                self.release = asyncio.Event()

            async def request(self, messages, settings=None, params=None):
                await self.release.wait()
                return await super().request(messages, settings, params)

        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            models = [SlowStubModel(text=f"r{i}") for i in range(2)]
            async with FleetTopology(mesh, models) as fleet:
                low = fleet.index_of_lowest_key()
                models[1 - low].release.set()  # only the primary is slow
                router, client = self._failover_client(
                    mesh, fleet, hedge_after=1.0
                )
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )
                call = asyncio.create_task(
                    client.agent("svc").execute("race me", timeout=60)
                )
                await settle(
                    lambda: fleet.calls_delivered(low) == 1,
                    message="the primary never got the call",
                )
                clock.advance(1.5)  # past hedge_after: the duplicate fires
                result = await call
                assert result.output == f"r{1 - low}"
                assert fleet.calls_delivered(1 - low) == 1
                # the duplicate was marked and the loser cancelled
                assert fleet.agents[1 - low]._hedge_requests == 1
                victim_topic = fleet.agents[low].replica_topic()
                await settle(
                    lambda: (victim_topic, "cancel") in chaos.seen,
                    message="the losing attempt was never cancelled",
                )
                models[low].release.set()  # clean teardown
                await client.close()
            await mesh.stop()


class TestWedgeWatchdog:
    """The engine wedge watchdog (ISSUE 9): a scripted hung device grant
    (the decode thread blocks mid-dispatch, a device that never answers)
    converts to typed RETRIABLE faults within the threshold, readiness
    flips false, the flight recorder dumps — and a late landing
    un-wedges the engine with zero leaked slots or pages."""

    async def test_wedged_dispatch_faults_typed_and_recovers(
        self, params, tmp_path, monkeypatch
    ):
        import threading

        from calfkit_tpu.exceptions import EngineWedgedError

        monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path))
        with virtual_clock() as clock:
            runtime = _rt(
                max_batch_size=1, watchdog_stall_s=0.5,
                decode_steps_per_dispatch=2,
            )
            engine = InferenceEngine(CFG, runtime, params=params)
            gate = threading.Event()
            script = ChaosScript().block_at("dispatch", 2, gate)
            engine._chaos = script
            await engine.start()
            try:
                active = asyncio.create_task(
                    _collect(engine, [1, 2, 3], 32, corr="wedge-active")
                )
                await settle(
                    lambda: script.calls.get("dispatch", 0) >= 2,
                    message="the dispatch never reached the block point",
                )
                queued = asyncio.create_task(
                    _collect(engine, [4, 5], 32, corr="wedge-queued")
                )
                await settle(
                    lambda: engine._pending,
                    message="the second request never queued",
                )
                # no landing while the clock passes the threshold
                clock.advance(0.6)
                with pytest.raises(EngineWedgedError):
                    await asyncio.wait_for(active, timeout=10)
                with pytest.raises(EngineWedgedError):
                    await asyncio.wait_for(queued, timeout=10)
                assert engine._wedged
                assert engine.stats.watchdog_trips == 1
                assert engine.stats.watchdog_faulted == 2
                # readiness follows the wedge (advert + /readyz)
                model = JaxLocalModelClient(
                    config=CFG, runtime=runtime, engine=engine
                )
                ready, reason = model.ready()
                assert ready is False and "wedged" in reason
                assert model.stats_snapshot()["wedged"] is True
                # a submit during the wedge sheds fast and typed
                with pytest.raises(EngineWedgedError):
                    await _collect(engine, [9], 4, corr="wedge-late")
                # the dump landed and carries the WEDGE event
                dumps = list(tmp_path.glob("*.jsonl"))
                assert dumps, "no wedge dump written"
                events = _journal_events(engine)
                assert any(e["event"] == "WEDGE" for e in events)
                # ---- recovery: the grant returns, a landing un-wedges
                clock.advance(0.01)
                gate.set()
                await settle(
                    lambda: not engine._wedged,
                    message="a landing never un-wedged the engine",
                )
                assert model.ready()[0] is True
                await settle(lambda: _drained(engine))
                assert_engine_drained(engine)
                # serving resumes for new work
                tokens = await _collect(engine, [1, 2], 4, corr="after")
                assert tokens
            finally:
                gate.set()
                await engine.stop()


class TestOrphanReaper:
    """Caller liveness leases (ISSUE 10): the server-side orphan reaper.
    A caller that dies — heartbeats stop past the lease TTL — has its
    runs abandoned BY THE ENGINE, queued and active alike, slots/pages
    freed through the ordinary retirement path, with a typed
    non-retriable ``mesh.orphaned`` terminal.  This is what makes
    fire-and-forget ``send()`` safe: no client-side supervisor exists
    for a run nobody awaits."""

    async def test_caller_death_reaps_queued_and_active(self, params):
        """Beats stop; one TTL later the engine reaps BOTH the active
        and the queued leased run: typed RunOrphanedError, zero leaked
        slots/pages, journal timeline ending ORPHAN → … → SLOT_FREE."""
        runtime = _rt(
            max_batch_size=1, kv_layout="paged", overlap_dispatch=True,
            flightrec_events=1 << 14,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        with virtual_clock() as clock:
            await engine.start()
            try:
                ttl = 5.0
                leases.note_beat("lease-dead", ttl)
                active = asyncio.create_task(
                    _collect(
                        engine, [1, 2, 3], 64, corr="orph-a",
                        lease=("lease-dead", ttl),
                    )
                )
                await settle(
                    lambda: engine._active,
                    message="the leased run never activated",
                )
                queued = asyncio.create_task(
                    _collect(
                        engine, [7, 8], 64, corr="orph-b",
                        lease=("lease-dead", ttl),
                    )
                )
                await settle(
                    lambda: len(engine._pending) + len(engine._carry) == 1,
                    message="the second leased run never queued",
                )
                # the caller dies: no more beats — one TTL later both
                # runs are orphans
                clock.advance(ttl + 0.5)
                with pytest.raises(RunOrphanedError):
                    await asyncio.wait_for(active, timeout=10)
                with pytest.raises(RunOrphanedError):
                    await asyncio.wait_for(queued, timeout=10)
                await settle(
                    lambda: _drained(engine, total_free),
                    message="engine did not drain after the orphan reap",
                )
                assert_engine_drained(engine, total_free)
                assert engine.stats.orphaned_requests == 2
                # orphans are NOT consumer cancels: no double count
                assert engine.stats.cancelled_requests == 0
                assert engine.stats.expired_requests == 0
                events = _journal_events(engine)
                tl = flightrec.timeline_events(events, "orph-a")
                names = [e["event"] for e in tl]
                assert "ORPHAN" in names, names
                assert "SLOT_FREE" in names, names
                assert names.index("ORPHAN") < (
                    len(names) - 1 - names[::-1].index("SLOT_FREE")
                ), f"ORPHAN did not precede the final SLOT_FREE: {names}"
                # the engine still serves live callers after the reap
                leases.note_beat("lease-live", ttl)
                tokens = await _collect(
                    engine, [9], 8, corr="after",
                    lease=("lease-live", ttl),
                )
                assert len(tokens) == 8
            finally:
                await engine.stop()

    async def test_lease_lapsed_at_submit_refused_before_device_work(
        self, params
    ):
        """A run arriving under an already-lapsed lease is refused at
        the gate — the EXPIRE-at-submit twin, no prefill burned."""
        engine = InferenceEngine(CFG, _rt(), params=params)
        with virtual_clock() as clock:
            await engine.start()
            try:
                leases.note_beat("lease-gone", 2.0)
                clock.advance(3.0)
                with pytest.raises(RunOrphanedError):
                    await _collect(
                        engine, [1, 2], 8, corr="late",
                        lease=("lease-gone", 2.0),
                    )
                assert engine.stats.orphaned_requests == 1
                assert engine.stats.prefill_tokens == 0
            finally:
                await engine.stop()

    async def test_heartbeat_wedge_within_ttl_run_survives(self, params):
        """A late beat WITHIN the TTL re-arms the reaper instead of
        orphaning: the registered expiry pops, the store shows a fresh
        beat, and the run completes normally."""
        runtime = _rt(decode_steps_per_dispatch=2)
        engine = InferenceEngine(CFG, runtime, params=params)
        pace = ChaosScript()

        def throttle(point):
            pace(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = throttle
        with virtual_clock() as clock:
            await engine.start()
            try:
                ttl = 10.0
                leases.note_beat("lease-wedge", ttl)
                run = asyncio.create_task(
                    _collect(
                        engine, [1, 2, 3], 48, corr="survivor",
                        lease=("lease-wedge", ttl),
                    )
                )
                await settle(
                    lambda: engine._active,
                    message="the leased run never activated",
                )
                # the caller's beat wedges for 0.6 TTL, then recovers:
                # total elapsed passes the ORIGINAL expiry, but the late
                # beat keeps the lease alive — the reaper must re-arm,
                # not orphan
                clock.advance(ttl * 0.6)
                leases.note_beat("lease-wedge", ttl)
                clock.advance(ttl * 0.6)
                tokens = await asyncio.wait_for(run, timeout=30)
                assert len(tokens) == 48
                assert engine.stats.orphaned_requests == 0
            finally:
                await engine.stop()

    @pytest.mark.parametrize("ragged", [True, False])
    async def test_precedence_one_typed_error_both_schedulers(
        self, params, ragged
    ):
        """THE precedence law (ISSUE 10 satellite), pinned on BOTH
        schedulers: a run whose deadline AND lease lapse in the same
        instant faults with exactly ONE typed error — the deadline's
        (expired outranks orphaned; the deadline sweep also runs first
        each pass) — and a lease-only lapse faults ``mesh.orphaned``.
        The ragged and bifurcated lanes share one _raise_terminal and
        one reap, so agreement is checked, not assumed."""
        runtime = _rt(
            chunked_prefill=True, overlap_dispatch=True,
            ragged_waves=ragged, decode_steps_per_dispatch=2,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        pace = ChaosScript()

        def throttle(point):
            pace(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = throttle
        with virtual_clock() as clock:
            await engine.start()
            try:
                assert engine._ragged is ragged
                now = cancellation.wall_clock()
                ttl = 2.0
                leases.note_beat("lease-both", ttl)
                both = asyncio.create_task(
                    _collect(
                        engine, [1, 2, 3], 64, corr="both",
                        deadline=now + ttl, lease=("lease-both", ttl),
                    )
                )
                await settle(
                    lambda: engine._active,
                    message="the doubly-doomed run never activated",
                )
                # deadline AND lease lapse in one step: exactly one
                # typed error, and it is the deadline's
                clock.advance(ttl + 1.0)
                with pytest.raises(DeadlineExceededError):
                    await asyncio.wait_for(both, timeout=10)
                await settle(lambda: _drained(engine))
                assert engine.stats.expired_requests == 1
                assert engine.stats.orphaned_requests == 0
                assert engine.stats.cancelled_requests == 0
                # lease-only lapse on the same scheduler: mesh.orphaned
                leases.note_beat("lease-only", ttl)
                orphan = asyncio.create_task(
                    _collect(
                        engine, [4, 5], 64, corr="only",
                        lease=("lease-only", ttl),
                    )
                )
                await settle(
                    lambda: engine._active,
                    message="the leased-only run never activated",
                )
                clock.advance(ttl + 1.0)
                with pytest.raises(RunOrphanedError):
                    await asyncio.wait_for(orphan, timeout=10)
                await settle(lambda: _drained(engine))
                assert_engine_drained(engine)
                assert engine.stats.orphaned_requests == 1
                assert engine.stats.expired_requests == 1
            finally:
                await engine.stop()

    async def test_caller_death_mid_fire_and_forget_over_the_mesh(
        self, params
    ):
        """THE acceptance drill: a LEASED client ``send()``s a run nobody
        awaits through the real mesh → worker → engine path, then dies
        hard (beat task killed, no tombstone).  One TTL later the engine
        reaps the orphan — drained, zero leaks — and the typed
        ``mesh.orphaned`` fault went to the (dead) reply topic."""
        runtime = _rt(
            max_batch_size=2, decode_steps_per_dispatch=1,
            kv_layout="paged", overlap_dispatch=True,
            flightrec_events=1 << 14,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        throttle = ChaosScript()

        def pace(point):
            throttle(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = pace
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=100
        )
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            chaos = BrokerChaos()
            mesh.chaos = chaos
            agent = Agent("leased", model=model)
            async with Worker([agent], mesh=mesh, owns_transport=True):
                ttl = 1.0
                client = Client.connect(mesh, lease_ttl=ttl)
                corr = await client.agent("leased").send("fire and forget")
                await settle(
                    lambda: engine._active,
                    message="the send() never reached the engine",
                )
                # hard caller death: beats stop, no tombstone
                assert client._lease_task is not None
                client._lease_task.cancel()
                clock.advance(ttl + 0.5)
                await settle(
                    lambda: _drained(engine, total_free),
                    message="the engine never reaped the orphan",
                )
                assert_engine_drained(engine, total_free)
                assert engine.stats.orphaned_requests == 1
                # the typed fault went out for the record (dead inbox)
                await settle(
                    lambda: chaos.kinds_seen("fault") >= 1,
                    message="no mesh.orphaned fault was published",
                )
                events = _journal_events(engine)
                tl = flightrec.timeline_events(events, corr)
                names = [e["event"] for e in tl]
                assert "ORPHAN" in names, names
                await client.close()
            await engine.stop()

    async def test_clean_close_releases_lease_and_reaps_now(self, params):
        """A clean ``close()`` tombstones the lease: outstanding leased
        runs orphan IMMEDIATELY — no TTL of grace for a deliberate
        departure (frozen clock proves no lapse was needed)."""
        runtime = _rt(decode_steps_per_dispatch=1)
        engine = InferenceEngine(CFG, runtime, params=params)
        throttle = ChaosScript()

        def pace(point):
            throttle(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = pace
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=100
        )
        with virtual_clock():
            mesh = InMemoryMesh()
            agent = Agent("leaving", model=model)
            async with Worker([agent], mesh=mesh, owns_transport=True):
                client = Client.connect(mesh, lease_ttl=30.0)
                await client.agent("leaving").send("left behind")
                await settle(
                    lambda: engine._active,
                    message="the send() never reached the engine",
                )
                await client.close()  # tombstones the lease
                await settle(
                    lambda: _drained(engine),
                    message="a released lease never reaped the orphan",
                )
                assert engine.stats.orphaned_requests == 1
                assert engine.stats.expired_requests == 0
            await engine.stop()


    async def test_no_liveness_feed_means_no_enforcement(self, params):
        """Fail-safe wiring: a worker with NO control plane (no liveness
        feed) must treat leased calls as un-leased — beats cannot reach
        it, and orphaning a live caller's run one TTL after admission
        would be worse than burning a dead one's.  The run completes
        despite the clock passing the TTL."""
        runtime = _rt(decode_steps_per_dispatch=2)
        engine = InferenceEngine(CFG, runtime, params=params)
        throttle = ChaosScript()

        def pace(point):
            throttle(point)
            if point == "dispatch":
                time.sleep(0.01)

        engine._chaos = pace
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine, max_new_tokens=24
        )
        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            agent = Agent("feedless", model=model)
            async with Worker(
                [agent], mesh=mesh, owns_transport=True,
                control_plane=False,
            ):
                ttl = 0.5
                client = Client.connect(mesh, lease_ttl=ttl)
                handle = await client.agent("feedless").start(
                    "still alive", timeout=600
                )
                await settle(
                    lambda: engine._active,
                    message="the call never reached the engine",
                )
                clock.advance(ttl * 10)  # far past the TTL
                result = await handle.result()
                assert result.output is not None
                assert engine.stats.orphaned_requests == 0
                await client.close()
            await engine.stop()


class TestDecodeFromOffsetResume:
    """True decode-from-offset resume (ISSUE 10): the survivor of a
    failover consumes ``deps["calfkit.resume_text"]`` — the delivered
    prefix enters via PREFILL, decode produces only the remaining
    tokens, and the caller observes one contiguous byte-exact stream
    (greedy parity vs an unkilled run)."""

    async def test_resume_generates_only_remaining_tokens(self, params):
        """Engine-level accounting: a resumed request's prefix enters as
        prefill (riding the prefix cache), decode counts ONLY the
        remaining tokens, the deltas are exactly the continuation, and
        the terminal response is byte-identical to the unresumed run."""
        from calfkit_tpu.engine.model_client import (
            ModelSettings,
            ResponseDone,
            ResumeOffset,
            TextDelta,
        )
        from calfkit_tpu.models.messages import ModelRequest, UserPart

        from tests._chaos import BijectiveTokenizer

        runtime = _rt(
            kv_layout="paged", chunked_prefill=True, prefix_cache=True,
            overlap_dispatch=True,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine,
            tokenizer=BijectiveTokenizer(), max_new_tokens=48,
        )
        messages = [ModelRequest(parts=[UserPart(content="tell a story")])]
        try:
            reference = await model.request(messages)
            full = reference.text() or ""
            assert len(full) >= 8, f"reference too short to resume: {full!r}"
            k = len(full) // 2
            p0 = engine.stats.prefill_tokens
            d0 = engine.stats.decode_tokens
            hits0 = engine.stats.prefix_hits

            events = []
            async for event in model.request_stream(
                messages, ModelSettings(resume_text=full[:k])
            ):
                events.append(event)
            # the resume protocol: offset first, then ONLY fresh deltas,
            # then a terminal carrying the FULL answer
            assert isinstance(events[0], ResumeOffset), events[0]
            assert events[0].chars == k
            deltas = "".join(
                e.text for e in events if isinstance(e, TextDelta)
            )
            assert deltas == full[k:], (deltas, full)
            done = events[-1]
            assert isinstance(done, ResponseDone)
            assert (done.response.text() or "") == full  # byte-exact
            # token accounting: the prefix entered via prefill (k tokens
            # on the bijective tokenizer), decode paid only the rest
            assert engine.stats.decode_tokens - d0 == len(full) - k
            prefill_delta = engine.stats.prefill_tokens - p0
            assert prefill_delta > k  # prompt + the delivered prefix
            # the shared prompt prefix rode the survivor-side cache
            assert engine.stats.prefix_hits > hits0
        finally:
            await engine.stop()

    async def test_resume_with_spent_budget_decodes_nothing(self, params):
        """A delivered prefix that already spent the whole token budget
        short-circuits: no engine work, just ResumeOffset + terminal."""
        from calfkit_tpu.engine.model_client import (
            ModelSettings,
            ResponseDone,
            ResumeOffset,
        )
        from calfkit_tpu.models.messages import ModelRequest, UserPart

        from tests._chaos import BijectiveTokenizer

        runtime = _rt()
        engine = InferenceEngine(CFG, runtime, params=params)
        model = JaxLocalModelClient(
            config=CFG, runtime=runtime, engine=engine,
            tokenizer=BijectiveTokenizer(), max_new_tokens=4,
        )
        messages = [ModelRequest(parts=[UserPart(content="hi")])]
        try:
            prior = "".join(chr(0x100 + i) for i in (9, 10, 11, 12))
            events = [
                e
                async for e in model.request_stream(
                    messages, ModelSettings(resume_text=prior)
                )
            ]
            assert isinstance(events[0], ResumeOffset)
            assert isinstance(events[-1], ResponseDone)
            assert (events[-1].response.text() or "") == prior
            assert engine.stats.decode_tokens == 0
            assert engine.stats.prefill_tokens == 0
        finally:
            await engine.stop()

    async def test_kill_mid_stream_resume_rides_survivor(self, params):
        """THE acceptance scenario: kill a replica mid-stream; the
        survivor RESUMES decode-from-offset — its prefill absorbed the
        delivered prefix, its decode produced only the remainder — and
        the caller observed one contiguous byte-exact stream, equal to
        an unkilled run's answer (greedy parity)."""
        from calfkit_tpu.models.node_result import InvocationResult

        from tests._chaos import BijectiveTokenizer

        with virtual_clock() as clock:
            mesh = InMemoryMesh()
            engines, models = [], []
            for _ in range(2):
                runtime = _rt(max_seq_len=256)
                engine = InferenceEngine(CFG, runtime, params=params)
                engines.append(engine)
                models.append(
                    JaxLocalModelClient(
                        config=CFG, runtime=runtime, engine=engine,
                        tokenizer=BijectiveTokenizer(), max_new_tokens=48,
                    )
                )
            async with FleetTopology(
                mesh, models, agent_kwargs={"stream_tokens": True}
            ) as fleet:
                low = fleet.index_of_lowest_key()
                router, client = TestFailoverChaos._failover_client(
                    mesh, fleet
                )
                await TestFleetChaos._eligible(
                    router, 2, "fleet never became routable"
                )
                # the unkilled reference (first call: EWMA ties at zero,
                # so it lands on the lowest key and warms that replica)
                ref = await client.agent("svc").execute(
                    "tell a story", timeout=120
                )
                full = ref.output or ""
                assert len(full) >= 24, f"answer too short: {full!r}"
                prompt_len = engines[low].stats.prefill_tokens
                assert prompt_len > 0
                # pace BOTH engines — the victim is whichever replica
                # the stream lands on (the EWMA tiebreak steers it away
                # from the ref-warmed one; derive it, don't assume it)
                slow = ChaosScript()

                def pace(point):
                    slow(point)
                    if point == "dispatch":
                        time.sleep(0.02)

                for engine in engines:
                    engine._chaos = pace
                before_p = [e.stats.prefill_tokens for e in engines]
                before_d = [e.stats.decode_tokens for e in engines]

                token_texts: list = []
                offsets: list = []
                result = None
                killed = False
                delivered_at_kill = 0
                victim = -1
                async for item in client.agent("svc").stream(
                    "tell a story", timeout=120
                ):
                    if isinstance(item, InvocationResult):
                        result = item
                        continue
                    if getattr(item.step, "kind", "") != "token":
                        continue
                    token_texts.append(item.step.text)
                    offsets.append(item.step.offset)
                    if not killed and sum(len(t) for t in token_texts) >= 8:
                        killed = True
                        delivered_at_kill = sum(len(t) for t in token_texts)
                        victim = 0 if engines[0]._active else 1
                        assert engines[victim]._active
                        fleet.kill(victim)
                        clock.advance(fleet.config.stale_after + 1)
                assert killed, "the stream never delivered enough to kill"
                assert result is not None
                streamed = "".join(token_texts)
                # one contiguous stream, byte-exact greedy parity with
                # the unkilled reference
                assert result.output == full
                assert streamed == full
                # the survivor resumed from offset: its prefill absorbed
                # prompt + delivered prefix, its decode paid ONLY the
                # remainder — nothing was re-generated (and nothing
                # needed deduping)
                survivor = 1 - victim
                resume_len = (
                    engines[survivor].stats.prefill_tokens
                    - before_p[survivor]
                    - prompt_len
                )
                assert resume_len >= delivered_at_kill > 0
                decode_delta = (
                    engines[survivor].stats.decode_tokens
                    - before_d[survivor]
                )
                assert decode_delta == len(full) - resume_len
                # the resumed attempt's first chunk was offset-stamped at
                # the delivered-prefix length
                assert resume_len in offsets, (resume_len, offsets)
                assert fleet.agents[survivor]._failover_requests == 1
                await client.close()
            for engine in engines:
                await engine.stop()
            await mesh.stop()
