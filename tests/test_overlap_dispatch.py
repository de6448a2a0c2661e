"""Overlapped execution: double-buffered decode dispatch (ISSUE 3).

The correctness contract under test:

- TOKEN-STREAM PARITY: overlap-on output is byte-identical to the
  lockstep reference across greedy / seeded-sampled / stop-token-mid-
  block / retirement-bound-inside-block / spec-decode-on /
  prefix-cache-hit / chunked-admission-under-load;
- ONE-DISPATCH-LATE RETIREMENT: a retiring row's slot and pages free
  only after the in-flight dispatch lands — exactly once, never early
  (shared prefix-cache pages keep their refcount until the landing);
- CANCELLATION MID-FLIGHT: an abandoned consumer gets nothing delivered
  after the cancel is reaped, and its resources free exactly once;
- the device-side retirement mask (``sampler.retire_mask_slots``)
  classifies stop tokens and generation bounds identically to the host
  authority (``_record_token``).
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from calfkit_tpu.exceptions import (  # noqa: E402
    EngineOverloadedError,
    InferenceError,
)
from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import (  # noqa: E402
    RuntimeConfig,
    SpecConfig,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.inference.sampler import (  # noqa: E402
    SamplingParams,
    retire_mask_slots,
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


async def _gen(engine, prompt, n, **kw):
    return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]


def _settled(engine, total_free: int) -> bool:
    """Settle predicate for a drained engine.  The decode thread nulls
    ``_pend`` BEFORE ``_free_deferred`` returns the slot and pages, so the
    free list and the page pool are part of the condition: settling on
    ``_pend``/``_active`` alone observes a state that is consistent one
    tick later (same law as tests/test_chaos.py ``_drained``)."""
    return (
        engine._pend is None and not engine._active
        and len(engine._free) == engine.runtime.max_batch_size
        and engine._page_alloc.free_pages == total_free
    )


async def _serve_all(params, runtime, jobs):
    """Run ``jobs`` = [(prompt, max_new, kwargs), ...] concurrently on a
    fresh engine; returns the per-job token streams."""
    engine = InferenceEngine(CFG, runtime, params=params)
    await engine.start()
    try:
        return await asyncio.gather(
            *[_gen(engine, p, n, **kw) for p, n, kw in jobs]
        ), engine
    finally:
        await engine.stop()


async def _parity(params, jobs, **rt_over):
    """The A/B harness: same jobs, overlap on vs off, streams must match."""
    on, eng_on = await _serve_all(
        params, _rt(overlap_dispatch=True, **rt_over), jobs
    )
    off, eng_off = await _serve_all(
        params, _rt(overlap_dispatch=False, **rt_over), jobs
    )
    assert on == off, "overlap-on streams diverged from the lockstep oracle"
    assert eng_off.stats.overlap_wasted_tokens == 0
    # one-dispatch-late tax stays within the documented bound
    assert eng_on.stats.overlap_wasted_tokens <= (
        len(jobs) * eng_on.runtime.decode_steps_per_dispatch
    )
    return on, eng_on


class TestRetireMaskMath:
    """sampler.retire_mask_slots against the host authority's semantics."""

    def _run(self, toks, stops, bound, active=None, emitted=None):
        toks = jnp.asarray(toks, jnp.int32)
        B = toks.shape[0]
        table = np.full((B, 4), -1, np.int32)
        for i, row in enumerate(stops):
            table[i, : len(row)] = row
        n_valid, done = retire_mask_slots(
            toks, jnp.asarray(table), jnp.asarray(bound, jnp.int32),
            jnp.ones((B,), bool) if active is None else jnp.asarray(active),
            emitted=None if emitted is None else jnp.asarray(emitted, jnp.int32),
        )
        return np.asarray(n_valid).tolist(), np.asarray(done).tolist()

    def test_no_stop_bound_beyond_block(self):
        n, d = self._run([[5, 6, 7, 8]], [[]], [10])
        assert (n, d) == ([4], [False])

    def test_bound_inside_block(self):
        n, d = self._run([[5, 6, 7, 8]], [[]], [2])
        assert (n, d) == ([2], [True])

    def test_bound_exactly_at_block_end_retires(self):
        n, d = self._run([[5, 6, 7, 8]], [[]], [4])
        assert (n, d) == ([4], [True])

    def test_stop_token_mid_block_excluded(self):
        # stop at position 2: deliver the two tokens before it
        n, d = self._run([[5, 6, 9, 8]], [[9]], [10])
        assert (n, d) == ([2], [True])

    def test_stop_at_first_position(self):
        n, d = self._run([[9, 6, 7, 8]], [[9]], [10])
        assert (n, d) == ([0], [True])

    def test_bound_beats_later_stop(self):
        # host loop retires at the bound before ever seeing the stop
        n, d = self._run([[5, 6, 7, 9]], [[9]], [2])
        assert (n, d) == ([2], [True])

    def test_inactive_rows_report_nothing(self):
        n, d = self._run(
            [[9, 6, 7, 8], [5, 6, 7, 8]], [[9], []], [10, 1],
            active=[False, False],
        )
        assert (n, d) == ([0, 0], [False, False])

    def test_emitted_limits_spec_padding(self):
        # padding zeros past emitted must not match a stop token 0: the
        # row neither truncates nor (crucially) retires on padding
        n, d = self._run([[5, 6, 0, 0]], [[0]], [10], emitted=[2])
        assert (n, d) == ([2], [False])
        # ... but a real 0 inside the emitted window still stops
        n, d = self._run([[5, 0, 6, 0]], [[0]], [10], emitted=[3])
        assert (n, d) == ([1], [True])

    def test_multiple_stop_tokens(self):
        n, d = self._run([[5, 6, 7, 8]], [[8, 6]], [10])
        assert (n, d) == ([1], [True])


class TestTokenStreamParity:
    async def test_greedy_dense_varied_bounds(self, params):
        # bounds 3/5/9 all land mid-block at steps=4 (retirement inside
        # a dispatch), 8 rides the exact block boundary
        jobs = [
            ([1, 2, 3], 3, {}), ([4, 5], 5, {}), ([6, 7, 8, 9], 9, {}),
            ([10, 11], 8, {}), ([1, 2, 3], 12, {}),
        ]
        await _parity(params, jobs)

    async def test_greedy_paged(self, params):
        jobs = [([1, 2, 3], 7, {}), ([4, 5], 10, {}), ([6, 7], 5, {})]
        await _parity(params, jobs, kv_layout="paged")

    async def test_seeded_sampled_parity(self, params):
        sp = SamplingParams(temperature=0.9, top_k=12)
        jobs = [
            ([1, 2, 3], 9, dict(sampling=sp, seed=7)),
            ([4, 5, 6], 6, dict(sampling=sp, seed=11)),
            ([7, 8], 11, dict(sampling=SamplingParams(temperature=0.6), seed=3)),
            ([9, 1], 7, {}),  # greedy row sharing the sampled batch
        ]
        streams, _ = await _parity(params, jobs)
        assert any(streams), "sampled workload produced no tokens"

    async def test_stop_token_mid_block(self, params):
        # find what greedy emits, then stop on a token observed mid-stream
        ref, _ = await _serve_all(
            params, _rt(overlap_dispatch=False), [([1, 2, 3], 12, {})]
        )
        stream = ref[0]
        stop = stream[5]  # lands mid-block at steps=4
        jobs = [
            ([1, 2, 3], 12, dict(stop_tokens=frozenset({stop}))),
            ([4, 5], 8, {}),
        ]
        streams, _ = await _parity(params, jobs)
        assert stop not in streams[0]  # the stop token is never delivered
        assert streams[0] == stream[: stream.index(stop)]

    async def test_spec_decode_parity(self, params):
        spec_jobs = [
            ([7, 7, 8, 9, 7, 7, 8], 10, {}),  # self-similar: drafter hits
            ([1, 2, 3], 6, {}),
        ]
        await _parity(params, spec_jobs, speculative=SpecConfig(k=3))

    async def test_chunked_admission_under_load(self, params):
        # more requests than slots: carries, waves, and retirement-driven
        # admission all interleave with in-flight dispatches
        jobs = [([1 + i, 2 + i], 4 + (i % 5), {}) for i in range(10)]
        await _parity(params, jobs, chunked_prefill=True)

    async def test_prefix_cache_hit_parity(self, params):
        shared = list(range(1, 33))  # two full 16-token pages
        jobs = [
            (shared + [40], 6, {}),
            (shared + [41], 6, {}),
            (shared + [42], 9, {}),
        ]
        await _parity(
            params, jobs,
            kv_layout="paged", chunked_prefill=True, prefix_cache=True,
        )


class TestLateRetirement:
    async def test_pages_freed_exactly_once_and_late(self, params):
        """Every page returns to the pool exactly once, and never while
        the dispatch that could still write it is in flight."""
        runtime = _rt(overlap_dispatch=True, kv_layout="paged")
        engine = InferenceEngine(CFG, runtime, params=params)
        freed_slots: list[int] = []
        real_free = engine._page_alloc.free

        def counting_free(slot):
            assert engine._pend is None or slot not in engine._pend["slot_set"], (
                "page reservation freed while its slot was still covered "
                "by an in-flight dispatch"
            )
            if engine._page_alloc.held_slots.get(slot):
                freed_slots.append(slot)
            real_free(slot)

        engine._page_alloc.free = counting_free
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            streams = await asyncio.gather(
                *[_gen(engine, [1 + i, 2], 5 + i) for i in range(4)]
            )
        finally:
            await engine.stop()
        assert all(len(s) == 5 + i for i, s in enumerate(streams))
        # four requests, four slots, no reuse: exactly one real free each
        assert len(freed_slots) == 4, f"frees: {freed_slots}"
        assert engine._page_alloc.free_pages == total_free
        assert engine.stats.overlap_wasted_tokens > 0  # late retirement ran

    async def test_prefix_refcounts_survive_late_retirement(self, params):
        """Shared prefix pages: refcounts never go negative, release is
        deferred past the in-flight dispatch, and the engine lands with
        every reference returned."""
        runtime = _rt(
            overlap_dispatch=True, kv_layout="paged",
            chunked_prefill=True, prefix_cache=True,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        prefix = engine._prefix
        real_release = prefix.release

        def checked_release(pages):
            # a double release (e.g. early free at retire AND the deferred
            # free at landing) would drive a refcount below zero here —
            # a newer dispatch for OTHER rows may legally be in flight
            for page in pages:
                assert prefix._refs[page] >= 1, (
                    f"page {page} released below zero refs"
                )
            # no in-flight dispatch may still COVER a row whose shared
            # pages these are: a retiring participant's release defers to
            # its landing, so any live in-flight row holding these pages
            # means an early release
            if engine._pend is not None:
                for slot, req in engine._pend["participants"]:
                    if engine._active.get(slot) is req:
                        assert not set(req.shared_pages) & set(pages), (
                            "shared pages released under a live in-flight "
                            "reader"
                        )
            real_release(pages)

        prefix.release = checked_release
        shared = list(range(1, 33))
        await engine.start()
        try:
            first = await _gen(engine, shared + [40], 6)
            assert len(first) == 6
            # second round hits the cache; short bounds retire mid-block
            streams = await asyncio.gather(
                *[_gen(engine, shared + [41 + i], 3 + i) for i in range(3)]
            )
        finally:
            await engine.stop()
        assert all(len(s) == 3 + i for i, s in enumerate(streams))
        assert engine.stats.prefix_hits >= 1
        # all references returned: every cached page sits at zero refs
        assert all(r == 0 for r in prefix._refs.values())

    async def test_deferred_release_happens_inside_flight_window(self, params):
        """The defer path actually engages: at least one retirement lands
        while a dispatch is in flight and routes through pend.deferred."""
        runtime = _rt(overlap_dispatch=True, kv_layout="paged")
        engine = InferenceEngine(CFG, runtime, params=params)
        deferred_seen = []
        real_land = engine._land_decode

        def spying_land(pend):
            deferred_seen.append(len(pend["deferred"]))
            return real_land(pend)

        engine._land_decode = spying_land
        await engine.start()
        try:
            await asyncio.gather(
                *[_gen(engine, [1 + i], 5) for i in range(3)]
            )
        finally:
            await engine.stop()
        assert any(n > 0 for n in deferred_seen), (
            "no retirement was deferred through an in-flight dispatch"
        )


class TestCancellationMidFlight:
    async def test_cancel_frees_once_and_delivers_nothing_after(self, params):
        runtime = _rt(overlap_dispatch=True, kv_layout="paged")
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            agen = engine.generate([1, 2, 3], max_new_tokens=64)
            got = []
            async for token in agen:
                got.append(token)
                if len(got) >= 2:
                    break
            assert len(engine._active) == 1
            request = next(iter(engine._active.values()))
            await agen.aclose()  # cancel with a dispatch in flight
            # let the scheduler reap + drain the in-flight dispatch
            for _ in range(50):
                await asyncio.sleep(0.02)
                if _settled(engine, total_free):
                    break
            assert not engine._active
            assert engine._pend is None
            assert engine._page_alloc.free_pages == total_free
            assert len(engine._free) == runtime.max_batch_size
            # a block already in flight at close time may legally deliver
            # (the cancel wasn't reaped yet); once the reap + drain have
            # run, NOTHING more may reach the closed queue
            while not request.out.empty():
                request.out.get_nowait()
            # bounded soak for a late thread-side delivery: the engine is
            # already drained above (pend None, active empty), so any
            # illegal delivery would have to land within a few ticks of
            # the reap — a long real-clock nap here was pure tax (ISSUE
            # 11 drive-by: residual real-sleep waits on tier-1)
            for _ in range(25):
                await asyncio.sleep(0.002)
            assert request.out.empty(), (
                "delivery to a cancelled consumer after the reap"
            )
            # the engine still serves
            follow_up = await _gen(engine, [4, 5], 4)
            assert len(follow_up) == 4
        finally:
            await engine.stop()


class TestStopTableCap:
    async def test_oversized_stop_set_faults_with_overlap(self, params):
        runtime = _rt(overlap_dispatch=True, max_stop_tokens=2)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            with pytest.raises(InferenceError, match="max_stop_tokens"):
                await _gen(engine, [1, 2], 4, stop_tokens=frozenset({5, 6, 7}))
            # within the cap still serves
            assert len(await _gen(engine, [1, 2], 4,
                                  stop_tokens=frozenset({500, 501}))) == 4
        finally:
            await engine.stop()

    async def test_lockstep_keeps_arbitrary_stop_sets(self, params):
        runtime = _rt(overlap_dispatch=False, max_stop_tokens=2)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            stream = await _gen(
                engine, [1, 2], 4, stop_tokens=frozenset(range(300, 310))
            )
            assert len(stream) <= 4
        finally:
            await engine.stop()


class TestOverlapTelemetry:
    async def test_gap_histogram_and_waste_surface(self, params):
        from calfkit_tpu.inference.client import JaxLocalModelClient

        runtime = _rt(overlap_dispatch=True)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            await asyncio.gather(*[_gen(engine, [1 + i], 6) for i in range(3)])
        finally:
            await engine.stop()
        # launches with a dispatch in flight observe a zero-gap sample
        assert engine.latency["dispatch_gap_ms"].count > 0
        # the client snapshot surfaces the new keys (live branch)
        client = JaxLocalModelClient(config="debug", runtime=runtime)
        client._engine = engine
        snap = client.stats_snapshot()
        assert snap["overlap_dispatch"] is True
        assert snap["overlap_wasted_tokens"] == (
            engine.stats.overlap_wasted_tokens
        )
        assert "dispatch_gap_p99" in snap["latency_ms"]
        # cold snapshot carries the same keys (zeros)
        cold = JaxLocalModelClient(config="debug", runtime=runtime)
        assert cold.stats_snapshot()["overlap_wasted_tokens"] == 0
        # EngineStats windowing covers the new counter
        cum, delta = engine.stats.snapshot_and_delta()
        assert "overlap_wasted_tokens" in cum
        assert "overlap_wasted_tokens" in delta


class TestQueuedCancellation:
    """ISSUE 5 satellite: cancellation of STILL-QUEUED entries, and the
    reap's ordering against a concurrent admission wave — the parity
    matrix above covers active-slot cancels only."""

    @pytest.mark.parametrize("overlap", [True, False])
    async def test_cancel_queued_request_vs_concurrent_admission(
        self, params, overlap
    ):
        from tests._chaos import assert_engine_drained, settle

        runtime = _rt(
            max_batch_size=2, kv_layout="paged", overlap_dispatch=overlap
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            # fill both slots with long-ish streams, then queue two more
            active = [
                asyncio.create_task(_gen(engine, [1 + i], 24))
                for i in range(2)
            ]
            await settle(lambda: len(engine._active) == 2)
            queued = [
                asyncio.create_task(_gen(engine, [10 + i], 24))
                for i in range(2)
            ]
            await settle(
                lambda: len(engine._pending) + len(engine._carry) == 2
            )
            # abandon both queued consumers while the actives keep the
            # engine mid-wave; the reap must drop the queued entries
            # without disturbing admission of fresh work
            for task in queued:
                task.cancel()
            fresh = asyncio.create_task(_gen(engine, [20], 8))
            for task in queued:
                with pytest.raises(asyncio.CancelledError):
                    await task
            # actives complete in full, the fresh submit admits and
            # completes — cancelled queue entries never held resources
            assert [len(s) for s in await asyncio.gather(*active)] == [24, 24]
            assert len(await fresh) == 8
            await settle(
                lambda: not engine._active and engine._pend is None
            )
            assert_engine_drained(engine, total_free)
            assert engine.stats.cancelled_requests == 2
        finally:
            await engine.stop()

    @pytest.mark.parametrize("overlap", [True, False])
    async def test_cancel_mid_chunked_admission_under_load(
        self, params, overlap
    ):
        """Cancel ONE member of a chunked-admission wave while its
        prefill chunks are still landing: the corpse is shed at
        activation, the surviving member streams in full, and every
        page the corpse reserved returns to the pool."""
        from tests._chaos import assert_engine_drained, settle

        runtime = _rt(
            max_batch_size=2, kv_layout="paged", chunked_prefill=True,
            prefill_chunk=16, overlap_dispatch=overlap,
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        total_free = engine._page_alloc.free_pages
        await engine.start()
        try:
            # same bucket (64): both join one admission wave of 4 chunks
            doomed = asyncio.create_task(
                _gen(engine, list(range(1, 60)), 16)
            )
            survivor = asyncio.create_task(
                _gen(engine, list(range(100, 158)), 16)
            )
            await settle(
                lambda: engine._inflight is not None
                and len(engine._inflight["wave"]) == 2,
                message="chunked admission wave never formed",
            )
            doomed.cancel()
            with pytest.raises(asyncio.CancelledError):
                await doomed
            assert len(await survivor) == 16
            await settle(
                lambda: not engine._active and engine._pend is None
                and engine._inflight is None
            )
            assert_engine_drained(engine, total_free)
            # the engine still admits chunked waves afterwards
            assert len(await _gen(engine, list(range(50)), 8)) == 8
        finally:
            await engine.stop()


class TestShedExpireParity:
    """The shed and expire paths must behave identically under the
    overlapped and lockstep schedulers: same typed errors, same
    counters, byte-identical streams for the admitted survivors."""

    async def _oversubscribe(self, params, overlap):
        runtime = _rt(
            max_batch_size=2, max_pending=2, overlap_dispatch=overlap
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            results = await asyncio.gather(
                *[_gen(engine, [1 + i], 8) for i in range(8)],
                return_exceptions=True,
            )
        finally:
            await engine.stop()
        served = {
            i: r for i, r in enumerate(results) if isinstance(r, list)
        }
        shed = {
            i for i, r in enumerate(results)
            if isinstance(r, EngineOverloadedError)
        }
        return served, shed, engine.stats

    async def test_shed_parity_overlap_vs_lockstep(self, params):
        served_on, shed_on, stats_on = await self._oversubscribe(
            params, True
        )
        served_off, shed_off, stats_off = await self._oversubscribe(
            params, False
        )
        assert shed_on == shed_off, "shed sets diverged across schedulers"
        assert shed_on, "oversubscription never shed"
        assert served_on == served_off, (
            "admitted survivors' streams diverged from the lockstep oracle"
        )
        assert stats_on.shed_requests == stats_off.shed_requests == len(
            shed_on
        )

    @pytest.mark.parametrize("overlap", [True, False])
    async def test_expire_parity_active_and_queued(self, params, overlap):
        from calfkit_tpu.exceptions import DeadlineExceededError
        from tests._chaos import assert_engine_drained, settle, virtual_clock

        with virtual_clock() as clock:
            runtime = _rt(
                max_batch_size=1, kv_layout="paged",
                overlap_dispatch=overlap,
            )
            engine = InferenceEngine(CFG, runtime, params=params)
            total_free = engine._page_alloc.free_pages
            await engine.start()
            try:
                active = asyncio.create_task(
                    _gen(engine, [1, 2], 64, deadline=clock.now + 5)
                )
                await settle(lambda: engine._active)
                queued = asyncio.create_task(
                    _gen(engine, [3, 4], 64, deadline=clock.now + 5)
                )
                await settle(
                    lambda: len(engine._pending) + len(engine._carry) == 1
                )
                clock.advance(10)
                with pytest.raises(DeadlineExceededError):
                    await active
                with pytest.raises(DeadlineExceededError):
                    await queued
                await settle(lambda: _settled(engine, total_free))
                assert_engine_drained(engine, total_free)
                assert engine.stats.expired_requests == 2
                assert engine.stats.cancelled_requests == 0
                # un-deadlined work still serves under the same scheduler
                assert len(await _gen(engine, [9], 8)) == 8
            finally:
                await engine.stop()
