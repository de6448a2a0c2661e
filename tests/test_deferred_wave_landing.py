"""A wave's first tokens come down with the dispatch that carried its last
chunk (ISSUE 37): the landing is no sync of its own, the wave's rows are
active and in the NEXT dispatch before their first tokens are on the host.

What that has to hold, each with the machinery the tree already had:

(a) the streams stay byte-identical to the lockstep oracle's with waves
    joining mid-decode: dense, recurrent, recurrent with routed experts,
    and with prefixes shared through the cache;
(b) a row that retires AT its landing (a first token that is a stop,
    ``max_new_tokens == 1``) is in a dispatch by then: it retires by the
    deferred path and that dispatch's column for it is discarded;
(c) the retire heap's horizon of a row activated before its first token
    agrees with ``_record_token``;
(d) a request cancelled or expired between the finalize program's enqueue
    and its landing, ``stop()`` and a wedge trip with a landing pending:
    nothing lost, leaked or delivered twice; slots, pages and the capacity
    ledger all accounted afterwards.
"""

import asyncio
import threading

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from calfkit_tpu.exceptions import DeadlineExceededError, EngineWedgedError  # noqa: E402
from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.sim import assert_engine_drained, settle, virtual_clock  # noqa: E402
from tests.arch_harness import GDN_MOE as FAMILY  # noqa: E402
from tests.arch_harness import HYBRID_MAMBA, both_forms_at_toy_size  # noqa: E402, F401 - an autouse fixture

GDN_MOE, HYBRID = FAMILY.toy, HYBRID_MAMBA.toy

DENSE = preset("debug")
LONG = list(range(3, 23))  # 20 tokens: a bucket of 32, two chunks of 16
SHORT = list(range(3, 13))  # 10 tokens: one chunk, so it is its wave's last


def _rt(**over) -> RuntimeConfig:
    kw = dict(
        max_batch_size=4, max_seq_len=128, prefill_chunk=16, decode_steps_per_dispatch=4,
        page_size=8, chunked_prefill=True, kv_layout="paged", window_buckets=(32, 128),
        compilation_cache=False, max_prefill_wave=2,
    )
    kw.update(over)
    return RuntimeConfig(**kw)


MODELS = {
    "dense": (DENSE, lambda: M.init_params(DENSE, jax.random.key(0), dtype=jnp.float32)),
    "recurrent": (HYBRID, lambda: M.init_params(HYBRID, jax.random.key(1))),
    "recurrent_expert": (GDN_MOE, lambda: FAMILY.seeded(GDN_MOE)),
}
_PARAMS: dict = {}


def _model(name: str):
    config, make = MODELS[name]
    if name not in _PARAMS:
        _PARAMS[name] = make()
    return config, _PARAMS[name]


async def _gen(engine, prompt, n, **kw):
    return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]


async def _decoding(engine) -> None:
    """A row is decoding and a dispatch is in flight: the next wave rides."""
    await settle(lambda: engine._active and engine.stats.decode_dispatches
                 and engine._pend is not None, interval=0.001, ticks=20000)


async def _idle(engine) -> None:
    """Every dispatch landed, every row retired, every slot back."""
    await settle(lambda: engine._pend is None and not engine._active
                 and len(engine._free) == engine.runtime.max_batch_size)


def _prompt(config, n: int, seed: int) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(3, config.vocab_size, n)]


async def _joining(config, params, runtime, jobs) -> "tuple[list, InferenceEngine]":
    """``jobs`` = [(prompt, max_new, kwargs), ...]: the first starts alone,
    every later one is submitted while rows decode, the last two at once (a
    wave of two rows).  Under the lockstep oracle the same order."""
    engine = InferenceEngine(config, runtime, params=params, seed=3)
    paged_alone = engine._paged and engine._prefix is None
    total_free = engine._page_alloc.free_pages if paged_alone else None
    await engine.start()
    try:
        tasks = []
        for i, (prompt, n, kw) in enumerate(jobs):
            if 0 < i < len(jobs) - 1 and runtime.overlap_dispatch:
                await _decoding(engine)
            tasks.append(asyncio.ensure_future(_gen(engine, prompt, n, **kw)))
        streams = list(await asyncio.gather(*tasks))
        await _idle(engine)
        assert_engine_drained(engine, total_free)
        return streams, engine
    finally:
        await engine.stop()


# ----------------------------------------------------------------- (a)
PARITY = {
    "dense": ("dense", {}),
    "dense_prefix_cache": ("dense", dict(prefix_cache=True)),
    "dense_unpaged": ("dense", dict(kv_layout="dense")),
    "recurrent": ("recurrent", {}),
    "recurrent_expert": ("recurrent_expert", {}),
}


def _parity_jobs(config, shared_prefix: bool) -> list:
    """Four requests in one bucket of two chunks (a chunk that is not its
    wave's last rides too; the last two form a wave of two rows); with
    ``shared_prefix`` six over three buckets, two of them behind a shared
    prefix of two whole pages.  Each engine builds its programs anew, several
    seconds a program for the recurrent models on the CPU: the scene is no
    larger than what it has to show."""
    if not shared_prefix:
        return [
            (_prompt(config, 20, 1), 24, {}),
            (_prompt(config, 25, 3), 1, {}),  # retires at its landing
            (_prompt(config, 21, 2), 9, {}),
            (_prompt(config, 19, 6), 6, {}),
        ]
    shared = _prompt(config, 16, seed=9)  # two whole pages of 8
    return [
        (_prompt(config, 20, 1), 40, {}),
        (shared + _prompt(config, 5, 2), 9, {}),
        (_prompt(config, 10, 3), 1, {}),  # retires at its landing
        (shared + _prompt(config, 3, 4), 12, {}),
        (_prompt(config, 37, 5), 7, {}),
        (_prompt(config, 12, 6), 6, {}),
    ]


def _served(config, params, runtime, jobs):
    """Two engines' worth of program builds pass the 60 s that an async test
    is given: a loop of its own, and a limit that still ends a hang."""
    return asyncio.run(asyncio.wait_for(_joining(config, params, runtime, jobs), timeout=240))


@pytest.mark.parametrize("case", sorted(PARITY))
def test_streams_equal_the_lockstep_oracle_with_waves_joining_mid_decode(case):
    name, over = PARITY[case]
    config, params = _model(name)
    jobs = _parity_jobs(config, bool(over.get("prefix_cache")))
    if not over.get("prefix_cache"):
        over = dict(over, window_buckets=(128,))  # one window: half the programs
    on, engine = _served(config, params, _rt(**over), jobs)
    off, oracle = _served(config, params, _rt(overlap_dispatch=False, **over), jobs)
    assert on == off, "streams diverged from the lockstep oracle"
    assert [len(s) for s in on] == [n for _, n, _ in jobs]
    counters = engine.stats.counters()
    # the first wave found no active rows: a sync of its own; the later ones rode
    assert counters["pipeline_drains_wave"] >= 1
    assert counters["wave_landings_deferred"] >= 2
    # the oracle lands every wave by a sync of its own
    assert oracle.stats.wave_landings_deferred == 0
    assert oracle.stats.pipeline_drains_wave >= 2
    if over.get("prefix_cache"):
        assert counters["prefix_hits"] >= 1 and oracle.stats.prefix_hits >= 1
    if config.moe:
        ours, theirs = engine.moe_expert_counts(), oracle.moe_expert_counts()
        # every wave's counters came down with its landing; the decode steps'
        # differ by the columns run for rows already retired
        assert ours.sum() >= theirs.sum() > 0


# ----------------------------------------------------------------- (b)
async def _riding_scene(over: dict, second: "tuple[list, int, dict]", spy=None):
    """One row decodes 48 tokens; ``second`` joins mid-decode, its one chunk
    riding a dispatch of the first's.  Returns (first stream, second stream
    or the exception it raised, the engine, the pool's free pages at start)."""
    config, params = _model("dense")
    engine = InferenceEngine(config, _rt(**over), params=params)
    total_free = engine._page_alloc.free_pages
    if spy is not None:
        spy(engine)
    await engine.start()
    try:
        first = asyncio.ensure_future(_gen(engine, LONG, 48))
        await _decoding(engine)
        prompt, n, kw = second
        (joined,) = await asyncio.gather(_gen(engine, prompt, n, **kw), return_exceptions=True)
        stream = await first
        await _idle(engine)
        return stream, joined, engine, total_free
    finally:
        await engine.stop()


@pytest.fixture(scope="module")
def alone():
    """What the two prompts of the scene answer when served alone, in lockstep."""
    async def run():
        config, params = _model("dense")
        engine = InferenceEngine(config, _rt(overlap_dispatch=False), params=params)
        await engine.start()
        try:
            return await _gen(engine, LONG, 48), await _gen(engine, SHORT, 8)
        finally:
            await engine.stop()

    return asyncio.run(run())


RETIRES_AT_ITS_LANDING = {
    "first_token_a_stop": lambda short: (dict(stop_tokens=frozenset({short[0]})), 8, []),
    "max_new_tokens_1": lambda short: ({}, 1, short[:1]),
    "second_token_a_stop": lambda short: (
        dict(stop_tokens=frozenset({short[1]})), 8, short[:1]),
}


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("case", sorted(RETIRES_AT_ITS_LANDING))
async def test_a_row_that_retires_at_its_deferred_landing(alone, case, prefix_cache):
    long_alone, short_alone = alone
    kw, n, want = RETIRES_AT_ITS_LANDING[case](short_alone)
    if short_alone[1] == short_alone[0] and case == "second_token_a_stop":
        pytest.skip("the toy model repeats its first token")
    stream, joined, engine, total_free = await _riding_scene(
        dict(prefix_cache=prefix_cache), (SHORT, n, kw))
    assert joined == want
    assert stream == long_alone  # the row it joined saw nothing of it
    counters = engine.stats.counters()
    assert counters["wave_landings_deferred"] == 1
    if case != "second_token_a_stop":
        # the dispatch after the landing carried the row: its column is pad
        assert counters["overlap_wasted_tokens"] >= 1
    assert_engine_drained(engine, None if prefix_cache else total_free)


def _rides(engine, wave) -> bool:
    """``wave``'s landing hangs on the dispatch in flight: its finalize
    program is enqueued and its first tokens are not down."""
    pend = engine._pend
    return pend is not None and pend["landing"] is not None and pend["landing"]["wave"] is wave


# ----------------------------------------------------------------- (c)
async def test_the_retire_heap_agrees_with_record_token_for_a_row_activated_before_its_first_token():
    horizons = []

    def spy(engine):
        original = engine._activate_wave

        def activate(wave):
            original(wave)
            for request in wave:
                horizons.append((_rides(engine, wave), request.generated,
                                 request.heap_entry[0] - engine._decode_clock))

        engine._activate_wave = activate

    await _riding_scene({}, (SHORT, 48, {}), spy)
    # the first wave landed by a sync (first token recorded), the second rode a
    # dispatch (none yet): the same budget gives the same horizon
    assert horizons == [(False, 1, 47), (True, 0, 47)]


# ----------------------------------------------------------------- (d)
def _between(engine, act) -> None:
    """Run ``act(wave)`` on the serve loop right after a wave whose landing
    rides a dispatch was activated: its finalize program is enqueued and its
    first tokens are not down."""
    original = engine._activate_wave

    def activate(wave):
        original(wave)
        if _rides(engine, wave):
            act(wave)

    engine._activate_wave = activate


def _before_activation(engine, act) -> None:
    """Run ``act(wave)`` on the tick thread right after the finalize program
    was enqueued behind a dispatch: before the serve loop activates the wave."""
    original = engine._finalize_inflight

    def finalize(logits, ride=None):
        wave = engine._inflight["wave"]
        landed = original(logits, ride)
        if ride is not None:
            act(wave)
        return landed

    engine._finalize_inflight = finalize


def _flag_cancelled(engine):
    def act(wave):
        for request in wave:
            request.cancelled = True
        engine._cancel_dirty = True

    return act


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("when", ["before_activation", "after_activation"])
async def test_a_cancel_between_the_enqueue_and_the_landing(alone, when, prefix_cache):
    def spy(engine):
        hook = _before_activation if when == "before_activation" else _between
        hook(engine, _flag_cancelled(engine))
        landed = engine._land_wave

        def land_wave(wave, *landing):
            # by its landing the cancelled row holds no slot: nothing recorded
            assert all(r.slot == -1 for r in wave if r.cancelled)
            landed(wave, *landing)

        engine._land_wave = land_wave

    stream, joined, engine, total_free = await _riding_scene(
        dict(prefix_cache=prefix_cache), (SHORT, 8, {}), spy)
    assert joined == []  # a cancelled stream ends, with nothing delivered
    assert stream == alone[0]
    counters = engine.stats.counters()
    assert counters["cancelled_requests"] == 1
    assert counters["wave_landings_deferred"] == 1
    assert_engine_drained(engine, None if prefix_cache else total_free)


async def test_a_deadline_that_passes_between_the_enqueue_and_the_landing(alone):
    with virtual_clock() as clock:
        def spy(engine):
            _between(engine, lambda wave: clock.advance(10))

        stream, joined, engine, total_free = await _riding_scene(
            {}, (SHORT, 8, dict(deadline=clock.now + 5)), spy)
    assert isinstance(joined, DeadlineExceededError)
    assert stream == alone[0]
    assert engine.stats.expired_requests == 1
    assert engine.stats.wave_landings_deferred == 1
    assert_engine_drained(engine, total_free)


async def test_stop_with_a_landing_pending_ends_every_stream_once():
    config, params = _model("dense")
    engine = InferenceEngine(config, _rt(), params=params)
    seen = {}

    def act(wave):
        seen["pend"] = engine._pend
        engine._running = False  # what stop() does first: the loop ends with this pass

    _between(engine, act)
    await engine.start()
    first = asyncio.ensure_future(_gen(engine, LONG, 48))
    await _decoding(engine)
    second = asyncio.ensure_future(_gen(engine, SHORT, 8))
    await settle(lambda: "pend" in seen)
    await asyncio.wait_for(engine._task, timeout=30)
    assert engine._pend is seen["pend"] and engine._pend["landing"] is not None
    await engine.stop()
    # both streams end (the second with nothing: its first token never came
    # down), neither twice
    assert await asyncio.wait_for(second, timeout=10) == []
    assert 0 < len(await asyncio.wait_for(first, timeout=10)) < 48
    assert engine._pend is None and not engine._active and engine._inflight is None


async def test_a_wedge_trip_with_a_landing_pending(alone, tmp_path, monkeypatch):
    monkeypatch.setenv("CALFKIT_FLIGHTREC_DIR", str(tmp_path))
    config, params = _model("dense")
    with virtual_clock() as clock:
        engine = InferenceEngine(config, _rt(watchdog_stall_s=0.5), params=params)
        total_free = engine._page_alloc.free_pages
        gate, armed, blocked = threading.Event(), threading.Event(), threading.Event()

        def chaos(point):
            # the tick that would land the dispatch the wave's landing rides:
            # a device grant that does not come back
            if point == "dispatch" and armed.is_set() and not gate.is_set():
                blocked.set()
                gate.wait(timeout=60)

        engine._chaos = chaos
        _between(engine, lambda wave: armed.set())
        await engine.start()
        try:
            first = asyncio.ensure_future(_gen(engine, LONG, 48))
            await _decoding(engine)
            second = asyncio.ensure_future(_gen(engine, SHORT, 8))
            await settle(blocked.is_set)
            assert engine._pend["landing"] is not None
            clock.advance(0.6)
            for stream in (first, second):
                with pytest.raises(EngineWedgedError):
                    await asyncio.wait_for(stream, timeout=10)
            assert engine.stats.watchdog_faulted == 2
            clock.advance(0.01)
            gate.set()
            await settle(lambda: not engine._wedged)
            await _idle(engine)
            assert_engine_drained(engine, total_free)
            assert engine.stats.wave_landings_deferred == 1
            assert await _gen(engine, SHORT, 8) == alone[1]  # serving resumes
        finally:
            gate.set()
            await engine.stop()
