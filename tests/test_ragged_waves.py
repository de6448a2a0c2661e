"""Ragged unified prefill+decode waves (ISSUE 6).

The correctness contract under test:

- TOKEN-STREAM PARITY: ragged-on output is byte-identical to the
  bifurcated oracle (``ragged_waves=False``, same chunked config) across
  greedy / seeded-sampled / chunked-prefill-under-load / prefix-cache-hit
  / spec-on / overlap-on / stop-token-mid-block;
- KERNEL MATH: the ragged attention law (query j attends kv positions
  < min(kv_len, start + j + 1)) serves decode (q_len=1), prefill-chunk
  (q_len=chunk), and verify (q_len=k+1) rows identically to the
  per-kind reference paths;
- ACCOUNTING: absorbed prefill rows count as dispatch participants
  (mean_batch_occupancy is the unified-wave fill metric), absorbed chunk
  tokens and unified dispatches surface through ``EngineStats`` /
  ``stats_snapshot()`` / the engine-stats record, and the budget knob
  actually bounds wave formation.
"""

import asyncio
from dataclasses import replace

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference import ragged as RG  # noqa: E402
from calfkit_tpu.inference.config import (  # noqa: E402
    RuntimeConfig,
    SpecConfig,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.inference.sampler import SamplingParams  # noqa: E402

CFG = preset("debug")


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _rt(**over):
    kw = dict(
        max_batch_size=4, max_seq_len=128, prefill_chunk=16,
        decode_steps_per_dispatch=4, page_size=16, chunked_prefill=True,
    )
    kw.update(over)
    return RuntimeConfig(**kw)


async def _gen(engine, prompt, n, **kw):
    return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]


async def _serve_all(params, runtime, jobs, config=CFG):
    engine = InferenceEngine(config, runtime, params=params)
    await engine.start()
    try:
        return await asyncio.gather(
            *[_gen(engine, p, n, **kw) for p, n, kw in jobs]
        ), engine
    finally:
        await engine.stop()


async def _parity(params, jobs, **rt_over):
    """The A/B harness: same jobs, ragged on vs off (the bifurcated
    oracle), streams must match byte-for-byte."""
    on, eng_on = await _serve_all(
        params, _rt(ragged_waves=True, **rt_over), jobs
    )
    off, eng_off = await _serve_all(
        params, _rt(ragged_waves=False, **rt_over), jobs
    )
    assert on == off, "ragged-on streams diverged from the bifurcated oracle"
    assert eng_on._ragged, "ragged lane never engaged"
    assert not eng_off._ragged
    assert eng_off.stats.prefill_absorbed_tokens == 0
    assert eng_off.stats.unified_dispatches == 0
    return on, eng_on


# --------------------------------------------------------------- kernel math
class TestRaggedAttentionMath:
    """The unified mask law vs the per-kind reference paths."""

    def _mixed(self, seed=0, B=3, K=2, G=4, hd=8, W=32, S=5):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((B, S, K * G, hd)), jnp.float32)
        kc = jnp.asarray(rng.standard_normal((B, K, W, hd)), jnp.float32)
        vc = jnp.asarray(rng.standard_normal((B, K, W, hd)), jnp.float32)
        return q, kc, vc

    def test_descriptor_build(self):
        rows = [
            RG.RaggedRow(RG.KIND_DECODE, start=7, q_len=1, kv_len=7),
            RG.RaggedRow(RG.KIND_PREFILL, start=16, q_len=16, kv_len=32),
            RG.RaggedRow(RG.KIND_VERIFY, start=9, q_len=4, kv_len=9),
        ]
        starts, q_lens, kv_lens = RG.build_descriptors(rows)
        assert starts == [7, 16, 9]
        assert q_lens == [1, 16, 4]
        assert kv_lens == [7, 32, 9]
        assert [r.kind_name for r in rows] == ["decode", "prefill", "verify"]
        assert rows[1].tokens() == 16

    def test_decode_row_matches_plain_attention(self):
        """q_len=1 at start=kv_len=lens reduces to the decode length mask."""
        q, kc, vc = self._mixed(S=1)
        lens = jnp.asarray([9, 30, 4], jnp.int32)
        got = M.ragged_attention_xla(q, kc, vc, lens, lens)
        # reference: attention_xla with explicit per-row positions
        want = M.attention_xla(q, kc, vc, (lens - 1)[:, None], lens)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_prefill_chunk_row_matches_causal_prefill(self):
        """q_len=S at start=offset with kv_len=offset+S IS the causal
        prefill mask over the scratch."""
        q, kc, vc = self._mixed()
        S = q.shape[1]
        starts = jnp.asarray([4, 0, 16], jnp.int32)
        got = M.ragged_attention_xla(q, kc, vc, starts, starts + S)
        pos = starts[:, None] + jnp.arange(S)[None, :]
        want = M.attention_xla(q, kc, vc, pos, starts + S)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )

    def test_mixed_wave_one_call(self):
        """One call serves a batch mixing all three row kinds; each row
        equals its own per-kind reference."""
        q, kc, vc = self._mixed()
        S = q.shape[1]
        rows = [
            RG.RaggedRow(RG.KIND_DECODE, start=9, q_len=1, kv_len=9),
            RG.RaggedRow(RG.KIND_PREFILL, start=8, q_len=S, kv_len=8 + S),
            RG.RaggedRow(RG.KIND_VERIFY, start=12, q_len=S, kv_len=12),
        ]
        starts, q_lens, kv_lens = RG.build_descriptors(rows)
        got = M.ragged_attention_xla(
            q, kc, vc,
            jnp.asarray(starts, jnp.int32), jnp.asarray(kv_lens, jnp.int32),
        )
        for b, row in enumerate(rows):
            pos = row.start + jnp.arange(row.q_len)[None, :]
            want = M.attention_xla(
                q[b:b + 1, : row.q_len], kc[b:b + 1], vc[b:b + 1],
                pos, jnp.asarray([row.kv_len], jnp.int32),
            )
            np.testing.assert_allclose(
                np.asarray(got[b:b + 1, : row.q_len]), np.asarray(want),
                rtol=1e-5, atol=1e-5,
                err_msg=f"row kind {row.kind_name} diverged",
            )


# ------------------------------------------------------------- budget math
class TestBudgetMath:
    def test_auto_budget_never_second_guesses_admission(self):
        budget = RG.token_budget(0, 32, 8, 512, 8)
        assert budget == 32 * 8 + 8 * 512
        # a full-width wave alongside a full decode batch always fits
        assert RG.fits_budget(budget, 32, 8, 8, 512)

    def test_explicit_budget_bounds_absorption_and_width(self):
        budget = RG.token_budget(96, 8, 8, 32, 8)
        assert budget == 96
        assert RG.fits_budget(budget, 4, 8, 2, 32)  # 32 + 64 <= 96
        assert not RG.fits_budget(budget, 4, 8, 3, 32)  # 32 + 96 > 96
        assert RG.wave_width_cap(budget, 4, 8, 32) == 2
        # the head always forms, even with zero slack
        assert RG.wave_width_cap(budget, 12, 8, 32) == 1

    async def test_budget_caps_wave_width_at_formation(self, params):
        """An explicit tight budget really narrows admission waves."""
        runtime = _rt(
            ragged_waves=True, max_prefill_wave=4,
            ragged_token_budget=16 + 4 * 4,  # one 16-token chunk row + decode
        )
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            streams = await asyncio.gather(
                *[_gen(engine, [1 + i, 2], 4) for i in range(4)]
            )
        finally:
            await engine.stop()
        assert all(len(s) == 4 for s in streams)
        # width-capped waves: more waves of width 1 instead of one of 4
        assert engine.stats.prefix_hits == 0  # sanity: no reuse in play


# ----------------------------------------------------------- stream parity
class TestTokenStreamParity:
    async def test_greedy_varied_bounds(self, params):
        jobs = [
            ([1, 2, 3], 3, {}), ([4, 5], 5, {}), ([6, 7, 8, 9], 9, {}),
            ([10, 11], 8, {}), ([1, 2, 3], 12, {}),
        ]
        await _parity(params, jobs)

    async def test_greedy_paged(self, params):
        jobs = [([1, 2, 3], 7, {}), ([4, 5], 10, {}), ([6, 7], 5, {})]
        streams, eng = await _parity(params, jobs, kv_layout="paged")
        assert any(streams)

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
        ref, _ = await _serve_all(
            params, _rt(ragged_waves=False), [([1, 2, 3], 12, {})]
        )
        stream = ref[0]
        stop = stream[5]  # lands mid-block at steps=4
        jobs = [
            ([1, 2, 3], 12, dict(stop_tokens=frozenset({stop}))),
            ([4, 5], 8, {}),
        ]
        streams, _ = await _parity(params, jobs)
        assert stop not in streams[0]
        assert streams[0] == stream[: stream.index(stop)]

    async def test_chunked_prefill_under_load(self, params):
        # more requests than slots: carries, waves, budget-capped
        # formation, and retirement-driven admission all interleave with
        # in-flight fused dispatches — multi-chunk prompts AND staggered
        # decode bounds, so retirements free slots while others still
        # decode and the follow-up waves get absorbed into live dispatches
        jobs = [
            (list(range(1 + i, 28 + i)), 4 + 3 * i, {}) for i in range(10)
        ]
        streams, eng = await _parity(params, jobs)
        assert eng.stats.prefill_absorbed_tokens > 0, (
            "under load, no prefill chunk ever rode a decode dispatch"
        )
        assert eng.stats.unified_dispatches > 0

    async def test_prefix_cache_hit_parity(self, params):
        shared = list(range(1, 33))  # two full 16-token pages
        jobs = [
            (shared + [40], 6, {}),
            (shared + [41], 6, {}),
            (shared + [42], 9, {}),
        ]
        streams, eng = await _parity(
            params, jobs, kv_layout="paged", prefix_cache=True,
        )
        assert eng.stats.prefix_hits >= 1

    async def test_spec_decode_parity(self, params):
        spec_jobs = [
            ([7, 7, 8, 9, 7, 7, 8] * 3, 10, {}),  # self-similar: drafter hits
            ([1, 2, 3], 6, {}),
        ]
        streams, eng = await _parity(
            params, spec_jobs, speculative=SpecConfig(k=3)
        )
        # spec stays lockstep: the wave rides the lane but no dispatch
        # fuses, so nothing may be double-counted as absorbed
        assert eng.stats.unified_dispatches == 0

    async def test_lockstep_config_degrades_to_bifurcated(self, params):
        """overlap_dispatch=False has no launch to fuse into: the flag
        stays set but the engine runs (and reports) bifurcated."""
        engine = InferenceEngine(
            CFG, _rt(ragged_waves=True, overlap_dispatch=False),
            params=params,
        )
        assert not engine._ragged
        await engine.start()
        try:
            assert len(await _gen(engine, [1, 2, 3], 6)) == 6
        finally:
            await engine.stop()
        assert engine.stats.unified_dispatches == 0


# -------------------------------------------------------------- accounting
class TestRaggedAccounting:
    async def test_occupancy_counts_absorbed_rows(self, params):
        """A dispatch that absorbed a chunk reports decode+chunk rows —
        occupancy with absorption must beat the same workload without."""
        jobs = [
            (list(range(1, 28)), 6 + 4 * i, {}) for i in range(6)
        ]
        on, eng_on = await _serve_all(
            params, _rt(ragged_waves=True, max_batch_size=4), jobs
        )
        off, eng_off = await _serve_all(
            params, _rt(ragged_waves=False, max_batch_size=4), jobs
        )
        assert on == off
        assert eng_on.stats.prefill_absorbed_tokens > 0
        assert eng_on.stats.mean_occupancy > eng_off.stats.mean_occupancy
        assert (
            eng_on.stats.mean_tokens_per_dispatch
            > eng_off.stats.mean_tokens_per_dispatch
        )

    async def test_snapshot_and_record_surface_ragged_keys(self, params):
        from calfkit_tpu.inference.client import JaxLocalModelClient
        from calfkit_tpu.models.records import EngineStatsRecord

        runtime = _rt(ragged_waves=True)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            # oversubscribed + staggered bounds: later waves form while
            # earlier rows still decode, so absorption actually happens
            await asyncio.gather(
                *[
                    _gen(engine, list(range(1 + i, 28 + i)), 4 + 3 * i)
                    for i in range(8)
                ]
            )
        finally:
            await engine.stop()
        client = JaxLocalModelClient(config="debug", runtime=runtime)
        client._engine = engine
        snap = client.stats_snapshot()
        assert snap["ragged_waves"] is True
        assert snap["prefill_absorbed_tokens"] == (
            engine.stats.prefill_absorbed_tokens
        )
        assert snap["tokens_per_dispatch"] > 0
        record = EngineStatsRecord(node_id="n1", **snap)
        assert record.ragged_waves is True
        assert record.prefill_absorbed_tokens > 0
        # cold snapshot carries the same keys (zeros), effective gating
        cold = JaxLocalModelClient(config="debug", runtime=runtime)
        csnap = cold.stats_snapshot()
        assert csnap["ragged_waves"] is True
        assert csnap["prefill_absorbed_tokens"] == 0
        plain = JaxLocalModelClient(
            config="debug", runtime=RuntimeConfig(ragged_waves=True)
        )
        assert plain.stats_snapshot()["ragged_waves"] is False  # no chunk lane
        # EngineStats windowing covers the new counters
        cum, delta = engine.stats.snapshot_and_delta()
        assert "prefill_absorbed_tokens" in cum
        assert "unified_dispatches" in delta

    async def test_ck_stats_batch_occ_column(self, params):
        from calfkit_tpu.cli.obs import render_stats_table
        from calfkit_tpu.inference.client import JaxLocalModelClient
        from calfkit_tpu.models.records import EngineStatsRecord

        runtime = _rt(ragged_waves=True)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            await _gen(engine, list(range(1, 28)), 6)
        finally:
            await engine.stop()
        client = JaxLocalModelClient(config="debug", runtime=runtime)
        client._engine = engine
        record = EngineStatsRecord(
            node_id="node-a", **client.stats_snapshot()
        )
        table = render_stats_table([record])
        assert "BATCH OCC" in table and "TOK/DISP" in table
        # the ragged marker rides the lifetime occupancy cell
        assert "*" in table

    async def test_flightrec_journals_ragged_waves(self, params):
        runtime = _rt(ragged_waves=True, flightrec_events=512)
        engine = InferenceEngine(CFG, runtime, params=params)
        await engine.start()
        try:
            await asyncio.gather(
                *[
                    _gen(engine, list(range(1 + i, 28 + i)), 4 + 3 * i)
                    for i in range(8)
                ]
            )
        finally:
            await engine.stop()
        from calfkit_tpu.observability import flightrec

        codes = [e[2] for e in engine._journal._ring if e is not None]
        assert flightrec.EV_RAGGED_WAVE in codes


# ------------------------------------------- paged decode read in place
# the debug preset's heads are 16 wide: the kernel that reads live pages in
# place wants whole lane tiles, so these engines get heads of 128 (G = 2)
WIDE = replace(CFG, name="debug-wide", d_model=256, n_heads=2, n_kv_heads=1)


@pytest.fixture(scope="module")
def wide_params():
    return M.init_params(WIDE, jax.random.key(1), dtype=jnp.float32)


async def _serve_wide(wide_params, jobs, **rt_over):
    return await _serve_all(
        wide_params, _rt(kv_layout="paged", ragged_waves=True, **rt_over),
        jobs, config=WIDE,
    )


SAMPLED = SamplingParams(temperature=0.9, top_k=12)
IN_PLACE_JOBS = {
    # more requests than slots: waves ride live dispatches, rows retire
    # and free their pages while others decode, prompts span page edges
    "greedy": [
        (list(range(1 + i, 20 + 3 * i)), 5 + 3 * i, {}) for i in range(7)
    ],
    "seeded-sampled": [
        ([1, 2, 3], 9, dict(sampling=SAMPLED, seed=7)),
        (list(range(4, 40)), 6, dict(sampling=SAMPLED, seed=11)),
        ([7, 8], 11, dict(sampling=SamplingParams(temperature=0.6), seed=3)),
        ([9, 1], 7, {}),  # a greedy row in the sampled batch
        (list(range(2, 19)), 8, dict(sampling=SAMPLED, seed=5)),
    ],
}


class TestPagedDecodeInPlace:
    @pytest.mark.parametrize("jobs", sorted(IN_PLACE_JOBS))
    def test_token_parity_with_the_xla_gather(self, wide_params, jobs):
        """paged + chunked + overlap + ragged: the kernel's streams are the
        XLA gather path's, token for token, and the kernel ran.  Two engines'
        builds, one of them the interpreted kernel's: 57-65 s of the 60 an
        async test is given when every core of a tier-1 run is taken (20 s
        alone), so it runs on a loop of its own under a longer limit."""
        asyncio.run(asyncio.wait_for(self._token_parity(wide_params, jobs), timeout=240))

    async def _token_parity(self, wide_params, jobs):
        from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

        before = KERNEL_TRACES["paged_decode", "interpreted"]
        want, xla = await _serve_wide(
            wide_params, IN_PLACE_JOBS[jobs], attention_impl="xla"
        )
        assert KERNEL_TRACES["paged_decode", "interpreted"] == before
        got, pal = await _serve_wide(
            wide_params, IN_PLACE_JOBS[jobs],
            attention_impl="pallas_interpret",
        )
        assert got == want
        assert all(len(s) == n for s, (_, n, _) in zip(got, IN_PLACE_JOBS[jobs]))
        # traced at most once a process for these shapes (the entry point
        # is a jit of its own)
        assert KERNEL_TRACES["paged_decode", "interpreted"] > 0
        assert pal._ragged and pal.stats.unified_dispatches > 0
        assert (xla._attn_impl, pal._attn_impl) == ("xla", "pallas_interpret")

    async def test_page_sums_by_difference(self, wide_params):
        """``decode_pages_live`` / ``decode_pages_window`` over a scripted
        run: one row of a known length, dispatch by dispatch."""
        engine = InferenceEngine(
            WIDE,
            _rt(kv_layout="paged", max_batch_size=4, window_buckets=(64, 128)),
            params=wide_params,
        )
        await engine.start()
        try:
            zero = engine.stats.counters()
            assert zero["decode_pages_live"] == zero["decode_pages_window"] == 0
            # the process's registry is shared by every engine of the run
            exported = engine.metrics["decode_pages_live"].value
            # 20 prompt tokens, page 16: 2 pages live from the first step;
            # the first token comes from the prefill, 12 more from three
            # 4-step dispatches at lengths 20, 24, 28 (2 pages each) in
            # the 64-token window (4 pages x 4 rows)
            out = await _gen(engine, list(range(1, 21)), 13)
            assert len(out) == 13
            mid = engine.stats.counters()
            steps = 4 * (mid["decode_dispatches"] - zero["decode_dispatches"])
            assert steps >= 12
            assert mid["decode_pages_live"] == 2 * steps
            assert mid["decode_rows_live"] == steps  # one row walks: a mean walk of 2 pages
            assert mid["decode_pages_window"] == 4 * 4 * steps
            # a longer row in a wider window, by difference
            await _gen(engine, list(range(1, 71)), 5)  # 70 tokens: 5 pages
            end = engine.stats.counters()
            steps = 4 * (end["decode_dispatches"] - mid["decode_dispatches"])
            assert end["decode_pages_live"] - mid["decode_pages_live"] == 5 * steps
            assert end["decode_rows_live"] - mid["decode_rows_live"] == steps
            assert (
                end["decode_pages_window"] - mid["decode_pages_window"]
                == 4 * 8 * steps
            )
            engine._sync_metric_counters()
            assert (
                engine.metrics["decode_pages_live"].value - exported
                == end["decode_pages_live"]
            )
        finally:
            await engine.stop()

    async def test_dense_layout_counts_no_pages(self, params):
        (_,), engine = await _serve_all(params, _rt(), [([1, 2, 3], 6, {})])
        assert engine.stats.decode_pages_window == 0
