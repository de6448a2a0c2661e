"""Speculative decoding: drafters, batched verify, ragged acceptance.

The correctness contract under test (ISSUE 1 acceptance):

- greedy speculative output is TOKEN-EXACT vs non-speculative greedy,
  per request, across plain / chunked-prefill / prefix-cache-hit
  admission paths and both KV layouts;
- sampled speculative output keeps the target-model distribution
  (rejection sampling against the same filtered logits);
- ragged acceptance needs no physical KV rollback — rejected positions
  sit beyond the advanced length, prefix-cache pages are never touched;
- a request cancelled mid-speculation-wave reclaims its slot/pages.
"""

import asyncio

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import (  # noqa: E402
    RuntimeConfig,
    SpecConfig,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.inference.sampler import (  # noqa: E402
    SamplingParams,
    filtered_logits,
    spec_accept_slots,
)
from calfkit_tpu.inference.spec import NgramDrafter  # noqa: E402

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


class TestNgramDrafter:
    def _drafter(self, k=4, ngram_max=3, ngram_min=1):
        return NgramDrafter(
            SpecConfig(k=k, ngram_max=ngram_max, ngram_min=ngram_min)
        )

    def test_proposes_continuation_of_repeated_pattern(self):
        d = self._drafter()
        history = [9, 1, 2, 3, 4, 5, 8, 1, 2, 3]
        # tail [1, 2, 3] matched earlier -> continuation [4, 5, 8, 1]
        assert d.propose([(0, history)]) == [[4, 5, 8, 1]]

    def test_most_recent_match_wins(self):
        d = self._drafter(k=1, ngram_max=2)
        history = [1, 2, 7, 5, 1, 2, 9, 5, 1, 2]
        # [1, 2] occurs at 0 (-> 7) and 4 (-> 9); the recent one wins
        assert d.propose([(0, history)]) == [[9]]

    def test_longer_tail_preferred(self):
        d = self._drafter(k=1, ngram_max=3)
        history = [5, 1, 2, 3, 8, 0, 2, 3, 6, 1, 2, 3]
        # the 3-gram [1,2,3] (-> 8) beats the more recent 2-gram [2,3] (-> 6)
        assert d.propose([(0, history)]) == [[8]]

    def test_no_match_proposes_nothing(self):
        d = self._drafter()
        assert d.propose([(0, [1, 2, 3, 4, 5])]) == [[]]
        assert d.propose([(0, [7])]) == [[]]
        assert d.propose([(0, [])]) == [[]]

    def test_proposals_capped_at_k(self):
        d = self._drafter(k=2)
        history = [1, 2, 3, 4, 5, 6, 1, 2]
        assert d.propose([(0, history)]) == [[3, 4]]

    def test_alignment_no_false_byte_match(self):
        # int32 byte view: token 0x01020304-style overlaps must not count.
        # [258, 1] vs tail [2]: no token-level 2 anywhere earlier.
        d = self._drafter(k=2, ngram_max=1)
        assert d.propose([(0, [513, 2, 513, 3, 2])]) == [[513, 3]]


class TestSpecAcceptMath:
    """sampler.spec_accept_slots in isolation: the distribution contract."""

    def _run(self, row_logits, drafts_row, temp_val, B=8192, seed=1):
        S, V = row_logits.shape
        logits = jnp.broadcast_to(row_logits, (B, S, V))
        drafts = jnp.broadcast_to(
            jnp.asarray(drafts_row, jnp.int32)[None], (B, S - 1)
        )
        ndraft = jnp.full((B,), S - 1, jnp.int32)
        keys = jax.random.split(jax.random.key(seed), B)
        temp = jnp.full((B,), temp_val, jnp.float32)
        top_k = jnp.zeros((B,), jnp.int32)
        top_p = jnp.ones((B,), jnp.float32)
        out, emitted = spec_accept_slots(
            logits, drafts, ndraft, jnp.zeros((B,), jnp.int32), keys,
            temp, top_k, top_p, sampled=temp_val > 0,
        )
        return np.asarray(out), np.asarray(emitted)

    def test_greedy_accepts_exact_matches_only(self):
        V = 8
        row = jnp.eye(3, V) * 9.0  # argmax chain: 0, 1, 2
        out, emitted = self._run(row, [0, 1], 0.0, B=4)
        # both drafts match -> all accepted + bonus argmax(pos 2) = 2
        assert emitted.tolist() == [3] * 4
        assert out[0].tolist() == [0, 1, 2]
        out, emitted = self._run(row, [0, 5], 0.0, B=4)
        # second draft wrong -> accept 1, correct with argmax(pos 1) = 1
        assert emitted.tolist() == [2] * 4
        assert out[0][:2].tolist() == [0, 1]

    def test_sampled_marginal_matches_target(self):
        """Emitted-token marginals must equal the filtered target
        distribution — the rejection-sampling guarantee, checked
        empirically over many PRNG rows."""
        V = 8
        key = jax.random.key(3)
        row = jax.random.normal(key, (2, V)) * 1.5
        temp = 0.8
        p = np.asarray(jax.nn.softmax(filtered_logits(
            row, jnp.full((2,), temp), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.float32),
        ), axis=-1))
        # draft position 0 with a HIGH-probability token so plenty of rows
        # accept and position 1's conditional has statistics
        d0 = int(np.argmax(p[0]))
        out, emitted = self._run(row, [d0], temp)
        B = len(out)
        emp0 = np.bincount(out[:, 0], minlength=V) / B
        assert np.abs(emp0 - p[0]).max() < 0.02, (emp0, p[0])
        acc = out[out[:, 0] == d0]  # rows that accepted the draft
        assert len(acc) > B * p[0][d0] * 0.8
        emp1 = np.bincount(acc[:, 1], minlength=V) / len(acc)
        assert np.abs(emp1 - p[1]).max() < 0.03, (emp1, p[1])

    def test_sampled_rejection_resamples_off_draft(self):
        """A rejected draft's correction must come from the residual (the
        draft token itself is excluded)."""
        V = 6
        row = jnp.zeros((2, V))  # uniform target
        # draft a token, temp 1: p(d) = 1/6, ~5/6 of rows reject
        out, emitted = self._run(row, [4], 1.0)
        rejected = out[emitted == 1]
        assert len(rejected) > 0
        # the correction for a rejected point-mass draft NEVER re-emits it
        assert not (rejected[:, 0] == 4).any()

    def test_undrafted_positions_never_accepted(self):
        V = 4
        row = jnp.eye(2, V) * 9.0
        B = 4
        logits = jnp.broadcast_to(row, (B, 2, V))
        drafts = jnp.zeros((B, 1), jnp.int32)  # token 0 == argmax(pos 0)
        ndraft = jnp.zeros((B,), jnp.int32)  # but NOT actually drafted
        out, emitted = spec_accept_slots(
            logits, drafts, ndraft, jnp.zeros((B,), jnp.int32),
            jax.random.split(jax.random.key(0), B),
            jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
            jnp.ones((B,), jnp.float32), sampled=False,
        )
        assert emitted.tolist() == [1] * B  # only the correction token


class TestSpecGreedyParity:
    """Token-exact greedy parity, spec on vs off, across admission paths
    and KV layouts — the tentpole's pinned acceptance criterion."""

    PROMPTS = ([1, 5, 9, 13], list(range(2, 34)), [7, 8, 9] * 5)

    async def _parity(self, params, base_rt, spec_rt, prompts=None, n=20):
        base = InferenceEngine(CFG, base_rt, params=params)
        spec = InferenceEngine(CFG, spec_rt, params=params)
        await base.start()
        await spec.start()
        for prompt in prompts or self.PROMPTS:
            want = await _gen(base, prompt, n)
            got = await _gen(spec, prompt, n)
            assert got == want, f"spec diverged for prompt len {len(prompt)}"
        await base.stop()
        await spec.stop()

    async def test_dense_plain_admission(self, params):
        await self._parity(
            params, _rt(), _rt(speculative=SpecConfig(k=4))
        )

    async def test_paged_plain_admission(self, params):
        await self._parity(
            params,
            _rt(kv_layout="paged"),
            _rt(kv_layout="paged", speculative=SpecConfig(k=3)),
        )

    async def test_chunked_prefill_admission(self, params):
        kw = dict(chunked_prefill=True)
        await self._parity(
            params, _rt(**kw), _rt(speculative=SpecConfig(k=4), **kw),
            prompts=(list(range(2, 50)),),
        )

    async def test_prefix_cache_hit_admission(self, params):
        """The SECOND identical prompt admits through prefix-page reuse;
        its speculative output must still match non-speculative greedy."""
        kw = dict(kv_layout="paged", chunked_prefill=True, prefix_cache=True)
        base = InferenceEngine(CFG, _rt(**kw), params=params)
        spec = InferenceEngine(
            CFG, _rt(speculative=SpecConfig(k=4), **kw), params=params
        )
        await base.start()
        await spec.start()
        prompt = list(range(2, 50))  # two full pages: cacheable prefix
        want_cold = await _gen(base, prompt, 16)
        want_hit = await _gen(base, prompt, 16)
        got_cold = await _gen(spec, prompt, 16)
        got_hit = await _gen(spec, prompt, 16)
        assert spec.stats.prefix_hits > 0  # the hit path actually ran
        assert got_cold == want_cold
        assert got_hit == want_hit == want_cold
        await base.stop()
        await spec.stop()

    async def test_wave_shrinks_near_max_seq(self, params):
        """Rows near max_seq must shrink the verify wave instead of
        letting chunk writes clamp backward over valid history."""
        base_rt = _rt(max_seq_len=32, prefill_chunk=16)
        spec_rt = _rt(
            max_seq_len=32, prefill_chunk=16, speculative=SpecConfig(k=4)
        )
        base = InferenceEngine(CFG, base_rt, params=params)
        spec = InferenceEngine(CFG, spec_rt, params=params)
        await base.start()
        await spec.start()
        prompt = list(range(2, 18))  # 16 tokens; room for ~15 new
        want = await _gen(base, prompt, 100)  # stops at the seq bound
        got = await _gen(spec, prompt, 100)
        assert got == want
        assert len(got) < 100  # the bound actually engaged
        await base.stop()
        await spec.stop()

    async def test_mixed_batch_spec_isolation(self, params):
        """Concurrent requests (ragged per-row acceptance) must not
        perturb each other's greedy streams."""
        spec = InferenceEngine(
            CFG, _rt(speculative=SpecConfig(k=4)), params=params
        )
        await spec.start()
        solo = await _gen(spec, [7, 8, 9], 12)
        results = await asyncio.gather(
            _gen(spec, [7, 8, 9], 12),
            _gen(spec, [7, 8, 9] * 4, 12),  # self-similar: drafts fire
            _gen(spec, list(range(20, 30)), 12),
        )
        assert results[0] == solo
        await spec.stop()


class TestSpecSampled:
    async def test_seeded_spec_sampling_reproducible(self, params):
        engine = InferenceEngine(
            CFG, _rt(speculative=SpecConfig(k=3)), params=params
        )
        await engine.start()
        sp = SamplingParams(temperature=1.2, top_k=50)
        out1 = await _gen(engine, [1, 5, 9, 13], 12, sampling=sp, seed=7)
        out2 = await _gen(engine, [1, 5, 9, 13], 12, sampling=sp, seed=7)
        assert out1 == out2 and len(out1) == 12
        await engine.stop()

    async def test_mixed_greedy_and_sampled_rows(self, params):
        """A sampled neighbor in the verify wave must not perturb a greedy
        row's exact output."""
        engine = InferenceEngine(
            CFG, _rt(speculative=SpecConfig(k=3)), params=params
        )
        await engine.start()
        baseline = await _gen(engine, [2, 4, 6], 10)

        async def sampled(i):
            return await _gen(
                engine, [3 + i, 7, 11], 10,
                sampling=SamplingParams(temperature=1.5, top_p=0.9), seed=i,
            )

        crowd, *_rest = await asyncio.gather(
            _gen(engine, [2, 4, 6], 10), sampled(1), sampled(2)
        )
        assert crowd == baseline
        await engine.stop()


class TestSpecSchedulerIntegrity:
    async def test_cancel_mid_speculation_wave(self, params):
        """Abandoning a stream mid-wave reclaims slot + pages and the
        engine keeps serving (the reap crosses a spec tick in flight)."""
        engine = InferenceEngine(
            CFG,
            _rt(kv_layout="paged", speculative=SpecConfig(k=4)),
            params=params,
        )
        await engine.start()
        agen = engine.generate([7, 8, 9] * 5, max_new_tokens=64)
        got = 0
        async for _ in agen:
            got += 1
            if got >= 3:
                break  # abandon while speculation waves are in flight
        await agen.aclose()
        out = await _gen(engine, [4, 5], 6)
        assert len(out) == 6
        for _ in range(100):
            if not engine._page_alloc.held_slots:
                break
            await asyncio.sleep(0.05)
        assert not engine._page_alloc.held_slots
        assert not engine._active
        await engine.stop()

    async def test_ragged_acceptance_no_page_leaks_under_prefix_cache(
        self, params
    ):
        """Churn with speculative waves + prefix reuse: every page ends
        free or cache-owned (rollback never frees/corrupts shared
        pages)."""
        engine = InferenceEngine(
            CFG,
            _rt(kv_layout="paged", chunked_prefill=True, prefix_cache=True,
                speculative=SpecConfig(k=4)),
            params=params,
        )
        await engine.start()
        prompt = list(range(2, 50))
        for _ in range(2):
            outs = await asyncio.gather(*[
                _gen(engine, prompt, 12) for _ in range(6)
            ])
            assert all(o == outs[0] for o in outs)
        free = engine._page_alloc.free_pages
        cached = engine._prefix.size
        assert free + cached == engine.runtime.pool_pages() - 1
        await engine.stop()

    async def test_stats_counters_and_snapshot(self, params):
        from calfkit_tpu.inference.client import JaxLocalModelClient

        client = JaxLocalModelClient(
            config=CFG,
            runtime=_rt(speculative=SpecConfig(k=4)),
            max_new_tokens=16,
        )
        from calfkit_tpu.models.messages import user_message

        await client.request([user_message("abcabcabc")])
        snap = client.stats_snapshot()
        spec = snap["speculative"]
        assert spec["drafter"] == "ngram" and spec["k"] == 4
        assert spec["spec_proposed"] >= spec["spec_accepted"] >= 0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0
        assert spec["tokens_per_dispatch"] >= 1.0
        engine = client._engine
        assert engine.stats.decode_tokens >= engine.stats.decode_dispatches
        await client.stop()

    async def test_spec_off_by_default(self, params):
        engine = InferenceEngine(CFG, _rt(), params=params)
        assert engine._drafter is None and engine._spec is None
        assert engine.runtime.speculative is None

    def test_invalid_k_rejected(self):
        with pytest.raises(ValueError, match="speculative.k"):
            InferenceEngine(CFG, _rt(speculative=SpecConfig(k=0)))

    def test_draft_params_without_seam_rejected(self, params):
        with pytest.raises(ValueError, match="draft_params"):
            InferenceEngine(CFG, _rt(), params=params, draft_params=params)


class TestDraftModelSeam:
    async def test_draft_model_parity_and_high_acceptance(self, params):
        """Draft == target (same params): near-total acceptance, and the
        output is still token-exact vs non-speculative greedy (the seam
        changes proposals, never the verified result)."""
        base = InferenceEngine(CFG, _rt(), params=params)
        spec = InferenceEngine(
            CFG,
            _rt(speculative=SpecConfig(k=4, draft=CFG)),
            params=params,
            draft_params=params,
        )
        await base.start()
        await spec.start()
        for prompt in ([1, 5, 9, 13], list(range(3, 20))):
            want = await _gen(base, prompt, 20)
            got = await _gen(spec, prompt, 20)
            assert got == want
        assert spec.stats.acceptance_rate > 0.9
        assert spec.stats.tokens_per_dispatch > 2.0
        await base.stop()
        await spec.stop()

    async def test_weak_draft_model_still_exact(self, params):
        """A draft model with DIFFERENT (random) weights proposes mostly
        garbage — acceptance collapses but output stays exact."""
        weak = M.init_params(CFG, jax.random.key(99), dtype=jnp.float32)
        base = InferenceEngine(CFG, _rt(), params=params)
        spec = InferenceEngine(
            CFG,
            _rt(speculative=SpecConfig(k=3, draft=CFG)),
            params=params,
            draft_params=weak,
        )
        await base.start()
        await spec.start()
        # a prompt whose 16 greedy steps all have a top-2 logit margin
        # >= 0.03: "exact" is only defined away from near-ties — the verify
        # program (k+1 positions) and the decode program (1 position) are
        # different XLA programs, and [2, 4, 6, 8] hits a 0.001 margin at
        # token 5 that the two round differently on the installed JAX
        prompt = [1, 2, 3, 4]
        want = await _gen(base, prompt, 16)
        got = await _gen(spec, prompt, 16)
        assert got == want
        await base.stop()
        await spec.stop()

    async def test_wide_admission_catchup_no_draft_cache_corruption(
        self, params
    ):
        """A late admission's wide catch-up bucket must not clamp-slide
        over a mid-generation neighbor's draft KV (r6 review): with
        draft == target the neighbor's acceptance stays ~perfect, which
        it cannot if its early positions were overwritten."""
        rt = _rt(
            max_batch_size=2, max_seq_len=64, prefill_chunk=16,
            speculative=SpecConfig(k=3, draft=CFG),
        )
        base = InferenceEngine(
            CFG,
            _rt(max_batch_size=2, max_seq_len=64, prefill_chunk=16),
            params=params,
        )
        spec = InferenceEngine(CFG, rt, params=params, draft_params=params)
        await base.start()
        await spec.start()
        long_a = [(3 * i + 1) % CFG.vocab_size for i in range(40)]
        long_b = [(5 * i + 2) % CFG.vocab_size for i in range(50)]
        want_a = await _gen(base, long_a, 16)

        async def a_run():
            return await _gen(spec, long_a, 16)

        async def b_run():
            await asyncio.sleep(0.3)  # A is mid-generation when B admits
            return await _gen(spec, long_b, 8)

        got_a, _ = await asyncio.gather(a_run(), b_run())
        assert got_a == want_a
        # the neighbor's wide catch-up didn't corrupt A's draft KV:
        # acceptance across the run stays high (corruption tanks it)
        assert spec.stats.acceptance_rate > 0.8, spec.stats.acceptance_rate
        await base.stop()
        await spec.stop()

    async def test_draft_cache_catchup_across_slot_reuse(self, params):
        """Sequential requests reuse slots; the draft cache must catch up
        per occupant (stale draft state would only hurt acceptance, but
        outputs must stay exact)."""
        base = InferenceEngine(CFG, _rt(max_batch_size=1), params=params)
        spec = InferenceEngine(
            CFG,
            _rt(max_batch_size=1, speculative=SpecConfig(k=3, draft=CFG)),
            params=params,
            draft_params=params,
        )
        await base.start()
        await spec.start()
        for prompt in ([1, 2, 3], [9, 8, 7, 6], [5, 5, 5]):
            want = await _gen(base, prompt, 10)
            got = await _gen(spec, prompt, 10)
            assert got == want
        await base.stop()
        await spec.stop()


class TestSpecSharded:
    async def test_spec_paged_on_tp_mesh(self, params):
        """Speculative verify under GSPMD: paged KV on a tp=2 mesh, same
        tokens as the single-device non-speculative engine."""
        from calfkit_tpu.inference.sharding import make_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs the virtual multi-device mesh")
        base = InferenceEngine(CFG, _rt(), params=params)
        spec = InferenceEngine(
            CFG,
            _rt(kv_layout="paged", tp=2, speculative=SpecConfig(k=3)),
            params=params,
            mesh=make_mesh(tp=2),
        )
        await base.start()
        await spec.start()
        prompt = [7, 8, 9] * 4
        want = await _gen(base, prompt, 12)
        got = await _gen(spec, prompt, 12)
        assert got == want
        await base.stop()
        await spec.stop()
