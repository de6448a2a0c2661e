"""Inference backend on CPU XLA: model math, engine scheduling, client."""

import asyncio
import time
from dataclasses import replace

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference.config import RuntimeConfig, preset  # noqa: E402
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402
from calfkit_tpu.inference.sampler import SamplingParams, sample  # noqa: E402
from calfkit_tpu.inference.sharding import (  # noqa: E402
    make_mesh,
    param_shardings,
    place_params,
)
from calfkit_tpu.inference.tokenizer import ByteTokenizer  # noqa: E402

CFG = preset("debug")


@pytest.fixture(scope="module")
def params():
    return M.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


class TestModelMath:
    def test_incremental_decode_matches_prefill(self, params):
        B, S = 2, 12
        toks = jax.random.randint(jax.random.key(1), (B, S), 3, CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        cache = M.make_empty_cache(CFG, B, 32, dtype=jnp.float32)
        full, _ = M.forward(params, CFG, toks, pos, cache, jnp.full((B,), S))

        cache2 = M.make_empty_cache(CFG, B, 32, dtype=jnp.float32)
        pre, cache2 = M.forward(
            params, CFG, toks[:, :8], pos[:, :8], cache2, jnp.full((B,), 8)
        )
        np.testing.assert_allclose(full[:, 7], pre[:, -1], atol=1e-4)
        last = pre[:, -1]
        for i in range(8, S):
            last, cache2 = M.forward(
                params, CFG, toks[:, i : i + 1], pos[:, i : i + 1], cache2,
                jnp.full((B,), i + 1),
            )
            np.testing.assert_allclose(full[:, i], last[:, -1], atol=1e-4)

    def test_decode_masks_ragged_kv_lengths(self, params):
        """Batched decode with rows at different kv lengths: each row's
        logits must match its solo decode (length masking isolates rows)."""
        toks0 = jax.random.randint(jax.random.key(2), (1, 10), 3, CFG.vocab_size)
        toks1 = jax.random.randint(jax.random.key(4), (1, 5), 3, CFG.vocab_size)
        # prefill each row alone
        c0 = M.make_empty_cache(CFG, 1, 32, dtype=jnp.float32)
        _, c0 = M.forward(
            params, CFG, toks0, jnp.arange(10)[None], c0, jnp.array([10])
        )
        c1 = M.make_empty_cache(CFG, 1, 32, dtype=jnp.float32)
        _, c1 = M.forward(
            params, CFG, toks1, jnp.arange(5)[None], c1, jnp.array([5])
        )
        # assemble the batch cache and decode one token per row
        batch_cache = tuple(
            jnp.concatenate([a, b], axis=1) for a, b in zip(c0, c1)
        )
        next_toks = jnp.array([[3], [4]])
        lens = jnp.array([11, 6])
        pos = (lens - 1)[:, None]
        out, _ = M.forward(params, CFG, next_toks, pos, batch_cache, lens)
        # solo decodes
        solo0, _ = M.forward(
            params, CFG, next_toks[:1], pos[:1], c0, jnp.array([11])
        )
        solo1, _ = M.forward(
            params, CFG, next_toks[1:], pos[1:], c1, jnp.array([6])
        )
        np.testing.assert_allclose(out[0], solo0[0], atol=1e-4)
        np.testing.assert_allclose(out[1], solo1[0], atol=1e-4)

    def test_sharded_matches_local(self, params):
        B, S = 2, 8
        toks = jax.random.randint(jax.random.key(3), (B, S), 3, CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        cache = M.make_empty_cache(CFG, B, 16, dtype=jnp.float32)
        lens = jnp.full((B,), S)
        local, _ = M.forward(params, CFG, toks, pos, cache, lens)
        mesh = make_mesh(tp=4, dp=2)
        sharded_params = place_params(params, param_shardings(CFG, mesh))
        sharded, _ = jax.jit(M.forward, static_argnums=1)(
            sharded_params, CFG, toks, pos, cache, lens
        )
        np.testing.assert_allclose(local, sharded, atol=1e-3)


class TestSampler:
    def test_greedy(self):
        logits = jnp.array([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]])
        out = sample(logits, jax.random.key(0), SamplingParams())
        assert out.tolist() == [1, 0]

    def test_top_k_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, -5.0, -6.0]] * 64)
        out = sample(
            logits, jax.random.key(1), SamplingParams(temperature=1.0, top_k=2)
        )
        assert set(np.asarray(out).tolist()) <= {0, 1}

    def test_top_p_restricts_support(self):
        logits = jnp.array([[10.0, 1.0, 0.5, 0.1]] * 64)
        out = sample(
            logits, jax.random.key(2), SamplingParams(temperature=1.0, top_p=0.5)
        )
        assert set(np.asarray(out).tolist()) == {0}


class TestEngine:
    async def test_single_request_deterministic(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
        )
        await engine.start()
        prompt = [1, 5, 9, 13]
        out1 = [t async for t in engine.generate(prompt, max_new_tokens=12)]
        out2 = [t async for t in engine.generate(prompt, max_new_tokens=12)]
        assert out1 == out2  # greedy: same prompt, same slot-independent result
        assert len(out1) == 12
        await engine.stop()

    async def test_continuous_batching_concurrent(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
        )
        await engine.start()

        async def run(seed):
            prompt = [1 + seed, 2 + seed, 3 + seed]
            return [t async for t in engine.generate(prompt, max_new_tokens=8)]

        # 6 requests through 4 slots: forces queueing + slot reuse
        results = await asyncio.gather(*[run(i) for i in range(6)])
        assert all(len(r) == 8 for r in results)
        # same prompt -> same tokens regardless of slot/batch company
        again = await run(0)
        assert again == results[0]
        assert engine.stats.decode_tokens >= 6 * 8
        await engine.stop()

    async def test_batch_isolation(self):
        """A request's output must not change when other requests share the
        batch (masking/occupancy correctness)."""
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=2),
        )
        await engine.start()
        solo = [t async for t in engine.generate([7, 8, 9], max_new_tokens=10)]

        async def noise(i):
            return [t async for t in engine.generate([20 + i] * 5, max_new_tokens=10)]

        crowd_task = asyncio.gather(*[noise(i) for i in range(3)])
        crowded = [t async for t in engine.generate([7, 8, 9], max_new_tokens=10)]
        await crowd_task
        assert crowded == solo
        await engine.stop()

    async def test_prompt_too_long_rejected(self):
        engine = InferenceEngine(
            CFG, RuntimeConfig(max_batch_size=2, max_seq_len=32, prefill_chunk=16)
        )
        await engine.start()
        from calfkit_tpu.exceptions import InferenceError

        with pytest.raises(InferenceError):
            async for _ in engine.generate(list(range(40))):
                pass
        await engine.stop()


class TestLocalClient:
    async def test_request_roundtrip_bytes(self):
        from calfkit_tpu.engine.model_client import ModelRequestParameters
        from calfkit_tpu.inference.client import JaxLocalModelClient
        from calfkit_tpu.models.messages import user_message

        cfg = preset("debug")
        client = JaxLocalModelClient(
            config=cfg,
            runtime=RuntimeConfig(max_batch_size=2, max_seq_len=256,
                                  prefill_chunk=32),
            max_new_tokens=16,
        )
        resp = await client.request([user_message("hi")])
        assert resp.model_name == "debug"
        assert resp.usage.output_tokens > 0
        await client.stop()

    def test_tool_call_parser(self):
        from calfkit_tpu.inference.client import default_tool_call_parser

        text = 'Let me check.\n{"tool_name": "get_weather", "args": {"city": "SF"}}\nok'
        remaining, calls = default_tool_call_parser(text)
        assert calls[0].tool_name == "get_weather"
        assert calls[0].args == {"city": "SF"}
        assert "tool_name" not in remaining

    def test_render_messages_template(self):
        from calfkit_tpu.engine.model_client import ModelRequestParameters
        from calfkit_tpu.inference.client import render_messages
        from calfkit_tpu.models.capability import ToolDef
        from calfkit_tpu.models.messages import (
            ModelResponse,
            TextOutput,
            user_message,
        )

        text = render_messages(
            [
                user_message("hello"),
                ModelResponse(parts=[TextOutput(text="hi there")]),
                user_message("and again"),
            ],
            ModelRequestParameters(tool_defs=[ToolDef(name="t", description="d")]),
        )
        assert "<|user|>\nhello" in text
        assert "<|assistant|>\nhi there" in text
        assert '"tool_name"' in text  # tool grammar in system block
        assert text.endswith("<|assistant|>\n")


class TestEngineReviewRegressions:
    async def test_retire_during_prefill_no_phantom_slot(self):
        """max_new_tokens=1: the request retires inside its own prefill and
        must not leave a phantom _active[-1] busy-spinning the scheduler."""
        engine = InferenceEngine(
            CFG, RuntimeConfig(max_batch_size=2, max_seq_len=64, prefill_chunk=16,
                               decode_steps_per_dispatch=2)
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 2, 3], max_new_tokens=1)]
        assert len(out) == 1
        await asyncio.sleep(0.1)
        assert engine._active == {}  # no phantom entry
        dispatches = engine.stats.decode_dispatches
        await asyncio.sleep(0.2)
        assert engine.stats.decode_dispatches == dispatches  # not spinning
        await engine.stop()

    async def test_stop_releases_queued_requests(self):
        """Requests still queued (not admitted) must get _DONE at stop."""
        engine = InferenceEngine(
            CFG, RuntimeConfig(max_batch_size=1, max_seq_len=64, prefill_chunk=16,
                               decode_steps_per_dispatch=2)
        )
        await engine.start()

        async def slow_request():
            return [t async for t in engine.generate([1, 2], max_new_tokens=40)]

        async def queued_request():
            return [t async for t in engine.generate([3, 4], max_new_tokens=40)]

        t1 = asyncio.create_task(slow_request())
        await asyncio.sleep(0.1)  # t1 occupies the only slot
        t2 = asyncio.create_task(queued_request())
        await asyncio.sleep(0.05)
        await engine.stop()
        done, pending = await asyncio.wait([t1, t2], timeout=2)
        assert not pending  # neither caller hangs


class TestQuantization:
    def test_quantized_forward_close_to_fp(self, params):
        from calfkit_tpu.inference.quant import quantize_params

        qparams = quantize_params(params)
        B, S = 2, 10
        toks = jax.random.randint(jax.random.key(7), (B, S), 3, CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        lens = jnp.full((B,), S)
        cache = M.make_empty_cache(CFG, B, 32, dtype=jnp.float32)
        fp, _ = M.forward(params, CFG, toks, pos, cache, lens)
        cache2 = M.make_empty_cache(CFG, B, 32, dtype=jnp.float32)
        q, _ = M.forward(qparams, CFG, toks, pos, cache2, lens)
        # int8 weight-only: same top-1 predictions on a tiny random model is
        # too strict; require high logit correlation instead
        fp_f = np.asarray(fp, np.float32).ravel()
        q_f = np.asarray(q, np.float32).ravel()
        corr = np.corrcoef(fp_f, q_f)[0, 1]
        assert corr > 0.99, f"quantized logits diverged (corr={corr:.4f})"

    def test_quantized_sharded_placement(self, params):
        from calfkit_tpu.inference.quant import quantize_params, quantize_shardings
        from calfkit_tpu.inference.sharding import param_shardings, place_params

        mesh = make_mesh(tp=2, dp=1)
        qparams = quantize_params(params)
        qshard = quantize_shardings(param_shardings(CFG, mesh))
        placed = place_params(qparams, qshard)
        assert placed["layers"]["wq"]["q8"].dtype == jnp.int8

    async def test_engine_runs_int8(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, quantization="int8"),
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 5, 9], max_new_tokens=10)]
        assert len(out) == 10
        again = [t async for t in engine.generate([1, 5, 9], max_new_tokens=10)]
        assert again == out  # deterministic under quantization too
        await engine.stop()


class TestInt4Quantization:
    """int4 weight-only (r5): packed nibbles + group-wise scales — half
    the decode weight stream of int8 again."""

    def test_pack_round_trip_is_exact_on_grid_values(self):
        from calfkit_tpu.inference.quant import dequant, quantize_tensor4

        # values that ARE representable (q * scale for q in [-7, 7]) must
        # survive quantize→dequant bit-exactly
        rng = np.random.default_rng(3)
        q = rng.integers(-7, 8, size=(4, 256, 6)).astype(np.float32)
        w = jnp.asarray(q * 0.035)  # one scale per whole axis group
        leaf = quantize_tensor4(w, (1,), group=128)
        key = next(k for k in leaf if k != "scale")
        assert leaf[key].dtype == jnp.uint8
        assert leaf[key].shape == (4, 128, 6)  # axis halved
        assert leaf["scale"].shape == (4, 2, 6)  # 256/128 groups
        back = dequant(leaf, jnp.float32)
        np.testing.assert_allclose(np.asarray(back), np.asarray(w), rtol=1e-6)

    def test_group_scales_beat_per_channel_on_outliers(self):
        from calfkit_tpu.inference.quant import dequant, quantize_tensor4

        # one huge outlier in group 0 must not destroy group 1's precision
        w = np.full((1, 256), 0.01, np.float32)
        w[0, 0] = 100.0
        leaf = quantize_tensor4(jnp.asarray(w), (1,), group=128)
        back = np.asarray(dequant(leaf, jnp.float32))
        assert abs(back[0, 0] - 100.0) < 100.0 / 7 + 1e-6
        # group 1 (no outlier) keeps small values accurately
        np.testing.assert_allclose(back[0, 128:], w[0, 128:], rtol=0.2)

    def test_host_and_device_quantizers_agree(self):
        from calfkit_tpu.inference.quant import (
            quantize_array_host,
            quantize_tensor4,
        )

        rng = np.random.default_rng(11)
        w = rng.standard_normal((3, 256, 4)).astype(np.float32)
        device = quantize_tensor4(jnp.asarray(w), (1,))
        host = quantize_array_host(w, (1,), bits=4)
        assert set(device) == set(host)
        key = next(k for k in device if k != "scale")
        np.testing.assert_array_equal(np.asarray(device[key]), host[key])
        np.testing.assert_allclose(
            np.asarray(device["scale"]), host["scale"], rtol=1e-6
        )

    def test_forward_parity_with_fp(self, params):
        """int4 logits track fp, and the error is QUANTIZATION noise (it
        shrinks monotonically as groups refine) — not an implementation
        bug.  On this 64-dim toy the default-group correlation ~0.95 is
        the intrinsic 4-bit floor (measured: g=64→0.948, g=4→0.983,
        g=2→0.993; real models average over 4096-wide fan-ins)."""
        from calfkit_tpu.inference.quant import (
            LAYER_REDUCTION_AXES,
            LM_HEAD_REDUCTION_AXES,
            quantize_tensor4,
        )

        B, S = 2, 10
        toks = jax.random.randint(jax.random.key(7), (B, S), 3, CFG.vocab_size)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        lens = jnp.full((B,), S)

        def logits(p):
            cache = M.make_empty_cache(CFG, B, 32, dtype=jnp.float32)
            out, _ = M.forward(p, CFG, toks, pos, cache, lens)
            return np.asarray(out, np.float32).ravel()

        def quantized(group):
            out = {"embed": params["embed"],
                   "final_norm": params["final_norm"], "layers": {}}
            for name, w in params["layers"].items():
                if name in LAYER_REDUCTION_AXES:
                    out["layers"][name] = quantize_tensor4(
                        w, LAYER_REDUCTION_AXES[name], group=group)
                else:
                    out["layers"][name] = w
            if "lm_head" in params:
                out["lm_head"] = quantize_tensor4(
                    params["lm_head"], LM_HEAD_REDUCTION_AXES, group=group)
            return out

        fp = logits(params)
        corr_default = np.corrcoef(fp, logits(quantized(128)))[0, 1]
        corr_fine = np.corrcoef(fp, logits(quantized(4)))[0, 1]
        assert corr_default > 0.93, f"int4 diverged (corr={corr_default:.4f})"
        assert corr_fine > 0.97, f"fine-group int4 diverged ({corr_fine:.4f})"
        # the noise-source pin: refining groups must REDUCE the error
        assert corr_fine > corr_default

    def test_sharded_placement_and_forward(self, params):
        from calfkit_tpu.inference.quant import (
            align_quant_sharding_keys,
            quantize_params,
            quantize_shardings,
        )
        from calfkit_tpu.inference.sharding import param_shardings, place_params

        mesh = make_mesh(tp=2, dp=1)
        qparams = quantize_params(params, bits=4)
        qshard = align_quant_sharding_keys(
            quantize_shardings(param_shardings(CFG, mesh), bits=4), qparams
        )
        placed = place_params(qparams, qshard)
        key = next(k for k in placed["layers"]["wq"] if k != "scale")
        assert placed["layers"]["wq"][key].dtype == jnp.uint8

    async def test_engine_runs_int4(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, quantization="int4"),
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 5, 9], max_new_tokens=10)]
        assert len(out) == 10
        again = [t async for t in engine.generate([1, 5, 9], max_new_tokens=10)]
        assert again == out  # deterministic under quantization too
        await engine.stop()

    def test_bitness_mismatch_fails_loudly(self):
        from calfkit_tpu.inference.quant import random_quantized_params_host

        params = random_quantized_params_host(CFG, bits=4)
        with pytest.raises(ValueError, match="other bitness"):
            InferenceEngine(
                CFG,
                RuntimeConfig(max_batch_size=2, max_seq_len=64,
                              prefill_chunk=16, quantization="int8"),
                params=params,
            )

    async def test_int4_long_context_sp_lane(self):
        """int4 weights under the sequence-parallel ring-prefill lane:
        dequant of packed+grouped leaves must compile and serve inside
        shard_map over the sp mesh (weights replicated, sequence
        sharded)."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device mesh")
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=64, prefill_chunk=16,
                          decode_steps_per_dispatch=4, long_context=True,
                          long_new_cap=8, tp=2, dp=4, quantization="int4"),
        )
        await engine.start()
        assert engine._sp_mesh().shape["sp"] == 8
        prompt = [(11 * i + 5) % CFG.vocab_size for i in range(100)]
        got = [t async for t in engine.generate(prompt, max_new_tokens=8)]
        assert len(got) == 8
        assert engine.stats.long_requests == 1
        await engine.stop()

    async def test_engine_runs_int4_paged_on_tp_mesh(self):
        """The 8B-shape path in miniature: host-built int4 params + paged
        KV on a tp=2 mesh (exercises the sharded unpack/reshape under
        GSPMD)."""
        from calfkit_tpu.inference.quant import random_quantized_params_host

        params = random_quantized_params_host(CFG, bits=4)
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=64, prefill_chunk=16,
                          decode_steps_per_dispatch=4, quantization="int4",
                          kv_layout="paged", page_size=16, num_kv_pages=17,
                          tp=2, dp=1),
            params=params,
            mesh=make_mesh(tp=2, dp=1),
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 5, 9], max_new_tokens=6)]
        assert len(out) == 6
        await engine.stop()


class TestPerRequestSampling:
    """Round-2: ModelSettings knobs ride per-slot device tensors, so one
    decode dispatch serves mixed greedy/sampled requests (ADVICE r1 medium)."""

    def _engine(self, max_batch_size=4):
        return InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=max_batch_size, max_seq_len=128,
                          prefill_chunk=16, decode_steps_per_dispatch=4),
        )

    async def test_seeded_sampling_reproducible(self):
        engine = self._engine()
        await engine.start()
        params = SamplingParams(temperature=1.2, top_k=50)
        prompt = [1, 5, 9, 13]
        out1 = [t async for t in engine.generate(
            prompt, max_new_tokens=12, sampling=params, seed=7)]
        out2 = [t async for t in engine.generate(
            prompt, max_new_tokens=12, sampling=params, seed=7)]
        assert out1 == out2  # same seed -> same stream, slot-independent
        assert len(out1) == 12
        await engine.stop()

    async def test_mixed_batch_greedy_rows_unaffected(self):
        engine = self._engine()
        await engine.start()
        prompt = [2, 4, 6]
        baseline = [t async for t in engine.generate(prompt, max_new_tokens=8)]

        async def sampled(i):
            return [t async for t in engine.generate(
                [3 + i, 7, 11], max_new_tokens=8,
                sampling=SamplingParams(temperature=1.5, top_p=0.9), seed=i)]

        async def greedy():
            return [t async for t in engine.generate(prompt, max_new_tokens=8)]

        results = await asyncio.gather(greedy(), sampled(1), sampled(2))
        assert results[0] == baseline  # sampled neighbors don't perturb greedy
        await engine.stop()

    async def test_abandoned_iterator_frees_slot(self):
        engine = self._engine(max_batch_size=2)
        await engine.start()
        agen = engine.generate([1, 2, 3], max_new_tokens=64)
        got = 0
        async for _ in agen:
            got += 1
            if got >= 2:
                break  # abandon mid-stream
        await agen.aclose()
        # engine must reclaim the slot and keep serving at full capacity
        outs = await asyncio.gather(*[
            _collect(engine.generate([5 + i, 6], max_new_tokens=6))
            for i in range(4)
        ])
        assert all(len(o) == 6 for o in outs)
        assert not engine._active
        assert sorted(engine._free) == [0, 1]
        await engine.stop()


async def _collect(agen):
    return [t async for t in agen]


class TestModelSettingsThreading:
    """JaxLocalModelClient honors per-request ModelSettings (ADVICE r1)."""

    def _client(self):
        from calfkit_tpu.inference.client import JaxLocalModelClient

        return JaxLocalModelClient(
            config=preset("debug"),
            runtime=RuntimeConfig(max_batch_size=2, max_seq_len=256,
                                  prefill_chunk=32,
                                  decode_steps_per_dispatch=4),
            max_new_tokens=24,
        )

    async def test_temperature_seed_reproducible(self):
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.models.messages import user_message

        client = self._client()
        settings = ModelSettings(temperature=0.9, top_k=40, seed=11)
        r1 = await client.request([user_message("hello")], settings)
        r2 = await client.request([user_message("hello")], settings)
        assert r1.text() == r2.text()
        await client.stop()

    async def test_stop_sequences_terminate(self):
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.models.messages import user_message

        client = self._client()
        free = await client.request([user_message("hi")])
        full = free.text()
        assert full  # byte tokenizer on random weights always emits text
        stop = full[1:3]  # a sequence the greedy model WILL produce
        r = await client.request(
            [user_message("hi")], ModelSettings(stop_sequences=[stop])
        )
        assert stop not in r.text()
        assert len(r.text()) < len(full)
        # the engine reclaims the cancelled slot at its next tick
        for _ in range(100):
            if not client._engine._active:
                break
            await asyncio.sleep(0.05)
        assert not client._engine._active
        await client.stop()

    async def test_max_tokens_respected(self):
        from calfkit_tpu.engine.model_client import ModelSettings
        from calfkit_tpu.models.messages import user_message

        client = self._client()
        r = await client.request(
            [user_message("hi")], ModelSettings(max_tokens=5)
        )
        assert r.usage.output_tokens <= 5
        await client.stop()


class TestQueuedCancellation:
    async def test_cancel_while_queued_drains_and_engine_stays_live(self):
        """A request cancelled BEFORE admission must be drained; the idle
        engine must keep awaiting (review r2: a skipped-but-present pending
        entry turned the serve loop into a busy spin)."""
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=1, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
        )
        await engine.start()

        async def long_req():
            return [t async for t in engine.generate([1, 2], max_new_tokens=24)]

        first = asyncio.create_task(long_req())
        await asyncio.sleep(0.3)  # first request admitted (slot occupied)
        queued = engine.generate([3, 4], max_new_tokens=24)
        starter = asyncio.create_task(anext(queued))
        await asyncio.sleep(0.1)  # body started: request enqueued, blocked
        starter.cancel()
        with pytest.raises(asyncio.CancelledError):
            await starter
        await queued.aclose()
        assert (await first)  # original request completes
        # engine idles without spinning and still serves new work
        out = await asyncio.wait_for(
            _collect(engine.generate([5, 6], max_new_tokens=6)), timeout=30
        )
        assert len(out) == 6
        assert not engine._pending and not engine._active
        await engine.stop()


class TestPagedKV:
    """Paged KV cache (round 2): block-table pool, reserve-at-admission,
    trash-page masking.  Reference anchor: SURVEY §5 long-context / VERDICT
    r1 item 3."""

    def _engine(self, layout, config=CFG, **over):
        kw = dict(
            max_batch_size=4, max_seq_len=128, prefill_chunk=16,
            decode_steps_per_dispatch=4, page_size=16, kv_layout=layout,
        )
        kw.update(over)
        return InferenceEngine(config, RuntimeConfig(**kw), seed=3)

    async def test_paged_matches_dense_tokens(self):
        dense = self._engine("dense")
        paged = self._engine("paged")
        await dense.start()
        await paged.start()
        # lengths that cross page boundaries (page_size=16)
        prompts = [[1, 5, 9], list(range(2, 20)), list(range(3, 40))]
        for prompt in prompts:
            want = [t async for t in dense.generate(prompt, max_new_tokens=20)]
            got = [t async for t in paged.generate(prompt, max_new_tokens=20)]
            assert got == want, f"paged diverged for prompt len {len(prompt)}"
        await dense.stop()
        await paged.stop()

    async def test_oversubscribed_pool_admission_control(self):
        # pool of 9 usable pages; each request needs ceil((3+28+1)/16)=2
        # pages -> only 4 of 8 requests fit at once; the rest must wait and
        # ALL must complete, with full page accounting at the end
        engine = self._engine("paged", num_kv_pages=10)
        await engine.start()

        async def one(i):
            return [
                t async for t in engine.generate(
                    [1 + i, 2, 3], max_new_tokens=28
                )
            ]

        outs = await asyncio.gather(*[one(i) for i in range(8)])
        assert all(len(o) == 28 for o in outs)
        assert engine._page_alloc.free_pages == 9  # every page returned
        assert not engine._page_alloc.held_slots
        await engine.stop()

    async def test_page_reuse_no_cross_request_bleed(self):
        """A slot's pages are freed and reused; the new occupant's output
        must be identical to a fresh engine's (no stale KV bleed)."""
        engine = self._engine("paged", num_kv_pages=10)
        await engine.start()
        first = [t async for t in engine.generate([1, 5, 9], max_new_tokens=20)]
        # churn: different prompts through the same pages
        for i in range(3):
            [t async for t in engine.generate([7 + i, 8, 9, 10], max_new_tokens=12)]
        again = [t async for t in engine.generate([1, 5, 9], max_new_tokens=20)]
        assert again == first
        await engine.stop()

    async def test_cancel_returns_pages(self):
        engine = self._engine("paged")
        await engine.start()
        agen = engine.generate(list(range(2, 20)), max_new_tokens=40)
        got = 0
        async for _ in agen:
            got += 1
            if got >= 2:
                break
        await agen.aclose()
        out = [t async for t in engine.generate([4, 5], max_new_tokens=6)]
        assert len(out) == 6
        for _ in range(100):
            if not engine._page_alloc.held_slots:
                break
            await asyncio.sleep(0.05)
        assert not engine._page_alloc.held_slots
        await engine.stop()

    async def test_paged_pallas_interpret_matches_xla(self):
        # heads of 128: the debug preset's heads of 16 are outside the
        # kernel's rule, and an explicit request there is refused
        wide = replace(CFG, d_model=256, n_heads=2, n_kv_heads=1)
        xla = self._engine("paged", config=wide)
        pal = self._engine(
            "paged", config=wide, attention_impl="pallas_interpret"
        )
        await xla.start()
        await pal.start()
        prompt = list(range(2, 21))
        want = [t async for t in xla.generate(prompt, max_new_tokens=12)]
        got = [t async for t in pal.generate(prompt, max_new_tokens=12)]
        # NOTE fixed prompt/seed: on random-init weights greedy argmax can
        # amplify benign accumulation-order differences, so don't extend
        # this to arbitrary prompts (the numerical bound is
        # tests/test_paged_decode_attention.py's allclose)
        assert got == want
        await xla.stop()
        await pal.stop()

    async def test_128_streams_through_paged_blocks_sharded(self):
        """BASELINE config-5 shape proof: 128 concurrent streams decode
        through paged blocks on a tp=2 sharded virtual mesh, with the pool
        oversubscribed vs dense (VERDICT r1 item 3 acceptance)."""
        from calfkit_tpu.inference.sharding import make_mesh

        B = 128
        rt = RuntimeConfig(
            max_batch_size=B, max_seq_len=128, prefill_chunk=16,
            decode_steps_per_dispatch=4, page_size=16, kv_layout="paged",
            # dense equivalent would need B*8=1024 pages; give 2 pages per
            # stream (prompt+16 new tokens fits) + trash
            num_kv_pages=2 * B + 1, tp=2,
        )
        engine = InferenceEngine(CFG, rt, mesh=make_mesh(tp=2), seed=5)
        await engine.start()

        async def one(i):
            return [
                t async for t in engine.generate(
                    [1 + (i % 50), 3, 5], max_new_tokens=16
                )
            ]

        outs = await asyncio.gather(*[one(i) for i in range(160)])
        assert all(len(o) == 16 for o in outs)
        assert engine._page_alloc.free_pages == 2 * B
        await engine.stop()

    async def test_unservable_reservation_rejected_loudly(self):
        """A request the pool could NEVER fit raises instead of queueing
        forever (review r2)."""
        engine = self._engine("paged", num_kv_pages=4)  # 3 usable pages
        await engine.start()
        with pytest.raises(Exception, match="KV pages"):
            async for _ in engine.generate([1, 2, 3], max_new_tokens=100):
                pass
        # engine still serves right-sized work
        out = [t async for t in engine.generate([1, 2], max_new_tokens=8)]
        assert len(out) == 8
        await engine.stop()

    def test_unaligned_max_seq_rejected(self):
        with pytest.raises(ValueError, match="max_seq_len"):
            InferenceEngine(
                CFG,
                RuntimeConfig(max_batch_size=2, max_seq_len=120,
                              prefill_chunk=16, page_size=16,
                              kv_layout="paged"),
            )


class TestRandomQuantizedParams:
    async def test_host_built_int8_params_serve_paged(self):
        """The 8B bench path in miniature: host-generated int8 params +
        paged KV + int8 runtime serve end-to-end."""
        from calfkit_tpu.inference.quant import random_quantized_params_host

        params = random_quantized_params_host(CFG)
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, page_size=16,
                          kv_layout="paged", quantization="int8"),
            params=params,
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 5, 9], max_new_tokens=8)]
        assert len(out) == 8
        out2 = [t async for t in engine.generate([1, 5, 9], max_new_tokens=8)]
        assert out2 == out  # deterministic through the quantized path
        await engine.stop()


class TestChunkedPrefill:
    """Opt-in chunked admission: long prompts advance one chunk per
    scheduler pass with decode ticks in between (round 2)."""

    def _engine(self, layout="dense", chunk=16, **over):
        kw = dict(
            max_batch_size=4, max_seq_len=128, prefill_chunk=chunk,
            decode_steps_per_dispatch=4, page_size=16, kv_layout=layout,
            chunked_prefill=True,
        )
        kw.update(over)
        return InferenceEngine(CFG, RuntimeConfig(**kw), seed=3)

    async def test_chunked_matches_single_shot(self):
        plain = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
            seed=3,
        )
        chunked = self._engine()
        await plain.start()
        await chunked.start()
        # one-chunk, exact-multiple, and straddling lengths
        for prompt in ([1, 5, 9], list(range(2, 34)), list(range(3, 60))):
            want = [t async for t in plain.generate(prompt, max_new_tokens=16)]
            got = [t async for t in chunked.generate(prompt, max_new_tokens=16)]
            assert got == want, f"chunked diverged at len {len(prompt)}"
        await plain.stop()
        await chunked.stop()

    async def test_chunked_paged_matches_dense(self):
        dense = self._engine("dense")
        paged = self._engine("paged")
        await dense.start()
        await paged.start()
        prompt = list(range(2, 50))
        want = [t async for t in dense.generate(prompt, max_new_tokens=12)]
        got = [t async for t in paged.generate(prompt, max_new_tokens=12)]
        assert got == want
        await dense.stop()
        await paged.stop()

    async def test_decode_progresses_during_long_prefill(self):
        """The whole point: an active stream keeps emitting while a long
        admission is in flight."""
        engine = self._engine(chunk=16, max_seq_len=256)
        await engine.start()
        # occupy a slot with an active stream
        active = engine.generate([1, 2], max_new_tokens=200)
        times: list[float] = []

        async def consume_active():
            async for _ in active:
                times.append(time.perf_counter())

        consumer = asyncio.create_task(consume_active())
        await asyncio.sleep(0.5)  # stream is decoding
        before = len(times)
        # a LONG prompt (8 chunks): chunked admission interleaves
        long_out = [
            t async for t in engine.generate(
                list(range(2, 130)), max_new_tokens=8
            )
        ]
        assert len(long_out) == 8
        during = len(times) - before
        assert during > 0, "active stream starved during long admission"
        consumer.cancel()
        with pytest.raises(asyncio.CancelledError):
            await consumer
        await active.aclose()
        await engine.stop()

    async def test_stop_mid_inflight_releases_waiters(self):
        engine = self._engine(chunk=16, max_seq_len=256)
        await engine.start()
        agen = engine.generate(list(range(2, 130)), max_new_tokens=8)
        starter = asyncio.create_task(anext(agen))
        await asyncio.sleep(0.05)  # admission likely mid-chunk
        await engine.stop()
        with pytest.raises((StopAsyncIteration, asyncio.CancelledError)):
            await starter
        await agen.aclose()

    async def test_sampled_chunked_reproducible(self):
        engine = self._engine()
        await engine.start()
        params = SamplingParams(temperature=1.1, top_k=30)
        prompt = list(range(2, 40))
        out1 = [t async for t in engine.generate(
            prompt, max_new_tokens=10, sampling=params, seed=5)]
        out2 = [t async for t in engine.generate(
            prompt, max_new_tokens=10, sampling=params, seed=5)]
        assert out1 == out2
        await engine.stop()

    def test_unaligned_chunking_rejected(self):
        with pytest.raises(ValueError, match="chunked_prefill"):
            InferenceEngine(
                CFG,
                RuntimeConfig(max_batch_size=2, max_seq_len=120,
                              prefill_chunk=16, chunked_prefill=True),
            )

    async def test_fully_cancelled_inflight_wave_aborts(self):
        engine = self._engine(chunk=16, max_seq_len=256, layout="paged")
        await engine.start()
        agen = engine.generate(list(range(2, 130)), max_new_tokens=8)
        starter = asyncio.create_task(anext(agen))
        await asyncio.sleep(0.1)  # admission in flight
        starter.cancel()
        with pytest.raises(asyncio.CancelledError):
            await starter
        await agen.aclose()
        for _ in range(100):
            if engine._inflight is None and not engine._page_alloc.held_slots:
                break
            await asyncio.sleep(0.05)
        assert engine._inflight is None
        assert not engine._page_alloc.held_slots  # reservation released
        # engine still serves
        out = [t async for t in engine.generate([4, 5], max_new_tokens=6)]
        assert len(out) == 6
        await engine.stop()


class TestLongContextLane:
    """Prompts beyond max_seq_len served through the engine's
    sequence-parallel lane (ring prefill + context-parallel decode),
    unified with the slot scheduler (PARITY known-gap closure)."""

    @staticmethod
    def _params():
        return M.init_params(CFG, jax.random.key(3), dtype=jnp.float32)

    def _long_engine(self, params, **rt):
        defaults = dict(
            max_batch_size=2, max_seq_len=64, prefill_chunk=16,
            decode_steps_per_dispatch=4, long_context=True, long_new_cap=16,
        )
        defaults.update(rt)
        return InferenceEngine(CFG, RuntimeConfig(**defaults), params=params)

    async def test_long_prompt_matches_short_lane(self):
        """The same 100-token prompt produces identical greedy tokens via
        the long lane (max_seq_len=64 engine) and via the ordinary short
        lane of a roomier engine — one merge law everywhere."""
        params = self._params()
        prompt = [(7 * i + 3) % CFG.vocab_size for i in range(100)]

        long_engine = self._long_engine(params)
        await long_engine.start()
        got = [t async for t in long_engine.generate(prompt, max_new_tokens=8)]
        assert long_engine.stats.long_requests == 1
        await long_engine.stop()

        ref_engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=256, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
            params=params,
        )
        await ref_engine.start()
        want = [t async for t in ref_engine.generate(prompt, max_new_tokens=8)]
        await ref_engine.stop()
        assert got == want

    async def test_long_and_short_interleave(self):
        """Short requests keep streaming while a long request is served."""
        params = self._params()
        engine = self._long_engine(params)
        await engine.start()
        long_prompt = [(3 * i + 1) % CFG.vocab_size for i in range(90)]

        async def long_run():
            return [t async for t in engine.generate(long_prompt, max_new_tokens=12)]

        async def short_run(i):
            return [t async for t in engine.generate([5 + i, 6, 7], max_new_tokens=6)]

        long_out, *short_outs = await asyncio.gather(
            long_run(), short_run(0), short_run(1), short_run(2)
        )
        assert len(long_out) == 12
        assert all(len(s) == 6 for s in short_outs)
        # short lane answers are unaffected by the long company
        solo = [t async for t in engine.generate([5, 6, 7], max_new_tokens=6)]
        assert short_outs[0] == solo
        await engine.stop()

    async def test_long_request_cancellation_reaps(self):
        params = self._params()
        engine = self._long_engine(params, long_new_cap=32)
        await engine.start()
        prompt = [(i + 2) % CFG.vocab_size for i in range(80)]
        agen = engine.generate(prompt, max_new_tokens=32)
        got = [await anext(agen)]  # first token arrived: lane is active
        await agen.aclose()  # abandon mid-generation -> cancel
        for _ in range(100):
            if engine._long is None and not engine._long_pending:
                break
            await asyncio.sleep(0.05)
        assert engine._long is None
        # lane still serves the next long request
        out = [t async for t in engine.generate(prompt, max_new_tokens=4)]
        assert len(out) == 4 and out[0] == got[0]
        await engine.stop()

    async def test_long_disabled_rejects(self):
        engine = InferenceEngine(
            CFG, RuntimeConfig(max_batch_size=2, max_seq_len=32, prefill_chunk=16)
        )
        await engine.start()
        from calfkit_tpu.exceptions import InferenceError

        with pytest.raises(InferenceError, match="long_context"):
            async for _ in engine.generate(list(range(40))):
                pass
        await engine.stop()

    async def test_long_prompt_ceiling_rejects(self):
        params = self._params()
        engine = self._long_engine(params, long_max_prompt=128)
        await engine.start()
        from calfkit_tpu.exceptions import InferenceError

        with pytest.raises(InferenceError, match="long_max_prompt"):
            async for _ in engine.generate(list(range(200))):
                pass
        await engine.stop()

    async def test_long_max_new_over_cap_faults(self):
        """A long request whose token budget exceeds long_new_cap FAULTS
        with a typed error by default — the engine must not silently
        rewrite the caller's budget (the pre-r6 clamp corrupted downstream
        accounting that trusted max_new_tokens)."""
        from calfkit_tpu.exceptions import InferenceError

        params = self._params()
        engine = self._long_engine(params, long_new_cap=8)
        await engine.start()
        prompt = [(i + 9) % CFG.vocab_size for i in range(70)]
        with pytest.raises(InferenceError, match="long_new_cap"):
            async for _ in engine.generate(prompt, max_new_tokens=1000):
                pass
        # the lane still serves a within-budget request afterwards
        out = [t async for t in engine.generate(prompt, max_new_tokens=4)]
        assert len(out) == 4
        await engine.stop()

    async def test_long_max_new_clamped_only_with_optin(self):
        """long_clamp_new_tokens=True restores clamping as an explicit
        negotiation (the old silent default)."""
        params = self._params()
        engine = self._long_engine(
            params, long_new_cap=8, long_clamp_new_tokens=True
        )
        await engine.start()
        prompt = [(i + 9) % CFG.vocab_size for i in range(70)]
        out = [t async for t in engine.generate(prompt, max_new_tokens=1000)]
        assert len(out) == 8  # clamped to the cap, not hung, not 1000
        await engine.stop()

    async def test_long_lane_sp8_over_full_mesh(self):
        """On a dp=4 x tp=2 engine mesh the long lane shards the sequence
        over ALL 8 devices (sp=8 ring) — tokens still match the short lane
        bit-for-bit (greedy)."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device mesh")
        params = self._params()
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=64, prefill_chunk=16,
                          decode_steps_per_dispatch=4, long_context=True,
                          long_new_cap=8, tp=2, dp=4),
            params=params,
        )
        await engine.start()
        assert engine._sp_mesh().shape["sp"] == 8
        prompt = [(11 * i + 5) % CFG.vocab_size for i in range(100)]
        got = [t async for t in engine.generate(prompt, max_new_tokens=8)]
        await engine.stop()

        ref_engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=256, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
            params=params,
        )
        await ref_engine.start()
        want = [t async for t in ref_engine.generate(prompt, max_new_tokens=8)]
        await ref_engine.stop()
        assert got == want

    async def test_chunked_long_prefill_matches_monolithic(self):
        """With chunked_prefill=True the long lane prefills one chunk per
        scheduler pass (resumable, short ticks between chunks) — and the
        greedy tokens match the monolithic ring-prefill path exactly."""
        params = self._params()
        prompt = [(13 * i + 2) % CFG.vocab_size for i in range(100)]

        mono = self._long_engine(params)
        await mono.start()
        want = [t async for t in mono.generate(prompt, max_new_tokens=8)]
        await mono.stop()

        chunked = self._long_engine(params, chunked_prefill=True)
        await chunked.start()
        got = [t async for t in chunked.generate(prompt, max_new_tokens=8)]
        assert chunked.stats.long_requests == 1
        await chunked.stop()
        assert got == want

    async def test_short_streams_progress_during_chunked_long_prefill(self):
        """A long admission must not starve active short streams: with
        chunked_prefill the long prefill yields between chunks."""
        params = self._params()
        engine = self._long_engine(params, chunked_prefill=True)
        await engine.start()
        during_prefill = 0

        async def short_stream():
            nonlocal during_prefill
            out = []
            async for t in engine.generate([5, 6, 7], max_new_tokens=24):
                if engine._long_inflight is not None:
                    during_prefill += 1
                out.append(t)
            return out

        # park a short stream first so decode ticks are interleaving
        short_task = asyncio.create_task(short_stream())
        await asyncio.sleep(0.05)
        long_prompt = [(i + 4) % CFG.vocab_size for i in range(120)]
        long_out = [
            t async for t in engine.generate(long_prompt, max_new_tokens=8)
        ]
        short_out = await short_task
        assert len(long_out) == 8 and len(short_out) == 24
        # the ACTUAL interleaving observable: short tokens arrived while the
        # long prefill was mid-flight (a monolithic stall would leave 0)
        assert during_prefill > 0
        # the short stream's answer is company-independent
        solo = [t async for t in engine.generate([5, 6, 7], max_new_tokens=24)]
        assert short_out == solo
        await engine.stop()

    @staticmethod
    async def _collect(engine, prompt, n):
        return [t async for t in engine.generate(prompt, max_new_tokens=n)]

    async def test_chunked_long_prefill_cancellation_mid_flight(self):
        params = self._params()
        engine = self._long_engine(params, chunked_prefill=True)
        await engine.start()
        prompt = [(i + 1) % CFG.vocab_size for i in range(120)]
        agen = engine.generate(prompt, max_new_tokens=16)
        starter = asyncio.create_task(anext(agen))
        await asyncio.sleep(0.05)  # admission likely mid-chunk
        starter.cancel()
        with pytest.raises(asyncio.CancelledError):
            await starter
        await agen.aclose()
        for _ in range(100):
            if engine._long_inflight is None and engine._long is None:
                break
            await asyncio.sleep(0.05)
        assert engine._long_inflight is None and engine._long is None
        # lane still serves
        out = [t async for t in engine.generate(prompt, max_new_tokens=4)]
        assert len(out) == 4
        await engine.stop()

    async def test_chunked_long_prefill_sp8(self):
        """Chunked long prefill over a genuinely sequence-sharded scratch
        (sp=8): GSPMD shards each chunk's attention; tokens match the
        single-device short lane."""
        if len(jax.devices()) < 8:
            pytest.skip("needs the virtual 8-device mesh")
        params = self._params()
        prompt = [(17 * i + 3) % CFG.vocab_size for i in range(100)]
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=64, prefill_chunk=16,
                          decode_steps_per_dispatch=4, long_context=True,
                          long_new_cap=8, tp=2, dp=4, chunked_prefill=True),
            params=params,
        )
        await engine.start()
        got = [t async for t in engine.generate(prompt, max_new_tokens=8)]
        await engine.stop()

        ref = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=2, max_seq_len=256, prefill_chunk=16,
                          decode_steps_per_dispatch=4),
            params=params,
        )
        await ref.start()
        want = [t async for t in ref.generate(prompt, max_new_tokens=8)]
        await ref.stop()
        assert got == want


class TestEngineStress:
    async def test_churn_with_random_cancels_leaks_nothing(self):
        """40 requests through 4 slots with a third of consumers abandoning
        mid-stream: every slot, page, and queue must come back."""
        import random

        rng = random.Random(7)
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, kv_layout="paged",
                          page_size=16),
        )
        await engine.start()

        from tests.conftest import churn_abandon, drain_engine

        counts = await asyncio.gather(*[
            churn_abandon(engine, [2 + (i % 17), 3, 4, 5 + (i % 7)], rng)
            for i in range(40)
        ])
        assert all(c >= 2 for c in counts)
        # drain: all slots free, no pages held, nothing pending
        await drain_engine(engine)
        # loud on timeout: a leak in ANY of the four pools must fail, not
        # silently fall through the wait loop
        assert not engine._active and not engine._pending and not engine._carry
        assert sorted(engine._free) == list(range(4))
        assert not engine._page_alloc.held_slots
        # the retire heap must not pin any retired request's memory: every
        # surviving entry has its request reference nulled (r3 advisor)
        assert all(e[2] is None for e in engine._retire_heap)
        # engine still serves correctly after the churn
        out = [t async for t in engine.generate([9, 9, 9], max_new_tokens=5)]
        assert len(out) == 5
        await engine.stop()


class TestRetireHeap:
    """The bound-retirement heap's cross-thread discipline (VERDICT r3
    weak #5): early retirements null their entry, nulled entries pop
    lazily in _retirement_near, and compaction keeps the heap O(active)."""

    def _engine(self, bs: int = 2) -> InferenceEngine:
        return InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=bs, max_seq_len=128,
                          prefill_chunk=16, decode_steps_per_dispatch=4,
                          kv_layout="paged", page_size=16),
        )

    async def test_cancel_mid_stream_nulls_entry_and_lazy_pops(self):
        """Cancel a request whose bound sits at the heap TOP: the nulled
        entry must pop lazily inside _retirement_near, leaving the later
        bound visible — lazy invalidation breaking would either crash the
        peek or starve the short-dispatch TTFT lever."""
        engine = self._engine()
        await engine.start()

        # B holds the FAR bound; A (near bound) will sit at the heap top
        b_gen = engine.generate([7, 8, 9], max_new_tokens=90)
        b_iter = b_gen.__aiter__()
        await b_iter.__anext__()
        a_gen = engine.generate([3, 4, 5], max_new_tokens=30)
        got = 0
        async for _ in a_gen:
            got += 1
            if got == 2:
                break
        await a_gen.aclose()  # cancel A mid-stream
        for _ in range(200):
            if len(engine._active) == 1:
                break
            await asyncio.sleep(0.02)
        assert len(engine._active) == 1  # only B remains
        with engine._retire_lock:
            entries = list(engine._retire_heap)
        # A's entry is nulled (no memory pinned) or already compacted away
        live = [e for e in entries if e[2] is not None]
        assert all(e[2].slot != -1 for e in live)
        # the peek skips any stale top and still sees B's bound
        assert engine._retirement_near(10**6) is True
        with engine._retire_lock:
            assert all(e[2] is not None for e in engine._retire_heap[:1])
        await b_gen.aclose()
        await engine.stop()

    async def test_sustained_cancels_compact_heap(self):
        """Many early retirements must not grow the heap unboundedly:
        compaction rebuilds once nulled entries outnumber live ones."""
        engine = self._engine(bs=4)
        await engine.start()
        for i in range(30):
            agen = engine.generate([2 + (i % 9), 3], max_new_tokens=50)
            async for _ in agen:
                break  # first token then abandon
            await agen.aclose()
        for _ in range(200):
            if not engine._active:
                break
            await asyncio.sleep(0.02)
        assert not engine._active
        with engine._retire_lock:
            heap_len = len(engine._retire_heap)
            stale = engine._retire_stale
        # 30 tracked + 30 cancelled: without compaction the heap would hold
        # 30 corpses; with it, stale entries never exceed live ones + 1
        assert heap_len <= 8, heap_len
        assert stale * 2 <= heap_len + 1
        # still serves
        out = [t async for t in engine.generate([9, 9], max_new_tokens=5)]
        assert len(out) == 5
        await engine.stop()


class TestAttnAutoResolution:
    """attention_impl="auto" decides the paged decode read, the one
    attention computation with a kernel, from what the engine observes."""

    # (what differs from the eligible engine, the answer)
    @pytest.mark.parametrize(
        "platform,over,wide,want",
        [
            ("tpu", {}, True, "pallas"),
            ("cpu", {}, True, "xla"),
            ("gpu", {}, True, "xla"),
            ("tpu", {"kv_layout": "dense"}, True, "xla"),
            ("tpu", {"tp": 2}, True, "xla"),
            ("tpu", {"dp": 2}, True, "xla"),
            ("tpu", {}, False, "xla"),  # heads 16 wide: no whole lane tile
            ("tpu", {"page_size": 4}, True, "xla"),  # under a sublane tile
            ("tpu", {"attention_impl": "xla"}, True, "xla"),
            # explicit: the platform test alone is waived
            ("cpu", {"attention_impl": "pallas_interpret"}, True,
             "pallas_interpret"),
        ],
    )
    def test_paged_decode_auto_follows_what_the_engine_observes(
        self, monkeypatch, platform, over, wide, want
    ):
        """The kernel that reads live pages in place on a TPU, paged, one
        device, eligible head and page shape; XLA otherwise.  No artifact
        and no environment decide it."""
        from types import SimpleNamespace

        config = (
            replace(CFG, d_model=512, n_heads=4, n_kv_heads=2) if wide else CFG
        )
        rt = RuntimeConfig(**{
            "max_batch_size": 2, "max_seq_len": 128, "prefill_chunk": 16,
            "kv_layout": "paged", "page_size": 16, **over,
        })
        engine = InferenceEngine(config, rt)
        devices = jax.devices()
        monkeypatch.setattr(
            jax, "devices",
            lambda *a: [SimpleNamespace(platform=platform)] if not a else devices,
        )
        assert engine._resolved_attn_impl() == want


class TestPrefillWaveWidth:
    """max_prefill_wave: admission-wave width is a serving knob (burst
    TTFT vs prefill-scratch memory), power-of-two trimmed."""

    async def test_wide_wave_admits_in_one_dispatch(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=16, max_seq_len=128,
                          prefill_chunk=16, decode_steps_per_dispatch=4,
                          max_prefill_wave=16),
        )
        waves: list[int] = []
        original = engine._prefill_wave

        def spy(wave, bucket):
            waves.append(len(wave))
            return original(wave, bucket)

        engine._prefill_wave = spy
        await engine.start()
        outs = await asyncio.gather(*[
            _gen_n(engine, [2 + i, 3, 4], 6) for i in range(16)
        ])
        assert all(len(o) == 6 for o in outs)
        # a drained 16-slot batch fills in far fewer dispatches than the
        # old fixed cap of 8 would allow; the widest wave used the knob
        assert max(waves) > 8, waves
        await engine.stop()

    async def test_narrow_wave_caps_at_one(self):
        engine = InferenceEngine(
            CFG,
            RuntimeConfig(max_batch_size=4, max_seq_len=128,
                          prefill_chunk=16, decode_steps_per_dispatch=4,
                          max_prefill_wave=1),
        )
        waves: list[int] = []
        original = engine._prefill_wave

        def spy(wave, bucket):
            waves.append(len(wave))
            return original(wave, bucket)

        engine._prefill_wave = spy
        await engine.start()
        outs = await asyncio.gather(*[
            _gen_n(engine, [2 + i, 3], 5) for i in range(6)
        ])
        assert all(len(o) == 5 for o in outs)
        assert set(waves) == {1}
        await engine.stop()

    def test_invalid_width_rejected(self):
        with pytest.raises(ValueError, match="max_prefill_wave"):
            InferenceEngine(
                CFG,
                RuntimeConfig(max_batch_size=2, max_seq_len=128,
                              prefill_chunk=16, max_prefill_wave=0),
            )


async def _gen_n(engine, prompt, n):
    return [t async for t in engine.generate(prompt, max_new_tokens=n)]


class TestPrefillWaveValidation:
    def test_non_power_of_two_rejected_loudly(self):
        with pytest.raises(ValueError, match="power of two"):
            InferenceEngine(
                CFG,
                RuntimeConfig(max_batch_size=16, max_seq_len=128,
                              prefill_chunk=16, max_prefill_wave=12),
            )
