"""The real-chip lane (the reference's `live` analog, SURVEY §4).

Run with:  CALFKIT_TESTS_TPU=1 python -m pytest tests/test_tpu_live.py -m tpu -q

Deselected by default; each test is bounded.  Needs a process that owns a
chip; `chip_smoke.py` at the repo root is the quicker proof that the
serving path starts on one.
"""

from __future__ import annotations

import os

import pytest

pytestmark = pytest.mark.tpu

from tests._env import tpu_lane_enabled

requires_tpu_env = pytest.mark.skipif(
    not tpu_lane_enabled(),
    reason="set CALFKIT_TESTS_TPU=1 (conftest otherwise forces the CPU platform)",
)


def _chip():
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        pytest.skip("no accelerator visible")
    return devices


@requires_tpu_env
class TestChipSmoke:
    def test_matmul_alive(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        _chip()
        x = jnp.ones((256, 256), jnp.bfloat16)
        y = jnp.float32(x @ x)
        assert float(np.asarray(y).sum()) == pytest.approx(256**3, rel=1e-3)

    async def test_engine_generates_on_chip(self):
        import numpy as np

        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        _chip()
        engine = InferenceEngine(
            preset("debug"),
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=8),
        )
        await engine.start()
        out = [t async for t in engine.generate([1, 5, 9], max_new_tokens=16)]
        assert len(out) == 16
        again = [t async for t in engine.generate([1, 5, 9], max_new_tokens=16)]
        assert again == out  # greedy determinism on the accelerator
        await engine.stop()

    async def test_paged_matches_dense_on_chip(self):
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        _chip()
        kw = dict(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                  decode_steps_per_dispatch=8, page_size=16)
        dense = InferenceEngine(preset("debug"), RuntimeConfig(**kw), seed=3)
        paged = InferenceEngine(
            preset("debug"), RuntimeConfig(kv_layout="paged", **kw), seed=3
        )
        await dense.start()
        await paged.start()
        prompt = list(range(2, 30))
        want = [t async for t in dense.generate(prompt, max_new_tokens=16)]
        got = [t async for t in paged.generate(prompt, max_new_tokens=16)]
        assert got == want
        await dense.stop()
        await paged.stop()


@requires_tpu_env
class TestRound4FeaturesOnChip:
    """Round-4 features under real hardware: the kafka-wire mesh carrying
    a chip-backed engine, `auto` taking the paged decode kernel, and
    the long-context sp lane on the accelerator."""

    async def test_agent_on_chip_over_kafka_wire(self):
        """client → kafkad (real Kafka wire) → worker → engine ON CHIP →
        streamed reply: the full production shape, all native pieces."""
        from calfkit_tpu.client import Client
        from calfkit_tpu.inference import JaxLocalModelClient
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.mesh.kafka_wire import (
            KafkaWireMesh,
            find_kafkad,
            spawn_kafkad,
        )
        from calfkit_tpu.nodes import Agent
        from calfkit_tpu.worker import Worker

        _chip()
        if find_kafkad() is None:
            pytest.skip("kafkad not built")
        proc = spawn_kafkad(0)
        try:
            mesh = KafkaWireMesh(f"127.0.0.1:{proc.kafkad_port}")
            client_mesh = KafkaWireMesh(f"127.0.0.1:{proc.kafkad_port}")
            await client_mesh.start()
            model = JaxLocalModelClient(
                config=preset("debug"),
                runtime=RuntimeConfig(
                    max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                    decode_steps_per_dispatch=8,
                ),
                max_new_tokens=12,
            )
            agent = Agent("chip_kafka_agent", model=model)
            async with Worker([agent], mesh=mesh, owns_transport=True):
                client = Client.connect(client_mesh)
                result = await client.agent("chip_kafka_agent").execute(
                    "hello from the wire", timeout=600
                )
                assert isinstance(result.output, str)
                await client.close()
            await client_mesh.stop()
            await model.stop()
        finally:
            proc.terminate()
            proc.wait(timeout=5)

    async def test_attn_auto_serves_through_the_kernel_on_chip(self):
        """On a chip `auto` takes the paged decode kernel for a paged
        engine with eligible heads, and the engine serves the explicit XLA
        engine's greedy tokens: the one selection exercised on hardware."""
        from dataclasses import replace

        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        if _chip()[0].platform != "tpu":
            pytest.skip("the kernel is a TPU kernel")
        wide = replace(preset("debug"), d_model=256, n_heads=2, n_kv_heads=1)
        kw = dict(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                  decode_steps_per_dispatch=8, kv_layout="paged", page_size=16)
        xla_engine = InferenceEngine(
            wide, RuntimeConfig(attention_impl="xla", **kw), seed=3
        )
        await xla_engine.start()
        prompt = list(range(3, 40))
        want = [t async for t in xla_engine.generate(prompt, max_new_tokens=12)]
        await xla_engine.stop()
        auto_engine = InferenceEngine(wide, RuntimeConfig(**kw), seed=3)
        assert auto_engine._attn_impl == "pallas"
        await auto_engine.start()
        got = [t async for t in auto_engine.generate(prompt, max_new_tokens=12)]
        await auto_engine.stop()
        assert got == want

    async def test_long_context_sp_lane_on_chip(self):
        """A prompt past max_seq_len rides the ring-prefill lane on the
        accelerator and decodes greedily."""
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        _chip()
        engine = InferenceEngine(
            preset("debug"),
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, long_context=True,
                          long_new_cap=8),
        )
        await engine.start()
        prompt = [(7 * i + 3) % 500 for i in range(200)]  # > max_seq_len
        out = [t async for t in engine.generate(prompt, max_new_tokens=6)]
        assert len(out) == 6
        assert engine.stats.long_requests == 1
        await engine.stop()

    async def test_int4_engine_on_chip(self):
        """int4 packed weights (r5): unpack + group-scale dequant compiles
        and serves deterministically on the accelerator, and matches the
        same engine's tokens across runs."""
        from calfkit_tpu.inference.config import RuntimeConfig, preset
        from calfkit_tpu.inference.engine import InferenceEngine

        _chip()
        engine = InferenceEngine(
            preset("debug"),
            RuntimeConfig(max_batch_size=2, max_seq_len=128, prefill_chunk=16,
                          decode_steps_per_dispatch=4, quantization="int4",
                          kv_layout="paged", page_size=16, num_kv_pages=33),
            seed=11,
        )
        await engine.start()
        prompt = [3, 141, 59, 26]
        out = [t async for t in engine.generate(prompt, max_new_tokens=12)]
        again = [t async for t in engine.generate(prompt, max_new_tokens=12)]
        await engine.stop()
        assert len(out) == 12
        assert again == out
