"""Shared by tests/test_kda_mla_moe*.py: the toy model, its seeded tree, the engine harness.

Kimi Delta Attention (the gated delta rule with a decay a key CHANNEL) beside
latent attention in ONE stack, one leading dense layer, the experts chosen by
group and held by share (Ling-3.0-flash-VL's kind; preset
``debug-kda-mla-moe``: two periods ``K K M``, the first layer dense; 4 heads of
8; a latent of 16 | 4; 16 experts scored in 4 groups of which 2 are kept, 3 a
token, this "device" holding group 1).  Seeded random weights as the
benchmark's architecture file seeds them (the decay spanning its range
channel by channel, the gate's bias NOT zero, the norms' ``w`` off 1, the
embedding at unit scale), LOGITS compared and never sampled tokens.  The
other side of every comparison is the benchmark's plain reference,
``benchmarks/architectures/bailing-kda-mla-moe.py``: float32, the delta rule
token by token, latent attention expanded over the whole sequence, every held
expert on every token.

The weights and activations here are float32, so that the tolerance is tight
enough for the controls, each of which has to FAIL what the stated program
passes.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig, preset
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.sharding import make_mesh

ARCH = manifest.load_architecture("bailing-kda-mla-moe")
TOY = preset("debug-kda-mla-moe")
# float32 against float32: the two sides differ in the ORDER of sums (the
# two-level chunk form and its triangular solve against the recurrence, the
# one-pass step, the absorbed latent read against the expanded one, grouped
# experts against every expert masked, paged windows against whole rows) and
# in nothing else.  The stated program reads 1e-5 over a whole forward of 6
# layers and logits up to 4 in size; the nearest control (a gate taken in
# bfloat16) over 1e-3.  1e-4 as tests/_gdn_moe.py holds its own.
LOGIT_TOL = 1e-4


@pytest.fixture(autouse=True)
def both_forms_at_toy_size(monkeypatch):
    """At toy size the dense form's limit is 8 tokens, so that a decode step
    takes the dense form and a chunk of 16 the grouped one, as at the real size."""
    monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", 8)


def runtime(**kw) -> RuntimeConfig:
    base = dict(
        max_batch_size=2, max_seq_len=128, kv_layout="paged", page_size=8,
        chunked_prefill=True, prefill_chunk=16, window_buckets=(32, 128),
        compilation_cache=False, max_prefill_wave=2, decode_steps_per_dispatch=4,
    )
    base.update(kw)
    return RuntimeConfig(**base)


def seeded(config: ModelConfig = TOY, key: int = 3):
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    return ARCH.params(config, RuntimeConfig(), mesh, key)


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, TOY.vocab_size, n)]


class Spy:
    """Records every ``lm_logits`` a program computes, in order."""

    def __init__(self, monkeypatch):
        self.seen: list[np.ndarray] = []
        original = M.lm_logits

        def spied(x, params, eps, *rest):
            logits = original(x, params, eps, *rest)
            jax.debug.callback(lambda l: self.seen.append(np.asarray(l)), logits, ordered=True)
            return logits

        monkeypatch.setattr(M, "lm_logits", spied)

    def of_request(self, prompt: list[int], out: list[int], chunk: int) -> np.ndarray:
        """The logits that chose ``out``: the prompt's last position from the
        LAST chunk seen before the first step whose argmax chain is the
        served tokens, then one row of each of those steps."""
        steps = [(i, s) for i, s in enumerate(self.seen) if s.shape[1] == 1]
        last, n = len(prompt) - 1, len(out) - 1
        first, slot = next(
            (j, b) for j in range(len(steps) - n + 1) for b in range(steps[0][1].shape[0])
            if all(int(np.argmax(steps[j + i][1][b, 0])) == out[i + 1] for i in range(n))
        )
        chunks = [s for s in self.seen[: steps[first][0]] if s.shape[1] == chunk]
        row = next(r for r in range(chunks[-1].shape[0])
                   if int(np.argmax(chunks[-1][r, last % chunk])) == out[0])
        return np.stack([chunks[-1][row, last % chunk]]
                        + [steps[first + i][1][slot, 0] for i in range(n)])


def serve(engine_args: tuple, requests, sequential: bool = True, params=None, keep=False):
    """Outputs of ``requests`` (prompt, max_new_tokens) through one engine."""
    async def run():
        engine = InferenceEngine(
            *engine_args, seed=3, params=seeded(engine_args[0]) if params is None else params)
        await engine.start()
        try:
            async def one(prompt, n):
                return [t async for t in engine.generate(prompt, max_new_tokens=n)]

            if sequential:
                outs = [await one(p, n) for p, n in requests]
            else:
                outs = list(await asyncio.gather(*[one(p, n) for p, n in requests]))
            return outs, engine.params, (engine if keep else engine.stats.counters())
        finally:
            await engine.stop()

    return asyncio.run(run())


def reference_logits(params, config: ModelConfig, seq: list[int]) -> np.ndarray:
    tokens = np.asarray([seq], np.int32)
    return ARCH.forward_logits(params, config, tokens, np.asarray([len(seq)], np.int32))[0]


def forward(params, config, tokens, lens=None, **kw):
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    lens = jnp.full((B,), S, jnp.int32) if lens is None else jnp.asarray(lens)
    return M.forward(params, config, jnp.asarray(tokens), pos, M.make_empty_cache(config, B, S),
                     jnp.full((B,), S, jnp.int32), state=make_recurrent_state(config, B),
                     n_valid=lens, **kw)
