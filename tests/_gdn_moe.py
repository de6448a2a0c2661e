"""Shared by tests/test_gdn_moe*.py: the toy model, its seeded tree, the engine harness.

Gated DeltaNet beside gated attention, the expert block in every layer,
the experts held by share (Qwen3-Next-80B-A3B's kind).

Toy widths on the CPU that keep the RATIOS of the real model: two periods
``L L L A``, 2 key heads serving 4 value heads, 4 query heads over 2 KV heads
with the rotation on a quarter of the head, 8 experts scored with 3 a token
of which this "device" holds 4 (share 1 of 2), one gated shared expert.
Seeded random weights as the benchmark's architecture file seeds them (the
norms' ``w`` NOT at their identity, the embedding at unit scale), LOGITS
compared and never sampled tokens.  The other side of every comparison is
the benchmark's plain reference, ``benchmarks/architectures/
qwen3-next-gdn-moe.py``: float32, DeltaNet token by token, attention over
the whole sequence, every held expert on every token.

Each tolerance is written with its reason where it is set.  The weights and
activations here are float32, so that the tolerances are tight enough for
the controls: the same run with ``S`` in bfloat16, with the gate's product
in bfloat16, and five pieces of wrong mathematics, each has to FAIL the
tolerance that the stated program passes.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import warnings
from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import gdn, moe
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import (
    ModelConfig,
    RuntimeConfig,
    SpecConfig,
    UnsupportedWithRecurrentLayers,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.sharding import make_mesh

ARCH = manifest.load_architecture("qwen3-next-gdn-moe")
TOY = preset("debug-gdn-moe")
# float32 against float32: the two sides differ in the ORDER of sums (the
# chunkwise delta rule and its triangular solve against the recurrence, the
# one-pass step, grouped experts against every expert masked, paged windows
# against whole rows) and in nothing else.  Two readings set the limit, over
# 8 layers and logits up to 4.7 in size: the stated program reads 1.1e-5 at
# the worst generated position through the engine and 1.4e-5 over a whole
# forward (rounding grows with depth: 1.3e-6 after one layer); the nearest
# control, a gate taken in bfloat16, 3e-3 and the others more (their tests
# assert each).  1e-4 stands a factor of 7 above the first and 30 below the
# second.
LOGIT_TOL = 1e-4


@pytest.fixture(autouse=True)
def both_forms_at_toy_size(monkeypatch):
    """At toy size the limit of the dense form is two tokens an expert
    scored, so that a decode step takes the dense form and a chunk of 16
    tokens the grouped one, as they do at the real size."""
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
    """The benchmark's seeded tree: every norm's ``w`` off its identity, the
    gate's logits spread, the embedding at unit scale."""
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    return ARCH.params(config, RuntimeConfig(), mesh, key)


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, TOY.vocab_size, n)]


class Spy:
    """Records every ``lm_logits`` a program computes, in order."""

    def __init__(self, monkeypatch):
        self.seen: list[np.ndarray] = []
        original = M.lm_logits

        def spied(x, params, eps, scaling=1.0, plus_one=False):
            logits = original(x, params, eps, scaling, plus_one)
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


def serve(engine_args: tuple, requests, sequential: bool = True, params=None):
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
            return outs, engine.params, engine.stats.counters()
        finally:
            await engine.stop()

    return asyncio.run(run())


def served_in_three_phases(spy_of, engine_args: tuple, params, prompt_of):
    """ONE spied engine for the tests that would each build the same one (its
    programs compile once): a prompt of 37 with 21 new tokens, then three
    requests one after another, then two of them at once -> what each phase
    served, what ``lm_logits`` computed in the first two, the counters as
    those two left them and the metrics text after the first.  ``spy_of`` is
    the test module's own ``Spy`` (this model's or the Kimi Delta one's)."""
    from calfkit_tpu.observability.metrics import metrics_text

    requests = [(prompt_of(21, seed=s), 6) for s in (1, 2, 3)]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_DENSE_MAX_TOKENS", 8)  # both_forms_at_toy_size, which is a test's
        spy = spy_of(patch)

        async def run():
            engine = InferenceEngine(*engine_args, seed=3, params=params)
            await engine.start()
            try:
                async def one(prompt, n):
                    return [t async for t in engine.generate(prompt, max_new_tokens=n)]

                first = await one(prompt_of(37), 21)
                marks, counted, text = [len(spy.seen)], [engine.stats.counters()], metrics_text()
                alone = [await one(p, n) for p, n in requests]
                marks.append(len(spy.seen))
                counted.append(engine.stats.counters())
                together = list(await asyncio.gather(*[one(p, n) for p, n in requests[:2]]))
                return SimpleNamespace(
                    first=first, alone=alone, together=together, requests=requests,
                    params=engine.params, counters=counted, metrics=text,
                    seen=[SimpleNamespace(seen=spy.seen[a:b])
                          for a, b in zip([0] + marks, marks)])
            finally:
                await engine.stop()

        return asyncio.run(run())  # the patches are undone: no other test's engine is spied


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


