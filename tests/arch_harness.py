"""ONE harness for the per-architecture test families (tests/test_hybrid_mamba,
test_mla_moe*, test_gdn_moe*, test_cohere2_moe*, test_mellum_moe*, test_kda_mla_moe*,
test_evabyte* and their
kernels' files): a ``Family`` record a kind of model, and what every family's
tests do with it.

Every family compares LOGITS, never sampled tokens, of a toy model on the CPU
(float32 weights and activations, seeded random weights as the benchmark's
architecture file seeds them) against the benchmark's plain reference,
``benchmarks/architectures/<arch>.py``.  Each tolerance is written with its
reason beside its family; the controls of a family (a lower precision, a piece
of wrong mathematics) each have to FAIL the tolerance the stated program
passes, and stay in that family's test file with whatever else is its own.

An engine's jitted programs are closures of the instance: two engines of one
configuration in one process share nothing, and a build costs 20-80 s of a
tier-1 run.  So a test that serves through ``(toy, runtime(), seeded())`` and
only reads what came back takes the module's ``Standing`` engine
(``Family.standing()``); a test keeps a build of its own (``Family.serve``)
only where the build IS what it tests, and says so.
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks import manifest
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import moe
from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig, preset
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.sharding import make_mesh


class Spy:
    """Records every ``lm_logits`` a program computes, in order: the engine
    gives out tokens, and these tests compare logits."""

    def __init__(self, monkeypatch=None, seen=None):
        self.seen: list[np.ndarray] = [] if seen is None else seen
        if monkeypatch is not None:
            self.patch(monkeypatch)

    def patch(self, monkeypatch) -> None:
        """Programs TRACED while the patch stands report to this spy for as
        long as they live."""
        original = M.lm_logits

        def spied(x, params, eps, *rest, **kw):
            logits = original(x, params, eps, *rest, **kw)
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


async def collect(engine, prompt, n, probe=None):
    """The tokens of one request; ``probe(engine)`` is called at every one."""
    out = []
    async for t in engine.generate(prompt, max_new_tokens=n):
        out.append(t)
        if probe is not None:
            probe(engine)
    return out


async def _serve(engine, requests, sequential, probe=None):
    if sequential:
        return [await collect(engine, p, n, probe) for p, n in requests]
    return list(await asyncio.gather(*[collect(engine, p, n, probe) for p, n in requests]))


class Standing:
    """ONE spied engine that stands for a module's tests (its programs compile
    once): ``serve`` runs requests through it and gives back what they were
    served, the logits ``lm_logits`` computed meanwhile, the counters as they
    stand and what the requests ADDED to them.  The spy and the dense form's
    toy limit are patched in around each ``serve`` alone: no other test's
    engine is spied, and a test's own ``monkeypatch.undo()`` finds the
    program's own values."""

    def __init__(self, family: "Family", engine_args: tuple, params):
        self.family, self.spy = family, Spy()
        self._loop = asyncio.new_event_loop()
        with self._patched():
            self.engine = InferenceEngine(*engine_args, seed=3, params=params)
            self._loop.run_until_complete(self.engine.start())
        self.params = self.engine.params

    @contextlib.contextmanager
    def _patched(self):
        with pytest.MonkeyPatch.context() as patch:
            self.family.toy_forms(patch)
            self.spy.patch(patch)
            yield

    def serve(self, requests, sequential: bool = True, probe=None) -> SimpleNamespace:
        from calfkit_tpu.observability.metrics import metrics_text

        async def run():
            outs = await _serve(self.engine, requests, sequential, probe)
            for _ in range(400):  # the dispatch launched before the last block was seen
                if self.engine._pend is None and not self.engine._active:
                    break
                await asyncio.sleep(0.005)
            return outs

        before, mark = self.engine.stats.counters(), len(self.spy.seen)
        with self._patched():
            outs = self._loop.run_until_complete(run())
        after = self.engine.stats.counters()
        added = {k: v - before[k] for k, v in after.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
        return SimpleNamespace(
            outs=outs, requests=requests, params=self.params, engine=self.engine,
            counters=after, added=added, metrics=metrics_text(),
            spy=Spy(seen=self.spy.seen[mark:]))

    def close(self) -> None:
        self._loop.run_until_complete(self.engine.stop())
        self._loop.close()


@dataclass(frozen=True)
class Family:
    """A kind of model under test: the benchmark's architecture file (the
    reference and, where ``seed_tree`` is None, the seeding), the toy
    configuration, the logit tolerance, and what its runtime and its tree
    differ by from the common ones."""

    arch_name: str
    toy: ModelConfig
    logit_tol: float
    # the runtime settings that differ from the common ten below
    runtime_over: dict = field(default_factory=dict)
    # ``moe._DENSE_MAX_TOKENS`` at toy size (None: no routed experts), so that a
    # decode step takes the dense form and a chunk of 16 tokens the grouped one,
    # as they do at the real size
    dense_max_tokens: int | None = None
    # (config, key) -> tree; None: the architecture file's own ``params``
    seed_tree: Callable | None = None
    # what ``forward`` hands the program beside tokens, positions and an empty cache
    forward_takes: tuple = ("state", "n_valid")

    @functools.cached_property
    def arch(self):
        return manifest.load_architecture(self.arch_name)

    def toy_forms(self, monkeypatch) -> None:
        if self.dense_max_tokens is not None:
            monkeypatch.setattr(moe, "_DENSE_MAX_TOKENS", self.dense_max_tokens)

    def runtime(self, **kw) -> RuntimeConfig:
        base = dict(
            max_batch_size=2, max_seq_len=128, kv_layout="paged", page_size=8,
            chunked_prefill=True, prefill_chunk=16, window_buckets=(32, 128),
            compilation_cache=False, max_prefill_wave=2, decode_steps_per_dispatch=4,
        )
        base.update(self.runtime_over)
        base.update(kw)
        return RuntimeConfig(**base)

    def seeded(self, config: ModelConfig | None = None, key: int = 3):
        """The benchmark's seeded tree: every norm's ``w`` off its identity,
        the gate's logits spread, the embedding at unit scale (or the
        family's own ``seed_tree``)."""
        config = self.toy if config is None else config
        if self.seed_tree is not None:
            return self.seed_tree(config, key)
        mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
        return self.arch.params(config, RuntimeConfig(), mesh, key)

    def prompt_of(self, n: int, seed: int = 0) -> list[int]:
        return [int(t) for t in np.random.default_rng(seed).integers(3, self.toy.vocab_size, n)]

    def reference_logits(self, params, config: ModelConfig, seq: list[int]) -> np.ndarray:
        tokens = np.asarray([seq], np.int32)
        return self.arch.forward_logits(params, config, tokens, np.asarray([len(seq)], np.int32))[0]

    def forward(self, params, config, tokens, lens=None, **kw):
        """The program's whole forward of ``tokens`` as one chunk from an
        empty cache (and a zero state)."""
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if "n_valid" in self.forward_takes or lens is not None:
            kw["n_valid"] = jnp.full((B,), S, jnp.int32) if lens is None else jnp.asarray(lens)
        if "state" in self.forward_takes:
            kw["state"] = make_recurrent_state(config, B)
        return M.forward(params, config, jnp.asarray(tokens), pos,
                         M.make_empty_cache(config, B, S), jnp.full((B,), S, jnp.int32), **kw)

    def serve(self, engine_args: tuple, requests, sequential: bool = True, params=None,
              keep: bool = False, probe=None):
        """Outputs of ``requests`` (prompt, max_new_tokens) through an engine
        BUILT for them -> (outputs, its tree or with ``keep`` the engine, its
        counters); ``probe(engine)`` is called at every token."""
        async def run():
            engine = InferenceEngine(
                *engine_args, seed=3,
                params=self.seeded(engine_args[0]) if params is None else params)
            await engine.start()
            try:
                outs = await _serve(engine, requests, sequential, probe)
                return outs, (engine if keep else engine.params), engine.stats.counters()
            finally:
                await engine.stop()

        return asyncio.run(run())

    @contextlib.contextmanager
    def standing(self, engine_args: tuple | None = None, params=None):
        """For a module-scoped fixture: ``(toy, runtime(), seeded())`` built
        and started once, stopped when the module's tests are done."""
        args = (self.toy, self.runtime()) if engine_args is None else engine_args
        engine = Standing(self, args, self.seeded(args[0]) if params is None else params)
        try:
            yield engine
        finally:
            engine.close()

    def served_in_three_phases(self, engine: Standing) -> SimpleNamespace:
        """What three suites of a delta-rule family read of ONE engine: a
        prompt of 37 with 21 new tokens, then three requests one after
        another, then two of them at once."""
        requests = [(self.prompt_of(21, seed=s), 6) for s in (1, 2, 3)]
        first = engine.serve([(self.prompt_of(37), 21)])
        alone = engine.serve(requests)
        together = engine.serve(requests[:2], sequential=False)
        return SimpleNamespace(
            first=first.outs[0], alone=alone.outs, together=together.outs, requests=requests,
            params=engine.params, counters=[first.counters, alone.counters],
            metrics=first.metrics, seen=[first.spy, alone.spy])


def check_the_step_kernel_serves_what_xla_serves(family, wide, monkeypatch, chunk=16, **rt):
    """The engine-level case of the decode step's expert kernel (PR 53) for a
    family whose experts are held by share: ``wide`` (the toy with experts of
    one lane tile a side, inside every kernel's rule the engine will name)
    under ``attention_impl="pallas_interpret"`` serves the tokens "xla"
    serves and the reference's logits, counts the same experts hit, and
    counts every decode step run as a step of the kernel; under "xla" none."""
    from calfkit_tpu.inference.pallas_attention import KERNEL_TRACES

    params = family.seeded(wide)
    prompt = family.prompt_of(29, seed=9)
    (xla,), _, base = family.serve(
        (wide, family.runtime(attention_impl="xla", **rt)), [(prompt, 9)], params=params)
    built = KERNEL_TRACES["moe_step", "interpreted"]
    spy = Spy(monkeypatch)
    (out,), engine, counters = family.serve(
        (wide, family.runtime(attention_impl="pallas_interpret", **rt)), [(prompt, 9)],
        params=params, keep=True)
    assert engine._moe_step_impl == "pallas_interpret" and out == xla
    assert KERNEL_TRACES["moe_step", "interpreted"] > built
    assert counters["moe_experts_hit"] == base["moe_experts_hit"] > 0
    assert counters["moe_assignments"] == base["moe_assignments"]
    steps = counters["decode_dispatches"] * engine.runtime.decode_steps_per_dispatch
    assert (base["moe_step_kernel_steps"], counters["moe_step_kernel_steps"]) == (0, steps)
    assert steps > 0 and counters["short_dispatches"] == 0
    got = spy.of_request(prompt, out, chunk)
    want = family.reference_logits(params, wide, prompt + out)
    assert np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max() < family.logit_tol


def check_the_step_kernel_is_not_taken(engine, monkeypatch, platform: str, under: tuple) -> None:
    """``engine`` (a module's standing one, built under "auto" on this
    process's CPU, after it has served) ran no step of the expert kernel, and
    on ``platform`` takes it under none of ``under``: experts held WHOLE
    under no value on a TPU, experts held by share not under "auto" on a CPU."""
    from dataclasses import replace

    counters = engine.stats.counters()
    assert engine._moe_step_impl == "xla"
    assert counters["moe_step_kernel_steps"] == 0 < counters["moe_experts_hit"]
    real = jax.devices()
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(platform=platform)] if not a else real)
    for impl in under:
        monkeypatch.setattr(engine, "runtime", replace(engine.runtime, attention_impl=impl))
        assert engine._resolved_moe_step_impl() == "xla", impl


@pytest.fixture(autouse=True)
def both_forms_at_toy_size(request, monkeypatch):
    """Imported by a family's test file: every test of it runs with the dense
    form's limit at the family's toy size (``FAMILY`` of the module)."""
    request.module.FAMILY.toy_forms(monkeypatch)


@pytest.fixture(scope="module")
def standing(request):
    """Imported by a family's test file: ``(toy, runtime(), seeded())`` of the
    module's ``FAMILY`` built once, for the tests that only read what it
    served (a test that builds its own says why)."""
    with request.module.FAMILY.standing() as engine:
        yield engine


def _mla_moe_tree(config: ModelConfig, key: int):
    """The program's random tree (every matrix at 1/sqrt(fan_in), the bias
    zero) with the two leaves that decide the routing seeded as the
    benchmark's architecture file seeds them: the gate at twice that, so the
    scores spread, and ``e_score_correction_bias`` at some hundredths, NOT
    zero, so that a program that gets the bias wrong disagrees."""
    params = M.init_params(config, jax.random.key(key))
    experts = params["layers"]["moe"]
    experts["router"] = experts["router"] * 2.0
    experts["router_bias"] = jax.random.uniform(
        jax.random.key(key + 100), experts["router_bias"].shape, jnp.float32, -0.1, 0.1)
    return params


# Mamba-2 layers beside attention in one stack (granite-4.0-h-micro's kind): two
# periods of (mamba, mamba, attention), every kind of layer twice, the
# multipliers and the position rule of the real model.  The engine draws the
# tree itself from its seed (``seed_tree`` gives None).
HYBRID_MAMBA = Family(
    arch_name="granite-hybrid",
    toy=ModelConfig(
        name="toy-hybrid", vocab_size=128, d_model=32, n_layers=6, n_heads=4, n_kv_heads=2,
        d_ff=64, layer_types=("mamba", "mamba", "attention") * 2,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, dtype="float32", position_embedding="none",
        attention_multiplier=0.25, embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, tie_embeddings=True, max_seq_len=1024,
    ),
    # float32 against float32: the two sides differ in the ORDER of sums (the
    # chunked scan against the recurrence, bucketed attention against whole
    # rows).  Two readings set the limit, over 512 generated positions of the
    # bfloat16 control's run, logits up to 0.54 in size: the float32 state reads
    # 2.4e-7 at the worst position, the bfloat16 state 3.7e-3 (and passes 1e-4
    # at its 27th step).  2e-5 stands a factor of 80 above the first and 180
    # below the second.
    logit_tol=2e-5,
    seed_tree=lambda config, key: None,
)

# Latent attention and routed experts in one stack (Kimi-VL-A3B's decoder's
# kind): 8 experts with 2 a token and a shared one, a leading dense layer, rope
# on a part of the head (8 of 24), a latent (32) narrower than the heads' keys.
MLA_MOE = Family(
    arch_name="deepseek-mla-moe",
    toy=ModelConfig(
        name="toy-mla-moe", vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=64, rope_theta=800000.0, max_seq_len=256, dtype="float32",
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, n_experts_per_tok=2, n_shared_experts=1, moe_d_ff=16,
        first_k_dense=1, routed_scaling_factor=2.446,
    ),
    # float32 against float32: the two sides differ in the ORDER of sums (the
    # absorbed read against the expanded one, grouped experts against every
    # expert masked, bucketed windows against whole rows) and in nothing else: a
    # choice of experts is decided by float32 scores on both sides.  Two
    # readings set the limit, over the 21 generated positions of the engine test
    # and its controls, logits up to 3.6 in size: the stated precision reads
    # 2.2e-6 at the worst position (4.5e-6 over 96 positions); a bfloat16 latent
    # pool 8.7e-3 (0.61 over 96: somewhere an expert flips) and a bfloat16
    # router product 5.6e-4 (the rounded weights of the same experts; a flipped
    # expert would move the logits by its whole weighted output), each past 1e-4
    # at the first generated position.  1e-4 stands a factor of 22 above the
    # stated precision's largest reading and 5.6 below the nearer of the other
    # two.  (Readings of the first session's tree, gate x2 and a bias of +-0.1;
    # ``_mla_moe_tree`` draws the same two leaves, and every case holds the
    # limit on its side.)
    logit_tol=1e-4,
    runtime_over=dict(prefix_cache=True),
    # the two forms cross at 2,048 tokens at the published widths (moe.py); at
    # toy size the limit is two tokens an expert
    dense_max_tokens=16,
    seed_tree=_mla_moe_tree,
    forward_takes=(),
)

# Gated DeltaNet beside gated attention, the expert block in every layer, the
# experts held by share (Qwen3-Next-80B-A3B's kind; preset ``debug-gdn-moe``):
# two periods ``L L L A``, 2 key heads serving 4 value heads, 4 query heads over
# 2 KV heads with the rotation on a quarter of the head, 8 experts scored with 3
# a token of which this "device" holds 4 (share 1 of 2), one gated shared expert.
GDN_MOE = Family(
    arch_name="qwen3-next-gdn-moe",
    toy=preset("debug-gdn-moe"),
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
    logit_tol=1e-4,
    dense_max_tokens=8,
)

# Kimi Delta Attention (the gated delta rule with a decay a key CHANNEL) beside
# latent attention in ONE stack, one leading dense layer, the experts chosen by
# group and held by share (Ling-3.0-flash-VL's kind; preset
# ``debug-kda-mla-moe``: two periods ``K K M``, the first layer dense; 4 heads
# of 8; a latent of 16 | 4; 16 experts scored in 4 groups of which 2 are kept, 3
# a token, this "device" holding group 1).
KDA_MLA_MOE = Family(
    arch_name="bailing-kda-mla-moe",
    toy=preset("debug-kda-mla-moe"),
    # float32 against float32: the two sides differ in the ORDER of sums (the
    # two-level chunk form and its triangular solve against the recurrence, the
    # one-pass step, the absorbed latent read against the expanded one, grouped
    # experts against every expert masked, paged windows against whole rows) and
    # in nothing else.  The stated program reads 1e-5 over a whole forward of 6
    # layers and logits up to 4 in size; the nearest control (a gate taken in
    # bfloat16) over 1e-3.  1e-4 as ``GDN_MOE`` holds its own.
    logit_tol=1e-4,
    dense_max_tokens=8,
)

# Sliding-window layers beside global ones without positions, the parallel
# block, sigmoid-routed experts held by share with the shared experts averaged
# (command-a-plus's kind; preset ``debug-window-moe``): two periods ``W W W G``,
# a window of 24 on pages of 8 (three pages: the real one is 64), 8 query heads
# over 2 KV heads, 8 experts scored with 3 a token of which this "device" holds
# 4 (share 0 of 2), 2 shared experts averaged.
WINDOW_MOE = Family(
    arch_name="cohere2-moe-swa",
    toy=preset("debug-window-moe"),
    # float32 against float32: the two sides differ in the ORDER of sums (key
    # blocks with a running maximum against one softmax over the row, the ring of
    # pages and the fresh tokens merged against whole rows, grouped experts
    # against every expert masked) and in nothing else.  The stated program reads
    # 2e-5 at the worst position over 8 layers and logits up to 30 in size; the
    # nearest control reads 1e-2 and the others more (their tests assert each).
    logit_tol=1e-4,
    runtime_over=dict(window_buckets=(128,)),
    dense_max_tokens=8,
    forward_takes=("n_valid",),
)

# Sliding-window layers beside global ones, each kind rotating by its own law
# (the plain one; YaRN), the sequential RMSNorm block, softmax-routed experts
# ALL held and no shared one, an untied head (Mellum 2's kind; preset
# ``debug-mellum``): two periods ``W W W G``, a window of 24 on pages of 8
# SHORTER than the chunk of 32, 8 query heads over 2 KV heads, 8 experts with 3 a
# token, YaRN over an original context of 64 where the rows run to 128.
MELLUM_MOE = Family(
    arch_name="mellum-moe-swa",
    toy=preset("debug-mellum"),
    # float32 against float32, as ``WINDOW_MOE`` holds its own and for its
    # reasons: the two sides differ in the ORDER of sums and in nothing else.  The
    # stated program reads 4e-5 at the worst position over 8 layers and logits up
    # to 6 in size; the nearest control reads over 1e-3 (their tests assert each).
    logit_tol=1e-4,
    runtime_over=dict(window_buckets=(128,), prefill_chunk=32),
    dense_max_tokens=8,
    forward_takes=("n_valid",),
)

# A gated short convolution (a state that is the conv tail alone) beside rotary
# GQA attention with normed heads, two leading dense layers, then bias-routed
# experts with no shared one (LFM2-8B-A1B's kind; preset ``debug-lfm2-moe``:
# the cell's cut ``c c A c`` x 3, a head of 4 layers and two periods in the
# scan; 4 query heads over 2 KV heads of 8; 8 experts with 3 a token).
LFM2_MOE = Family(
    arch_name="lfm2-conv-gqa-moe",
    toy=preset("debug-lfm2-moe"),
    # float32 against float32: the two sides differ in the ORDER of sums (the
    # tail and a chunk's window against one padded sum over the row, grouped
    # experts against every expert masked, paged windows against whole rows)
    # and in nothing else.  The stated program reads 1.5e-5 at the worst
    # position over 12 layers and logits up to 5 in size; the nearest control
    # (the taps summed in bfloat16) reads 4e-3 and the others more (their tests
    # assert each).  1e-4 as ``GDN_MOE`` holds its own.
    logit_tol=1e-4,
    dense_max_tokens=8,
)

# EVA attention in every layer (EvaByte's kind; preset ``debug-evabyte``): an
# exact ALIGNED window of 32 beside one pooled key and value for every 4
# positions behind it, 4 heads of 8 (one query a KV head), a float32 residual
# stream, norms that multiply by 1 + g, a head of 2 x 64 rows of which head 0 is
# served.  A prefill chunk is one window; a dispatch of 8 steps crosses a
# window's edge with up to two chunks of its own completed behind it.
EVABYTE = Family(
    arch_name="evabyte-eva",
    toy=preset("debug-evabyte"),
    # float32 against float32: the two sides differ in the ORDER of sums (the
    # ring, the summary pages and the fresh tokens merged by their maxima
    # against one softmax over a whole row, the chunk's keys behind the
    # summaries in one blocked pass) and in nothing else.  Two readings set the
    # limit, over 3 layers and logits up to 4.2 in size: the stated program reads
    # 2.1e-6 at the worst position of a forward of four windows at both heads
    # and 1.8e-6 through the engine across three edges; the nearest control, a
    # pooling softmax taken in bfloat16, 5.3e-3 (uniform weights 0.52, no mu
    # 0.61; their tests assert each).  1e-4 stands a factor of 48 above the
    # first and 53 below the second.
    logit_tol=1e-4,
    runtime_over=dict(max_seq_len=256, window_buckets=(256,), prefill_chunk=32,
                      decode_steps_per_dispatch=8),
    forward_takes=(),
)

# ----------------------------------------------------------------------------
# The grouped expert products over the STACK (PR 34): one reading shared by
# ``tests/test_mla_moe.py`` (every expert held) and ``tests/test_gdn_moe.py``
# (experts held by share).  ``experts_grouped`` takes the stacked leaves and the
# layer's index and must give, for EVERY layer of a stack of three, what the
# parent's form gave on the layer sliced out (the slice as a stack of one: the
# parent's kernel call) and what the dense form gives: under ``jit``, inside a
# ``lax.scan`` with the index traced, as ``model.py``'s stacks call it.  The
# routings are made by hand, so that a group is exactly as empty or as full as
# the case says.
STACK_LAYERS, STACK_TOKENS = 3, 40
def experts_dense_rows_first(h, onehot, weights, lp):
    """``moe.experts_dense`` as it was spelled through PR 44, the ROWS first
    (``"td,edf->etf"``): the reference the respelled form is held to in
    float32 (``tests/test_gdn_moe.py``), and what the TPU compiler copies a
    whole expert stack for from 128 rows on (``tests/test_tpu_compile.py``)."""
    gates = jnp.sum(onehot * weights[..., None], axis=1)
    g = jnp.einsum("td,edf->etf", h, lp["w_gate"])
    u = jnp.einsum("td,edf->etf", h, lp["w_up"])
    act = jax.nn.silu(g) * u
    act = (act.astype(jnp.float32) * gates.T[:, :, None]).astype(h.dtype)
    return jnp.einsum("etf,efd->td", act, lp["w_down"])


STACK_ROUTINGS = ("even", "one_expert_empty", "first_expert_all", "last_expert_all")


def stack_routing(case: str, key: int, scored: int, k: int, first: int, held: int):
    """(chosen [T, k] int32 among ``scored`` experts, k distinct a token;
    weights [T, k] float32): ``first`` and ``held`` say which of them this
    device holds."""
    order = np.stack([
        np.random.default_rng((key, t)).permutation(scored) for t in range(STACK_TOKENS)])
    if case == "one_expert_empty":  # the second held expert is nobody's choice
        order = np.stack([row[row != first + 1] for row in order])
    elif case in ("first_expert_all", "last_expert_all"):  # ... is EVERY token's first choice
        full = first if case == "first_expert_all" else first + held - 1
        order = np.stack([np.concatenate([[full], row[row != full]]) for row in order])
    elif case == "every_pair_absent":  # a share alone: all choices fall on experts held elsewhere
        away = [e for e in range(scored) if not first <= e < first + held]
        order = np.stack([
            np.random.default_rng((key, t)).permutation(away) for t in range(STACK_TOKENS)])
    else:
        assert case == "even", case
    weights = np.random.default_rng(key).uniform(0.1, 1.0, (STACK_TOKENS, k)).astype(np.float32)
    return jnp.asarray(order[:, :k], jnp.int32), jnp.asarray(weights)


@functools.lru_cache(maxsize=None)
def stack_three_forms(config, case: str):
    """→ (over the stack, the parent's on the slice, dense), each
    ``[STACK_LAYERS, STACK_TOKENS, D]``, and the held pairs of the routing."""
    c = config
    share, first, E = c.expert_share, c.expert_first, c.n_routed_experts
    stack = jax.tree.map(
        lambda a: a[:STACK_LAYERS], moe.init_moe_params(c, jax.random.key(3), jnp.float32))
    assert stack["w_gate"].shape[:2] == (STACK_LAYERS, E)
    h = jax.random.normal(jax.random.key(4), (STACK_TOKENS, c.d_model))
    chosen, weights = stack_routing(case, 5, c.experts_scored, c.n_experts_per_tok, first, E)
    onehot = chosen[..., None] == jnp.arange(E, dtype=jnp.int32) + first

    @jax.jit
    def over_layers(stack, h, chosen, weights):
        def body(_, m):
            lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False), stack)
            return None, (
                moe.experts_grouped(h, chosen, onehot, weights, stack, m, share),
                # the parent's products: ``lax.ragged_dot`` on the SLICE with the layer's own
                # sizes, which is what a stack of that one layer compiles to
                moe.experts_grouped(
                    h, chosen, onehot, weights,
                    {n: lp[n][None] for n in ("w_gate", "w_up", "w_down")}, 0, share),
                moe.experts_dense(h, onehot, weights, lp),
            )
        return lax.scan(body, None, jnp.arange(STACK_LAYERS, dtype=jnp.int32))[1]

    forms = tuple(np.asarray(a) for a in over_layers(stack, h, chosen, weights))
    return forms, np.asarray(jnp.sum(onehot, axis=(0, 1)))


def stack_check(config, case: str, m: int) -> None:
    (stacked, parent, dense), pairs = stack_three_forms(config, case)
    k, E = config.n_experts_per_tok, config.n_routed_experts
    if case == "one_expert_empty":
        assert pairs[1] == 0 and pairs.sum() > 0
    elif case == "first_expert_all":
        assert pairs[0] == STACK_TOKENS
    elif case == "last_expert_all":
        assert pairs[E - 1] == STACK_TOKENS
    elif case == "every_pair_absent":
        assert pairs.sum() == 0 and not stacked[m].any() and not dense[m].any()
    if not config.expert_share:
        assert pairs.sum() == STACK_TOKENS * k  # every expert held: no pair behind the last group
    # the same products on the same operands: the parent's kernel call, bit for bit
    assert np.array_equal(stacked[m], parent[m])
    # float32 sums in two orders, outputs of order 1 (the file's other form tests: 5e-7)
    assert np.abs(stacked[m] - dense[m]).max() < 1e-5
    if pairs.sum():  # an offset wrong by ONE layer is another layer's experts
        for other in range(STACK_LAYERS):
            if other != m:
                assert np.abs(stacked[m] - dense[other]).max() > 1e-2
