"""Mellum 2's kind through the ENGINE: a window SHORTER than the prefill chunk,
chunked prefill then decode through the ring of pages past its wraps and past
YaRN's original context, short and long rows mixed in one batch, the two new
counters, the scopes, and what the served rows leave in the engine under the
rotation's controls.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import asyncio
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference.config import SpecConfig, UnsupportedWithWindowLayers
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import MELLUM_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - both_forms_at_toy_size is an autouse fixture
    Spy, both_forms_at_toy_size, check_the_step_kernel_is_not_taken, collect, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

CHUNK = 32  # the family's prefill chunk: LONGER than the window of 24
RING = 5  # ceil((24 + 4) / 8) + 1 pages of 8: 40 positions
ORIGINAL = TOY.rope_scaling_global.original_max_position_embeddings  # 64


def _holds(spy, prompt, out, params) -> float:
    got = spy.of_request(prompt, out, CHUNK)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    return float(np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max())


def test_chunked_prefill_then_decode_past_the_ring_s_wraps_and_the_original_context(standing):
    """A prompt of 50 (two chunks of 32, each LONGER than the window of 24, a
    padded tail) and 60 generated tokens: positions 50 .. 109 go through a
    ring of 40 past its second wrap at 80 and past the 64 positions YaRN's
    frequencies were made for.  Every generated position's logits (the ring
    read under the lower bound, the global read under its own rotation, the
    fresh tokens merged) against the reference's full forward of prompt +
    output; every chunk's too; the two chunk counters."""
    assert TOY.sliding_window < CHUNK == standing.engine.runtime.prefill_chunk
    prompt = FAMILY.prompt_of(50)
    served = standing.serve([(prompt, 60)])
    (out,), spy, engine, counters = served.outs, served.spy, served.engine, served.added
    assert len(out) == 60 and len(prompt) < ORIGINAL < len(prompt) + len(out)
    assert _holds(spy, prompt, out, engine.params) < LOGIT_TOL
    want = FAMILY.reference_logits(engine.params, TOY, prompt + out)
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == CHUNK])[: len(prompt)]
    assert np.abs(chunks - want[: len(prompt)]).max() < LOGIT_TOL
    assert counters["moe_assignments"] == 8 * 3 * (50 + 60) and counters["moe_experts_hit"] > 0
    assert counters["moe_assignments_absent"] == 0  # every expert is held
    # two chunks of 32 for 50 tokens: 14 positions of the second held no prompt token
    assert (counters["chunk_tokens"], counters["chunk_tokens_padding"]) == (64, 14)
    assert counters["decode_window_tokens_read"] == 6 * 24 * 64  # 6 window layers x min(len, W)
    assert counters["decode_global_tokens_read"] == 2 * sum(4 * n for n in range(50, 114, 4))
    assert counters["chunk_attn_pairs_window"] > 0 < counters["chunk_attn_pairs_global"]
    assert counters["moe_grouped_chunks"] == 2 and counters["moe_dense_chunks"] == 0
    gauges = served.counters
    assert (gauges["kv_pages_global_total"], gauges["kv_pages_window_total"]) == (32, 2 * RING)
    for name in ("chunk_tokens_total", "chunk_tokens_padding_total"):
        assert f"calfkit_engine_{name}" in served.metrics, name


def test_short_and_long_rows_mixed_in_one_batch(standing):
    """A short prompt (one chunk) and a long one (four chunks, past the
    original context before its first decode step) at once through two slots:
    the long row's chunks ride the short row's decode steps, each row is
    served as if it were alone, and every served token is the reference's.
    The short wave's chunk is three quarters padding, the long one's an
    eighth: the counters say so."""
    requests = [(FAMILY.prompt_of(9, seed=1), 40), (FAMILY.prompt_of(100, seed=2), 16)]
    together = standing.serve(requests, sequential=False)
    for (prompt, _), out in zip(requests, together.outs):
        want = FAMILY.reference_logits(together.params, TOY, prompt + out)
        served = want[len(prompt) - 1: len(prompt) - 1 + len(out)]
        assert [int(t) for t in np.argmax(served, -1)] == out
    added = together.added
    assert added["unified_dispatches"] >= 3 and added["prefill_absorbed_tokens"] >= 64
    # one chunk of 32 for 9 tokens, four for 100 (a bucket of 128): 23 + 28 positions of padding
    assert (added["chunk_tokens"], added["chunk_tokens_padding"]) == (32 + 128, 23 + 28)
    alone = [standing.serve([request]).outs[0] for request in requests]
    assert together.outs == alone


def test_the_programs_name_both_kinds_rope_and_attention_scopes():
    engine = InferenceEngine(TOY, FAMILY.runtime(attention_impl="xla"), seed=3, params=FAMILY.seeded())
    args, window, steps, sampled = engine._decode_args()
    text = jax.make_jaxpr(engine._decode_fn_paged(window // 8, steps, sampled))(
        *args, moe=engine._moe_zero).pretty_print(name_stack=True)
    for scope in ("decode_loop", "rope/window", "rope/global", "attention/window",
                  "attention/global", "mlp/moe", "kv_write"):
        assert scope in text, scope
    sk, sv = (jnp.zeros((8, 1, 2, 64, 8), jnp.float32) for _ in range(2))
    chunk = jax.make_jaxpr(engine._chunk_fn(CHUNK))(
        engine.params, sk, sv, jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(CHUNK),
        None, jnp.asarray([50]), engine._moe_zero).pretty_print(name_stack=True)
    for scope in ("chunk_loop", "rope/window", "rope/global", "attention/window",
                  "attention/global", "mlp/moe"):
        assert scope in chunk, scope


@pytest.mark.parametrize("option, kw", [
    ("speculative", dict(speculative=SpecConfig(k=2))),
    ("quantization", dict(quantization="int8")),
    ("long_context", dict(long_context=True)),
    ("kv_layout='dense'", dict(kv_layout="dense", chunked_prefill=False)),
    ("tp > 1", dict(tp=2)),
    ("dp > 1", dict(dp=2)),
])
def test_what_the_window_stack_refuses_it_refuses_for_this_model_too(option, kw):
    with pytest.raises(UnsupportedWithWindowLayers, match=option.split(" ")[0]):
        InferenceEngine(TOY, FAMILY.runtime(**kw), seed=3)


def _left_in(engine, requests, outs, new):
    """The architecture file's two readings of what ``requests`` left in ``engine``."""
    seqs = [p + o for (p, _), o in zip(requests, outs)]
    lens = np.asarray([len(s) for s in seqs])
    left = [ARCH._walk(engine.params, TOY, np.pad(seq, (0, 128 - len(seq))), len(seq), left=True)[1]
            for seq in seqs]
    sent = np.stack([np.asarray(l[0]) for l in left], axis=1).astype(np.int64)
    kept = [list(layer) for layer in zip(*[l[1] for l in left])]
    return ARCH._gate_mismatch(engine, sent), ARCH._keys_error(engine, TOY, kept, lens, new)


def _the_plain_rotation_on_the_global_layers(config):
    return replace(config, rope_scaling_global=None)


def _yarn_without_its_attention_factor(config):
    return replace(config, rope_scaling_global=replace(
        config.rope_scaling_global, attention_factor=1.0))


@pytest.mark.parametrize("fault, told_by", [
    (None, None),
    (_the_plain_rotation_on_the_global_layers, "global"),
    (_yarn_without_its_attention_factor, "global"),
    ("yarn on the window layers too", "window"),
])
def test_a_rotated_key_carries_its_law_into_the_pages(monkeypatch, fault, told_by):
    """What the served rows LEFT in the engine, two rows past the ring's wrap
    and the original context: as stated every layer's keys are the
    reference's; a wrong law on the global layers shows in THEIR pages (the
    first window layer's ring reads the same), YaRN where the plain law
    belongs in the first window layer's ring.  (Each fault is another
    program, and the expert counts are the engine's since its start: a build
    of its own.)"""
    from calfkit_tpu.inference import model as M

    if callable(fault):
        stack = M._window_stack
        monkeypatch.setattr(M, "_window_stack", lambda c, *a, **kw: stack(fault(c), *a, **kw))
    elif fault:
        frequencies = M.rope_frequencies
        monkeypatch.setattr(M, "rope_frequencies", lambda hd, theta, scaling=None:
                            frequencies(hd, theta, TOY.rope_scaling_global))
    requests = [(FAMILY.prompt_of(50, seed=1), 40), (FAMILY.prompt_of(70, seed=2), 40)]
    outs, engine, _ = FAMILY.serve((TOY, FAMILY.runtime()), requests, sequential=False, keep=True)
    gate, keys = _left_in(engine, requests, outs, 40)
    assert sorted(keys["slots"]) == [0, 1]
    # (the sequential block's FIRST gate reads what the first attention added: only a
    # fault of the window layers' own rotation can move it)
    assert told_by == "window" or gate["gate_mismatch"] == 0.0
    by_layer = np.asarray(keys["keys_error_by_layer"])  # [layer, (prefill-, decode-written)]
    kinds = np.asarray(TOY.layer_types)
    if told_by is None:
        assert keys["ring_error"] < 1e-5 and keys["keys_error_later"] < 1e-4
        assert gate["gate_mismatch_later"] == 0.0
    elif told_by == "global":
        assert keys["ring_error"] < 1e-5
        assert by_layer[kinds == "attention"].min() > 0.1, by_layer
        assert by_layer[:3].max() < 1e-4  # the window layers below the first global one
    else:
        assert keys["ring_error"] > 0.1, keys


def test_the_cell_s_agreement_holds_the_rotation_by_what_the_rows_leave(monkeypatch, capsys):
    """The configuration file's OWN rehearsal sizes through the harness's own
    comparison (``benchmarks.reference.agreement`` with the architecture
    file's ``forward_top2``): as stated every reading is (nearly) nothing;
    the plain rotation on the global layers FAILS ``keys_error_later`` and
    with it the check.  (The file's rehearsal sizes: builds of their own.)"""
    import dataclasses

    from benchmarks import manifest
    from benchmarks.reference import agreement
    from calfkit_tpu.inference import model as M
    monkeypatch.undo()  # the file's own rehearsal sizes and the dense form's own limit
    with open(manifest.os.path.join(manifest.os.path.dirname(manifest.__file__), "configs",
                                    "mellum2-12b-a2.5b-instruct.json")) as f:
        config = json.load(f)
    toy, rt = ARCH.model(config, True)
    stated, _ = ARCH.model(config, False)
    for name in ARCH._LIMITS:
        assert getattr(toy, name) == 0.0  # logged at toy widths
        assert getattr(stated, name) == config["agreement"][name] > 0
    assert toy.sliding_window < rt.prefill_chunk and stated.sliding_window < 2048
    assert toy.rope_scaling_global.original_max_position_embeddings == 8192 // 64
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=16,
                              ring_error_limit=1e-4, keys_error_later_limit=1e-3,
                              gate_mismatch_limit=1e-6, gate_mismatch_later_limit=1e-6)
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (40, 70, 130, 190)]

    def run(fault: bool):
        with pytest.MonkeyPatch.context() as patch:
            if fault:
                stack = M._window_stack
                patch.setattr(M, "_window_stack", lambda c, *a, **kw: stack(
                    replace(c, rope_scaling_global=None), *a, **kw))

            async def serve():
                engine = InferenceEngine(toy, replace(rt, compilation_cache=False), seed=3,
                                         params=FAMILY.seeded(toy, key=5))
                await engine.start()
                try:
                    return engine, list(await asyncio.gather(
                        *[collect(engine, p, 16) for p in prompts]))
                finally:
                    await engine.stop()

            return asyncio.run(serve())

    for fault, must in ((False, set()), (True, {"keys_error_later"})):
        engine, outs = run(fault)  # the engine stays alive: the check finds it by its tree
        capsys.readouterr()
        result = agreement(ARCH.forward_top2, engine.params, toy, prompts, outs, 0.25, 8)
        printed = capsys.readouterr()
        line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
        assert sorted(line["slots"]) == list(range(len(prompts)))
        over = set(line["over_their_limit"])
        # (a wrong rotation changes what the layers above read, so a later gate may flip too)
        assert must <= over <= must | {"gate_mismatch_later"}, line
        assert result["ok"] == (not over) and result["compared"] >= 8, result
        assert printed.err.count("(limit <= ") == 4
        del engine


def test_experts_held_whole_keep_the_dense_form_under_any_value(monkeypatch, standing):
    """The step kernel (PR 53) is for experts held by SHARE: these are held
    whole and hit whole, so on a TPU the engine takes it under no value of
    ``attention_impl``, and the module's engine ran none of its steps."""
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(
        standing.engine, monkeypatch, "tpu", ("auto", "pallas", "pallas_interpret", "xla"))
