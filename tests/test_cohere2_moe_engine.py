"""Window layers beside global ones through the ENGINE: chunked prefill then
decode through the ring of pages past two wraps of it, mixed decode and chunk
rows in one ragged dispatch, the page accounting by cache kind, the counters
and the refusals.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference.config import (
    SpecConfig,
    UnsupportedWithWindowLayers,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.paged import PagesByKind
from tests.arch_harness import WINDOW_MOE as FAMILY
from tests.arch_harness import (  # noqa: F401 - both_forms_at_toy_size is an autouse fixture
    Spy, both_forms_at_toy_size, check_the_step_kernel_is_not_taken,
    check_the_step_kernel_serves_what_xla_serves, collect, standing,
)

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

RING = 5  # ceil((24 + 4) / 8) + 1 pages of 8: 40 positions


def _holds(spy, prompt, out, params) -> float:
    got = spy.of_request(prompt, out, 16)
    want = FAMILY.reference_logits(params, TOY, prompt + out)
    return float(np.abs(got - want[len(prompt) - 1: len(prompt) - 1 + len(out)]).max())


def test_chunked_prefill_then_decode_through_the_ring_past_two_wraps(standing):
    """A prompt of 50 (four chunks of 16, a padded tail; two windows and more
    than the ring's 40 positions) and 60 generated tokens: positions 50 ..
    109 are written through a ring of 40, past its second wrap at 80.  Every
    generated position's logits (the ring read under the lower bound, the
    global read, the fresh tokens merged, the dense expert form) against the
    reference's full forward of prompt + output; every chunk's too."""
    prompt = FAMILY.prompt_of(50)
    served = standing.serve([(prompt, 60)])
    (out,), spy, engine, counters = served.outs, served.spy, served.engine, served.added
    assert len(out) == 60
    assert _holds(spy, prompt, out, engine.params) < LOGIT_TOL
    want = FAMILY.reference_logits(engine.params, TOY, prompt + out)
    chunks = np.concatenate([s[0] for s in spy.seen if s.shape[1] == 16])[: len(prompt)]
    assert np.abs(chunks - want[: len(prompt)]).max() < LOGIT_TOL
    # 8 layers x 3 experts a token x (50 prompt tokens + 59 decode steps run as 60)
    assert counters["moe_assignments"] + counters["moe_assignments_absent"] == 8 * 3 * (50 + 60)
    # pages the row wrote over: at landing 7 prompt pages less the ring's 5, then a page
    # every 8 tokens from page 7 (position 56) to page 13 (position 109)
    # (overlapped execution launches one dispatch more before the last block is seen:
    # 15 dispatches of 4 steps are needed, 16 run, the row frozen in the last)
    assert counters["decode_dispatches"] == 16
    assert counters["window_pages_given_back"] == (7 - RING) + 8
    assert counters["decode_global_tokens_read"] == 2 * sum(
        4 * n for n in range(50, 114, 4))  # 2 global layers x rows x len x steps
    assert counters["decode_window_tokens_read"] == 6 * 24 * 64  # 6 window layers x min(len, W)
    gauges = served.counters
    assert (gauges["kv_pages_global_in_use"], gauges["kv_pages_window_in_use"]) == (0, 0)
    assert (gauges["kv_pages_global_total"], gauges["kv_pages_window_total"]) == (32, 2 * RING)


def test_mixed_decode_and_chunk_rows_in_one_ragged_dispatch(monkeypatch, standing):
    """Two requests at once through two slots, the second's prompt longer:
    its five chunks ride the first's decode steps in ragged dispatches
    (``unified_dispatches``).  Every chunk's logits and every served token
    are the reference's, and each row is served as if it were alone.  (A wave
    of one row at a time is another runtime: a build of its own.)"""
    spy = Spy(monkeypatch)
    requests = [(FAMILY.prompt_of(21, seed=1), 30), (FAMILY.prompt_of(70, seed=2), 12)]
    outs, engine, counters = FAMILY.serve(
        (TOY, FAMILY.runtime(max_prefill_wave=1)), requests, sequential=False, keep=True)
    assert counters["unified_dispatches"] >= 4 and counters["prefill_absorbed_tokens"] >= 64
    chunks = [s[0] for s in spy.seen if s.shape[1] == 16]
    assert len(chunks) == 2 + 5
    for (prompt, _), out, mine in zip(requests, outs, (chunks[:2], chunks[2:])):
        want = FAMILY.reference_logits(engine.params, TOY, prompt + out)
        assert np.abs(np.concatenate(mine)[: len(prompt)] - want[: len(prompt)]).max() < LOGIT_TOL
        served = want[len(prompt) - 1: len(prompt) - 1 + len(out)]
        assert [int(t) for t in np.argmax(served, -1)] == out
    # the second row's decode steps beside the first's: by the chain of its tokens
    steps = [s for s in spy.seen if s.shape[1] == 1]
    prompt, out = requests[1][0], outs[1]
    want = FAMILY.reference_logits(engine.params, TOY, prompt + out)[len(prompt):]
    best = min(
        max(float(np.abs(steps[j + i][b, 0] - want[i]).max()) for i in range(len(out) - 1))
        for j in range(len(steps) - len(out) + 2) for b in range(2))
    assert best < LOGIT_TOL
    alone = [standing.serve([request]).outs[0] for request in requests]
    assert outs == alone


def test_a_row_of_four_windows_never_holds_more_than_its_ring(standing):
    """96 + 30 tokens under a window of 24: the row's window pages stay 5
    (its ring) at every token, its global pages are its whole footprint, and
    retirement returns both kinds."""
    seen = []

    def probe(engine):
        seen.append((dict(engine._page_alloc.held_slots), engine.stats.kv_pages_window_in_use,
                     engine._ledger.pages_in_use))

    served = standing.serve([(FAMILY.prompt_of(96), 30)], probe=probe)
    (out,), engine = served.outs, served.engine
    assert len(out) == 30 and seen
    for held, window_in_use, ledger in seen[:-1]:
        (n_global, n_window), = held.values()
        assert n_window == RING == window_in_use
        assert n_global == 16  # ceil((96 + 30 + 1) / 8): every token of the 2 global layers
        # ONE ledger, in pages of equal bytes (a layer's page): 2 global + 6 window layers
        assert ledger == 16 * 2 + RING * 6
    assert engine._page_alloc.held_slots == {} and engine._ledger.pages_in_use == 0
    assert engine._page_alloc.free_pages == engine._ledger.pages_total == 32 * 2 + 2 * RING * 6
    assert all(a.free_pages == a.num_pages - 1 for a in engine._page_alloc.by_kind)


def test_a_short_request_takes_a_shorter_ring(standing):
    held = []
    standing.serve([(FAMILY.prompt_of(9), 6)],
                   probe=lambda e: held.append(dict(e._page_alloc.held_slots)))
    assert set(held[0].values()) == {(2, 2)}  # 16 positions: two pages of each kind


def test_pages_by_kind_grants_both_kinds_or_neither():
    pages = PagesByKind(9, 7, (2, 6))
    assert pages.num_pages - 1 == 8 * 2 + 6 * 6 == pages.free_pages
    assert pages.fits((8, 6)) and not pages.fits((9, 1)) and not pages.fits((1, 7))
    first = pages.alloc(0, (5, 4))
    assert [len(p) for p in first] == [5, 4] and 0 not in first[0] + first[1]
    assert pages.alloc(1, (3, 3)) is None  # the window pool is short: nothing is taken
    assert pages.alloc(1, (4, 2)) is None  # the global pool is short
    assert pages.held_slots == {0: (5, 4)} and pages.free_pages == 3 * 2 + 2 * 6
    assert pages.alloc(1, (3, 2)) is not None
    pages.free(0)
    assert pages.held_slots == {1: (3, 2)}
    with pytest.raises(ValueError):
        pages.alloc(1, (1, 1))


@pytest.mark.parametrize("short", ["global", "window"])
def test_admission_waits_on_either_pool(short):
    """With one of the two pools nearly taken, a second request waits in the
    queue while the first lives (``alloc_stalls``, ``blocked_pages_s``),
    whichever pool it is that is short, and is served once the first's pages
    of BOTH kinds are back.  (It takes pages out of a pool by hand before
    the engine starts: an engine of its own.)"""
    async def run():
        engine = InferenceEngine(TOY, FAMILY.runtime(max_prefill_wave=1), seed=3, params=FAMILY.seeded())
        pool = engine._page_alloc.by_kind[0 if short == "global" else 1]
        # someone else holds most of one pool: the first request (7 global pages, a
        # ring of 5) fits beside them, the second (4 and 4) does not until it retires
        assert pool.alloc(99, 22 if short == "global" else 3) is not None
        await engine.start()
        try:
            first = asyncio.ensure_future(collect(engine, FAMILY.prompt_of(20, seed=1), 30))
            await asyncio.sleep(0)
            second = asyncio.ensure_future(collect(engine, FAMILY.prompt_of(20, seed=2), 5))
            outs = await asyncio.wait_for(asyncio.gather(first, second), 120)
            return outs, engine.stats.counters(), dict(engine._page_alloc.held_slots)
        finally:
            await engine.stop()

    outs, counters, held = asyncio.run(run())
    assert [len(o) for o in outs] == [30, 5]
    assert counters["alloc_stalls"] >= 1 and counters["blocked_pages_s"] > 0
    assert set(held) == {99}  # retirement returned both kinds


def test_a_request_no_pool_could_ever_serve_is_rejected():
    """(A pool of 9 pages is another runtime: a build of its own.)"""
    from calfkit_tpu.exceptions import InferenceError

    async def run():
        engine = InferenceEngine(TOY, FAMILY.runtime(num_kv_pages=9), seed=3, params=FAMILY.seeded())
        await engine.start()
        try:
            with pytest.raises(InferenceError, match="KV pages"):
                await collect(engine, FAMILY.prompt_of(70), 8)
            return await collect(engine, FAMILY.prompt_of(40), 8)
        finally:
            await engine.stop()

    assert len(asyncio.run(run())) == 8


def test_prefix_reuse_is_declined_and_counted_for_a_model_with_window_layers():
    from calfkit_tpu.observability.metrics import metrics_text

    prompt = FAMILY.prompt_of(40)
    outs, engine, counters = FAMILY.serve(  # the prefix cache on: another runtime, its own build
        (TOY, FAMILY.runtime(prefix_cache=True)),
        [(prompt, 4), (prompt, 4), (FAMILY.prompt_of(33, seed=4), 4)], keep=True)
    assert outs[0] == outs[1]
    assert counters["prefix_reuse_declined_window"] == 3 and counters["prefix_hits"] == 0
    assert engine._prefix is None  # nothing is ever registered
    text = metrics_text()
    for name in ("prefix_reuse_declined_window_total", "decode_window_tokens_read_total",
                 "decode_global_tokens_read_total", "window_pages_given_back_total",
                 "kv_pages_global_in_use", "kv_pages_window_in_use", "kv_pages_global_total",
                 "kv_pages_window_total"):
        assert f"calfkit_engine_{name}" in text, name


def test_the_dispatch_span_carries_the_two_page_counts(standing):
    from calfkit_tpu.observability.trace import TRACER

    TRACER.clear()
    was = TRACER.enabled
    TRACER.enabled = True
    try:
        standing.serve([(FAMILY.prompt_of(30), 9)])
        spans = [s for s in TRACER.finished() if s.name == "engine.dispatch"]
    finally:
        TRACER.enabled = was
        TRACER.clear()
    assert spans
    riding = [s.attrs for s in spans if s.attrs.get("rows")]
    assert riding and all(
        a["kv_pages_window_in_use"] == RING and a["kv_pages_global_in_use"] == 5 for a in riding)


@pytest.mark.parametrize("option, kw", [
    ("speculative", dict(speculative=SpecConfig(k=2))),
    ("quantization", dict(quantization="int8")),
    ("long_context", dict(long_context=True)),
    ("kv_layout='dense'", dict(kv_layout="dense", chunked_prefill=False)),
    ("tp > 1", dict(tp=2)),
    ("dp > 1", dict(dp=2)),
    ("decode_steps_per_dispatch", dict(decode_steps_per_dispatch=32)),
])
def test_what_knows_no_lower_bound_is_refused_at_construction(option, kw):
    with pytest.raises(UnsupportedWithWindowLayers, match=option.split(" ")[0]):
        InferenceEngine(TOY, FAMILY.runtime(**kw), seed=3)


def test_the_programs_name_both_kinds_attention_scopes():
    engine = InferenceEngine(TOY, FAMILY.runtime(attention_impl="xla"), seed=3, params=FAMILY.seeded())
    args, window, steps, sampled = engine._decode_args()
    text = jax.make_jaxpr(engine._decode_fn_paged(window // 8, steps, sampled))(
        *args, moe=engine._moe_zero).pretty_print(name_stack=True)
    for scope in ("decode_loop", "attention/window", "attention/global", "mlp/moe", "kv_write"):
        assert scope in text, scope
    sk, sv = (jnp.zeros((8, 1, 2, 32, 8), jnp.float32) for _ in range(2))
    chunk = jax.make_jaxpr(engine._chunk_fn(16))(
        engine.params, sk, sv, jnp.zeros((1, 16), jnp.int32), jnp.int32(16),
        None, jnp.asarray([30]), engine._moe_zero).pretty_print(name_stack=True)
    for scope in ("chunk_loop", "attention/window", "attention/global", "mlp/moe"):
        assert scope in chunk, scope


def test_the_pools_come_by_cache_kind(standing):
    engine = standing.engine
    (kg, kw), (vg, vw) = engine._k, engine._v
    assert kg.shape == vg.shape == (2, 33, 2, 8, 8)  # the 2 global layers, num_kv_pages
    assert kw.shape == vw.shape == (6, 2 * RING + 1, 2, 8, 8)  # 6 window layers, every slot's ring
    tg, tw = engine._tables
    assert tg.shape == (2, 16) and tw.shape == (2, RING)
    assert preset("debug").windowed is False


def _left_in(engine, requests, outs, new):
    """The architecture file's two readings of what ``requests`` left in ``engine``."""
    seqs = [p + o for (p, _), o in zip(requests, outs)]
    lens = np.asarray([len(s) for s in seqs])
    left = [ARCH._walk(engine.params, TOY, np.pad(seq, (0, 128 - len(seq))), len(seq), left=True)[1]
            for seq in seqs]
    sent = np.stack([np.asarray(l[0]) for l in left], axis=1).astype(np.int64)
    kept = [list(layer) for layer in zip(*[l[1] for l in left])]
    return ARCH._gate_mismatch(engine, sent), ARCH._keys_error(engine, TOY, kept, lens, new)


@pytest.mark.parametrize("narrow", [False, True])
def test_what_the_served_rows_leave_in_the_engine(monkeypatch, narrow):
    """The readings the architecture file takes from the engine that served
    the agreement's rows: every layer's keys in the rows' pages against the
    reference's (a window layer's RING: the last W positions, each in the
    entry its position names, two rows past the ring's wrap; the global
    layers' pages: every position), and every layer's tokens to each held
    expert.  Keys kept in a narrower type than stated show in the first and
    nowhere in the second.  (The expert counts are the engine's since its
    start, and the narrower keys another program: a build of its own.)"""
    from calfkit_tpu.inference import model as M

    requests = [(FAMILY.prompt_of(50, seed=1), 32), (FAMILY.prompt_of(21, seed=2), 60)]
    if narrow:
        qkv = M._window_qkv

        def rounded(h, lp, cos, sin):
            q, k, v = qkv(h, lp, cos, sin)
            return q, k.astype(jnp.bfloat16).astype(k.dtype), v
        monkeypatch.setattr(M, "_window_qkv", rounded)
    outs, engine, _ = FAMILY.serve((TOY, FAMILY.runtime()), requests, sequential=False, keep=True)
    gate, keys = _left_in(engine, requests, outs, 32)
    assert len(keys["ring_error_by_row"]) == 2 and sorted(keys["slots"]) == [0, 1]
    assert len(keys["keys_error_by_layer"]) == TOY.n_layers
    assert (keys["ring_error"] > 1e-3) if narrow else (keys["ring_error"] < 1e-5)
    assert (keys["keys_error_later"] > 1e-3) if narrow else (keys["keys_error_later"] < 1e-4)
    assert gate["gate_mismatch"] == 0.0 and (narrow or gate["gate_mismatch_later"] == 0.0)
    assert engine.window_ring(0).shape == (2, 2, 40, 8)
    assert engine.global_keys(0, 1).shape == (2, 128, 8)
    plain = InferenceEngine(preset("debug"), FAMILY.runtime(), seed=1)
    assert plain.window_ring() is None and plain.global_keys(0) is None


def _no_lower_bound_in_the_decode_kernel(monkeypatch):
    """The decode read of a window layer walks the whole ring, mask and all
    (the prefill keeps its lower bound)."""
    from calfkit_tpu.inference import model as M

    monkeypatch.setattr(
        M, "_window_ring_valid", lambda T, base, q_pos, W: jnp.arange(T)[None, :] < base[:, None])


def _rotary_on_the_global_layers(monkeypatch):
    from calfkit_tpu.inference import model as M

    stack = M._window_stack

    def rotated(config, *a, **kw):
        return stack(replace(config, position_embedding="rope"), *a, **kw)
    monkeypatch.setattr(M, "_window_stack", rotated)


@pytest.mark.parametrize("fault, told_by", [
    (_no_lower_bound_in_the_decode_kernel, "decode-written"),
    (_rotary_on_the_global_layers, "global"),
])
def test_a_later_layer_s_keys_tell_what_the_layers_below_added(monkeypatch, fault, told_by):
    """What the first layer's keys cannot: a window layer's decode read
    without its lower bound changes what that layer adds to the stream at the
    positions a decode step wrote, and with it the keys of every layer above;
    a rotation on the global layers shows in their own pages.  The first
    layer's ring and the first layer's gate read the same either way.  (Each
    fault is another program: a build of its own.)"""
    fault(monkeypatch)
    requests = [(FAMILY.prompt_of(50, seed=1), 24), (FAMILY.prompt_of(70, seed=2), 24)]
    outs, engine, _ = FAMILY.serve(
        (TOY, FAMILY.runtime()), requests, sequential=False, keep=True)
    gate, keys = _left_in(engine, requests, outs, 24)
    assert keys["ring_error"] < 1e-5 and gate["gate_mismatch"] == 0.0
    assert keys["keys_error_later"] > 1e-2, keys
    by_layer = np.asarray(keys["keys_error_by_layer"])  # [layer, (prefill-, decode-written)]
    if told_by == "decode-written":
        assert by_layer[:, 0].max() < 1e-4 < 1e-2 < by_layer[1:3, 1].min(), by_layer
    else:
        kinds = np.asarray(TOY.layer_types)
        assert by_layer[kinds == "attention"].min() > 0.1 > 1e-4 > by_layer[:3].max(), by_layer


def test_a_retired_row_stands_while_other_slots_and_pages_are_free():
    """Slots and pages of both kinds are granted oldest-first: four rows
    served one after the other through four slots leave four rings and four
    tables standing, so a check that reads them back afterwards finds every
    row (LIFO grants would have served all four in one slot's pages).  (Four
    slots: another runtime, a build of its own.)"""
    requests = [(FAMILY.prompt_of(30 + 9 * i, seed=i), 8) for i in range(4)]
    outs, engine, _ = FAMILY.serve(
        (TOY, FAMILY.runtime(max_batch_size=4)), requests, sequential=True, keep=True)
    _, keys = _left_in(engine, requests, outs, 8)
    assert sorted(keys["slots"]) == [0, 1, 2, 3]
    assert keys["ring_error"] < 1e-5 and keys["keys_error_later"] < 1e-4
    tables = [np.asarray(t) for t in engine._tables]
    for kind in (0, 1):
        held = tables[kind][tables[kind] > 0]
        assert len(set(held.tolist())) == len(held)  # no page in two rows' tables


@pytest.mark.parametrize("lane", [
    dict(chunked_prefill=False), dict(overlap_dispatch=False), dict(ragged_waves=False),
])
def test_every_lane_serves_the_tokens_the_ragged_lane_serves(standing, lane):
    """The one-shot prefill (the whole bucket's queries at once), the
    lockstep tick and the legacy bifurcated schedule write and read the same
    rings: the tokens are the ragged lane's.  (Each lane is a build of its own.)"""
    prompt = FAMILY.prompt_of(50)
    (want,) = standing.serve([(prompt, 40)]).outs
    (got,), _, _ = FAMILY.serve((TOY, FAMILY.runtime(**lane)), [(prompt, 40)])
    assert got == want and len(got) == 40


@pytest.mark.parametrize("fault", ["none", "keys_in_bfloat16", "gate_in_8_bits"])
def test_the_cell_s_agreement_holds_what_the_rows_leave_in_the_engine(monkeypatch, capsys, fault):
    """The configuration file's OWN rehearsal sizes through the harness's own
    comparison (``benchmarks.reference.agreement`` with the architecture
    file's ``forward_top2``): the file finds the engine by the tree it serves
    and holds the rings' keys and the first layer's expert counts to the
    reference's.  As stated both read (nearly) nothing; keys kept in a
    narrower type, and a gate taken in a lower precision, each FAILS its own limit
    and with it the check, whatever the served tokens say.  (The file's
    rehearsal sizes, and each fault another program: builds of its own.)"""
    import dataclasses
    import json

    from benchmarks import manifest
    from benchmarks.reference import agreement
    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference import moe
    monkeypatch.undo()  # the file's own rehearsal sizes and the dense form's own limit
    with open(manifest.os.path.join(manifest.os.path.dirname(manifest.__file__), "configs",
                                    "command-a-plus-05-2026.json")) as f:
        config = json.load(f)
    toy, rt = ARCH.model(config, True)
    stated, _ = ARCH.model(config, False)
    for name in ARCH._LIMITS:
        assert getattr(toy, name) == 0.0  # logged at toy widths
        assert getattr(stated, name) == config["agreement"][name] > 0
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=16,
                              ring_error_limit=1e-4, keys_error_later_limit=1e-3,
                              gate_mismatch_limit=1e-6, gate_mismatch_later_limit=1e-6)
    if fault == "keys_in_bfloat16":
        qkv = M._window_qkv

        def rounded(h, lp, cos, sin):
            q, k, v = qkv(h, lp, cos, sin)
            return q, k.astype(jnp.bfloat16).astype(k.dtype), v
        monkeypatch.setattr(M, "_window_qkv", rounded)
    if fault == "gate_in_8_bits":
        route = moe.route
        # (at toy widths, a few hundred assignments: the gate's weights rounded to 8 bits,
        # so that some choice among them flips; bfloat16 flips one in a thousand)
        monkeypatch.setattr(moe, "route", lambda h, lp, c: route(
            h.astype(jnp.bfloat16).astype(jnp.float32),
            {**lp, "router": lp["router"].astype(jnp.float8_e4m3fn).astype(jnp.float32)}, c))
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (40, 70, 100, 190)]

    async def run():
        engine = InferenceEngine(toy, replace(rt, compilation_cache=False), seed=3,
                                 params=FAMILY.seeded(toy, key=5))
        await engine.start()
        try:
            return engine, list(await asyncio.gather(*[collect(engine, p, 16) for p in prompts]))
        finally:
            await engine.stop()

    engine, outs = asyncio.run(run())  # the engine stays alive: the check finds it by its tree
    assert engine.window_ring(0).shape[:3] == (rt.max_batch_size, 2, 6 * rt.page_size)
    capsys.readouterr()
    result = agreement(ARCH.forward_top2, engine.params, toy, prompts, outs, 0.25, 8)
    printed = capsys.readouterr()
    line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
    assert sorted(line["slots"]) == list(range(len(prompts)))  # every row stands in a slot of its own
    # (narrower keys change what the layers above read, so a later gate may flip as well)
    must, may = {"none": (set(), set()),
                 "keys_in_bfloat16": ({"ring_error", "keys_error_later"}, {"gate_mismatch_later"}),
                 # (and another expert's output changes the stream the layers above key)
                 "gate_in_8_bits": ({"gate_mismatch", "gate_mismatch_later"},
                                    {"keys_error_later"})}[fault]
    over = line["over_their_limit"]
    assert must <= set(over) <= must | may, line
    assert result["ok"] == (not over) and result["compared"] >= 8, result
    assert printed.err.count("FAIL") == len(over) and printed.err.count("(limit <= ") == 4


def test_a_decode_step_s_experts_through_the_step_kernel(monkeypatch, standing):
    """Experts held by share (4 of 8 scored) of one lane tile a side, heads of
    64 on pages of 16 (inside the decode read's rule too): the step kernel in
    interpret mode serves what XLA serves.  Under "auto" on this CPU the
    module's engine ran none of its steps.  (Another configuration under two
    implementations: builds of its own.)"""
    wide = replace(TOY, d_model=128, moe_d_ff=128, attn_head_dim=64)
    check_the_step_kernel_serves_what_xla_serves(FAMILY, wide, monkeypatch, page_size=16)
    standing.serve([(FAMILY.prompt_of(20), 5)])
    check_the_step_kernel_is_not_taken(standing.engine, monkeypatch, "cpu", ("auto",))
