"""The chunk attention kernel (``pallas_attention.chunk_attention_pallas``) in
interpret mode on the CPU, against the two XLA forms of the same law:
``model.blocked_attention`` (a key block at a time with the running maximum:
what the kernel replaces on a chip) and ``model.attention_xla`` (one pass over
the row under the same mask).

float32 operands at toy widths, so that the sides differ in the ORDER of sums
and in nothing else; tiles of 8 queries and key blocks of 16, so that every
case below has tiles on both edges of the walk.  What interpret mode cannot
see (block shapes, VMEM) is compiled for the described v5e in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import pallas_attention as PA

TILE, BLOCK, P, HD = 8, 16, 128, 32
WINDOW = 24
TOL = 5e-6


def _case(seed, B, S, K, G, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((B, S, K * G, HD)), dtype),
            jnp.asarray(rng.standard_normal((B, K, P, HD)), dtype),
            jnp.asarray(rng.standard_normal((B, K, P, HD)), dtype))


def _one_pass(q, k, v, q_pos, lens, window):
    """``attention_xla``'s one-pass form under the window's mask: the scores
    of the whole row at once, one softmax."""
    B, S, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bskgh,bkwh->bkgsw", qg, k, preferred_element_type=jnp.float32) / np.sqrt(hd)
    kv = jnp.arange(k.shape[2])[None, None, :]
    valid = (kv <= q_pos[:, :, None]) & (kv < lens[:, None, None])
    if window:
        valid = valid & (kv > q_pos[:, :, None] - window)
    p = jax.nn.softmax(jnp.where(valid[:, None, None], s, -1e30), axis=-1)
    out = jnp.einsum("bkgsw,bkwh->bskgh", p, v, preferred_element_type=jnp.float32)
    return out.reshape(B, S, H, hd)


def _kernel(q, k, v, starts, lens, window, **kw):
    return PA.chunk_attention_pallas(
        q, k, v, jnp.asarray(starts, jnp.int32), jnp.asarray(lens, jnp.int32), window=window,
        interpret=True, tile=TILE, block=BLOCK, **kw)


# offsets: 0; inside the first window; past it; the lower bound (offset - 24 + 1) on a key
# block's edge (offset 39 -> 16) and mid-block (offset 48 -> 25)
@pytest.mark.parametrize("offset", [0, 8, 32, 39, 48])
@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("G", [1, 4, 16])
def test_the_kernel_is_blocked_attention_and_the_one_pass_form(offset, window, G):
    S = 32  # four query tiles
    q, k, v = _case(offset * 7 + G, 2, S, 2, G)
    starts = np.array([offset, offset], np.int32)
    lens = starts + S
    q_pos = jnp.asarray(starts[:, None] + np.arange(S)[None, :], jnp.int32)
    got = _kernel(q, k, v, starts, lens, window)
    blocked = M.blocked_attention(q, k, v, q_pos, jnp.asarray(lens), window=window, block=BLOCK)
    assert float(jnp.abs(got - blocked).max()) < TOL
    assert float(jnp.abs(got - _one_pass(q, k, v, q_pos, jnp.asarray(lens), window)).max()) < TOL


@pytest.mark.parametrize("S", [TILE, 4 * TILE])  # a chunk of one query tile, and of several
@pytest.mark.parametrize("window", [0, WINDOW])
def test_rows_at_offsets_of_their_own_and_shorter_than_the_chunk(S, window):
    """``seq_lens`` cuts keys: row 0 ends inside the chunk (its later queries
    see what the row holds, as the loop has it), row 1 holds no key at all:
    every query of it is fully masked and stays finite (0, as the loop gives)."""
    q, k, v = _case(S + window, 3, S, 2, 4)
    starts = np.array([40, 16, 56], np.int32)
    lens = np.array([40 + S // 2, 0, 56 + S], np.int32)
    q_pos = jnp.asarray(starts[:, None] + np.arange(S)[None, :], jnp.int32)
    got = _kernel(q, k, v, starts, lens, window)
    blocked = M.blocked_attention(q, k, v, q_pos, jnp.asarray(lens), window=window, block=BLOCK)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got[1]).max()) == 0.0 == float(jnp.abs(blocked[1]).max())
    assert float(jnp.abs(got - blocked).max()) < TOL


def test_the_roundings_are_the_cache_types():
    """bfloat16 operands: ``p`` is rounded to the cache's type before the PV
    product and ``z`` summed from the rounded ``p``, as the loop does, so the
    two agree to bfloat16's last place and not merely to its tolerance."""
    S = 32
    q, k, v = _case(11, 1, S, 2, 4, jnp.bfloat16)
    starts, lens = np.array([64], np.int32), np.array([96], np.int32)
    q_pos = jnp.asarray(starts[:, None] + np.arange(S)[None, :], jnp.int32)
    got = _kernel(q, k, v, starts, lens, WINDOW)
    blocked = M.blocked_attention(q, k, v, q_pos, jnp.asarray(lens), window=WINDOW, block=BLOCK)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - blocked.astype(jnp.float32)).max()) <= 2 ** -7


@pytest.mark.parametrize("window", [0, WINDOW])
def test_the_bounds_are_walked_not_only_masked(window):
    """The blocks-visited arithmetic for every tile is what the kernel's
    scalar tables hold, a tile's table is what its first and last query need
    and no more, and a key block outside the tables filled with NaN changes
    nothing: it is never copied, where a mask alone would multiply it by 0."""
    S, offset = 32, 48
    q, k, v = _case(5, 1, S, 2, 4)
    starts, lens = np.array([offset], np.int32), np.array([offset + S], np.int32)
    first, count = PA.chunk_attention_bounds(
        starts, lens, chunk=S, scratch=P, window=window, tile=TILE, block=BLOCK, xp=np)
    traced = PA.chunk_attention_bounds(
        jnp.asarray(starts), jnp.asarray(lens), chunk=S, scratch=P, window=window, tile=TILE,
        block=BLOCK)
    assert np.array_equal(first, np.asarray(traced[0])) and np.array_equal(count, np.asarray(traced[1]))
    for t in range(S // TILE):
        q0, q1 = offset + t * TILE, offset + (t + 1) * TILE - 1
        lo = max(q0 - window + 1, 0) // BLOCK if window else 0
        assert (first[0, t], count[0, t]) == (lo, q1 // BLOCK - lo + 1)
    assert count.max() <= PA.chunk_attention_key_steps(S, P, window, TILE, BLOCK)
    # the host's count of the same walk, at the module's own tiles (two tiles of 128
    # queries against key blocks of 1,024), beside what the loop walks for the chunk
    tile, block = PA.chunk_attention_tiles(256, 8192)
    at, w = np.array([4000], np.int32), 1200 if window else 0
    lo, n = PA.chunk_attention_bounds(
        at, at + 256, chunk=256, scratch=8192, window=w, tile=tile, block=block, xp=np)
    layers = (1, 0) if window else (0, 1)  # one layer of the kind under test
    _, _, visited, dense = PA.chunk_attention_work(4000, 256, 8192, at + 256, 1200, *layers)
    # keys 2,801 .. 4,127 then 2,929 .. 4,255: blocks 2-4, 2-4; without a window 0-4, 0-4
    assert (tile, block) == (128, 1024)
    assert visited == int(n.sum()) == (3 + 3 if window else 5 + 5)
    assert dense == 2 * (5 - int(lo.min())) >= visited
    # every block no tile's table names, in K and in V
    walked = {int(first[0, t]) + j for t in range(S // TILE) for j in range(int(count[0, t]))}
    outside = np.array([b not in walked for b in range(P // BLOCK)]).repeat(BLOCK)
    assert outside.any()
    poison = jnp.where(jnp.asarray(outside)[None, None, :, None], jnp.nan, 1.0)
    clean = _kernel(q, k, v, starts, lens, window)
    assert bool(jnp.array_equal(clean, _kernel(q, k * poison, v * poison, starts, lens, window)))
    assert bool(jnp.isfinite(clean).all())


def test_a_shape_outside_the_rule_is_refused_not_served_by_another_path():
    q, k, v = _case(2, 1, 32, 2, 4)
    with pytest.raises(PA.PallasShapeError, match="chunk_attention_ok"):
        PA.chunk_attention_pallas(q, k, v, jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32))
    with pytest.raises(PA.PallasShapeError, match="tiles of 5"):
        PA.chunk_attention_pallas(
            q, k, v, jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32),
            interpret=True, tile=5, block=BLOCK)
    # the rule itself: a head of whole lane tiles, a chunk of whole sublane tiles of the
    # dtype, a scratch of whole lane tiles
    assert PA.chunk_attention_ok(128, 2048, 18432, jnp.bfloat16)
    assert PA.chunk_attention_tiles(2048, 18432) == (
        PA.CHUNK_ATTN_QUERY_TILE, PA.CHUNK_ATTN_KEY_BLOCK)
    assert PA.chunk_attention_ok(128, 128, 384, jnp.float32)
    assert PA.chunk_attention_tiles(128, 384) == (128, 128)
    assert not PA.chunk_attention_ok(64, 2048, 18432, jnp.bfloat16)
    assert not PA.chunk_attention_ok(128, 2048 + 8, 18432, jnp.bfloat16)
    assert not PA.chunk_attention_ok(128, 2048, 18432 + 64, jnp.bfloat16)


@pytest.mark.parametrize("offset, true_lens, chunk, window", [
    (0, [20, 32, 50], 32, 24),  # the first chunk: rows ending inside it, at its end, past it
    (32, [40, 64, 70], 32, 24),  # a later chunk across the window's edge; a row that ended before
    (64, [20, 96], 32, 24),  # a row with no position of its own in the chunk
])
def test_the_pairs_counted_are_a_brute_force_count_over_the_mask(offset, true_lens, chunk, window):
    pairs_w = pairs_g = 0
    for n in true_lens:
        for qp in range(offset, min(offset + chunk, n)):
            keys = np.arange(P)
            pairs_g += int((keys <= qp).sum())
            pairs_w += int(((keys <= qp) & (keys > qp - window)).sum())
    got = PA.chunk_attention_work(offset, chunk, P, np.array(true_lens), window, 3, 2)
    assert got[:2] == (3 * pairs_w, 2 * pairs_g)
    assert 0 < got[2] <= got[3]


# --------------------------------------------------------------------------- #
# through a live engine of the cell's rehearsal sizes
# --------------------------------------------------------------------------- #

def _rehearsal_engine_args(attention_impl):
    """The command-a-plus cell's ``rehearsal`` model and runtime, with a head of
    one whole lane tile and a chunk of one query tile (as ``chip_smoke.py
    --rehearse`` widens its preset: the toy's heads of 16 and chunks of 32 are
    outside both kernels' rules), float32 so that the two implementations'
    tokens can be held equal."""
    from dataclasses import replace

    from benchmarks import manifest

    cell = manifest.resolve_cell(manifest.load_manifest(), "command-a-plus-05-2026.longdoc-closed")
    config = cell.config
    arch = manifest.load_architecture(config["architecture"])
    model, rt = arch.model(config, True)
    model = replace(model, attn_head_dim=128, dtype="float32")
    rt = replace(rt, prefill_chunk=128, attention_impl=attention_impl, max_batch_size=2)
    return model, rt


def _serve(attention_impl, requests):
    from tests.arch_harness import WINDOW_MOE  # the architecture file's seeded tree, one engine

    return WINDOW_MOE.serve(_rehearsal_engine_args(attention_impl), requests, keep=True)


def _requests():
    rng = np.random.default_rng(0)
    return [([int(t) for t in rng.integers(3, 500, n)], 12) for n in (200, 300)]


@pytest.fixture(scope="module")
def under_xla():
    """The two requests served once under ``"xla"`` for both tests below: what came back,
    the engine, its counters, the kernels traced meanwhile and the metrics text."""
    from types import SimpleNamespace

    from calfkit_tpu.observability.metrics import metrics_text

    PA.chunk_attention_pallas.clear_cache()
    PA.KERNEL_TRACES.clear()
    outs, engine, counters = _serve("xla", _requests())
    return SimpleNamespace(outs=outs, engine=engine, counters=counters,
                           traces=dict(PA.KERNEL_TRACES), metrics=metrics_text())


def test_a_live_engine_serves_the_same_tokens_under_the_kernel_and_under_xla(under_xla):
    """Prompts of 200 and 300 tokens (two and three chunks of 128 against a
    window of 64: the later chunks start past the window) and 12 tokens each:
    ``attention_impl="pallas_interpret"`` resolves the chunks to the kernel
    and serves what ``"xla"`` serves, and ``"xla"`` builds no kernel at all;
    on a CPU ``"auto"`` is ``"xla"`` (resolved at construction: nothing is served)."""
    from calfkit_tpu.inference.engine import CHUNK_ATTN_FIELDS, InferenceEngine

    requests = _requests()
    want, engine, counters = under_xla.outs, under_xla.engine, under_xla.counters
    assert engine._chunk_attn_impl == "xla" and not under_xla.traces
    assert all(len(out) == 12 for out in want)
    got, engine, counters_k = _serve("pallas_interpret", requests)
    assert engine._chunk_attn_impl == engine._attn_impl == "pallas_interpret"
    assert PA.KERNEL_TRACES["chunk_attention", "interpreted"] >= 2  # with and without a window
    assert ("chunk_attention", "compiled") not in PA.KERNEL_TRACES
    assert got == want
    assert InferenceEngine(*_rehearsal_engine_args("auto"))._chunk_attn_impl == "xla"
    # the counters are the same arithmetic whatever computes the chunks: 2 window layers
    # a period of W W W G cut to 4 layers -> 3 window layers and 1 global layer
    W = engine.config.sliding_window
    pairs_w = sum(min(q + 1, W) for n in (200, 300) for q in range(n))
    pairs_g = sum(q + 1 for n in (200, 300) for q in range(n))
    for c in (counters, counters_k):
        assert c["chunk_attn_pairs_window"] == engine.config.n_window_layers * pairs_w
        assert c["chunk_attn_pairs_global"] == engine.config.n_global_layers * pairs_g
        assert 0 < c["chunk_attn_key_blocks_visited"] <= c["chunk_attn_key_blocks_dense"]
    assert set(CHUNK_ATTN_FIELDS) <= set(counters)


def test_the_four_counters_reach_metrics_and_the_profile(monkeypatch, under_xla):
    from calfkit_tpu.inference import engine as E
    from calfkit_tpu.observability import devtrace

    counters, text = under_xla.counters, under_xla.metrics
    for field in E.CHUNK_ATTN_FIELDS:
        assert counters[field] > 0
        assert f"calfkit_engine_{field}_total" in text, field
    assert all(f in E._LOCAL_FIELDS for f in E.CHUNK_ATTN_FIELDS)  # never on the advert's window
    # GET /profile is devtrace.capture: what the counters grew by over its window
    grown = dict.fromkeys(E.CHUNK_ATTN_FIELDS, 0)

    def totals():
        out = dict(grown)
        grown["chunk_attn_pairs_window"] += 7
        return out

    monkeypatch.setattr(E, "chunk_attention_of_all_engines", totals)
    result = devtrace.capture(0.05)
    assert result["captured"]
    assert result["chunk_attention"] == {
        **dict.fromkeys(E.CHUNK_ATTN_FIELDS, 0), "chunk_attn_pairs_window": 7}
    monkeypatch.undo()
    assert set(E.chunk_attention_of_all_engines()) == set(E.CHUNK_ATTN_FIELDS)  # the live sum
