"""The dispatch's paged KV write (``model.consolidate_ring_paged``): per-row
window updates in place, held to the advanced-index scatter it replaced in
PR 46, which lives on HERE as the reference.  The pool after the write is
bit-identical to the scatter's everywhere but page 0 (the trash page), for a
plain pool, for pools by cache kind with a window ring that wraps, and for
the two sides of a latent pool; at a page's start, at its last position,
at every split of a straddle, past the table's end, and with inactive rows
whose stale table names a live neighbour's pages.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from calfkit_tpu.inference import model as M

PAGE = 8
PMAX = 4  # table entries a row (global); a window layer's ring holds RING
RING = 3
LAYERS = 3
LAYER_KINDS = ((0,), (1, 2))  # pools by kind: one global layer, two window layers


def _scatter(pool_side, r, page_ids, offsets):
    """PR 45's ``model._write_tokens``, to the letter."""
    vals = jnp.transpose(r, (2, 1, 0, 3, 4)).astype(pool_side.dtype)
    return pool_side.at[:, page_ids, :, offsets].set(vals)


def _reference(pool, ring, tables, base_lens, active, layer_kinds=None):
    """PR 45's ``consolidate_ring_paged`` and ``_consolidate_by_kind``."""
    if isinstance(tables, tuple):
        (kg, kw), (vg, vw) = pool
        tg, tw = tables
        gl, wl = (jnp.asarray(ids, jnp.int32) for ids in layer_kinds)
        rk, rv = ring
        kg, vg = _reference((kg, vg), (rk[gl], rv[gl]), tg, base_lens, active)
        T, page, R = rk.shape[1], kw.shape[3], tw.shape[1]
        pos = base_lens[:, None] + jnp.arange(T)[None, :]
        page_ids = jnp.take_along_axis(tw, (pos // page) % R, axis=1)
        page_ids = jnp.where(active[:, None], page_ids, 0)
        offsets = pos % page
        return ((kg, _scatter(kw, rk[wl], page_ids, offsets)),
                (vg, _scatter(vw, rv[wl], page_ids, offsets)))
    T, page = ring[0].shape[1], pool[0].shape[3]
    pos = base_lens[:, None] + jnp.arange(T)[None, :]
    logical = pos // page
    pmax = tables.shape[1]
    page_ids = jnp.take_along_axis(tables, jnp.minimum(logical, pmax - 1), axis=1)
    page_ids = jnp.where(active[:, None] & (logical < pmax), page_ids, 0)
    offsets = pos % page
    return (_scatter(pool[0], ring[0], page_ids, offsets),
            _scatter(pool[1], ring[1], page_ids, offsets))


def _sides(rng, lead, widths, dtype=jnp.bfloat16):
    return tuple(jnp.asarray(rng.standard_normal((*lead, w)), dtype) for w in widths)


def _tables(rng, rows, entries, pages):
    """Distinct pages a row from 1.. (page 0 is the trash page)."""
    ids = rng.permutation(np.arange(1, pages))[: rows * entries]
    return jnp.asarray(ids.reshape(rows, entries), jnp.int32)


# kind -> (KV heads, the two sides' widths)
KINDS = {"plain": (2, (16, 16)), "by_kind": (2, (16, 16)), "latent": (1, (32, 8))}


def _case(kind, T, lens, active, stale=None, seed=0):
    """(pool, ring, tables, base_lens, active, layer_kinds) with every row's
    table distinct; ``stale`` = (row, neighbour): the row's table is the
    neighbour's (a retired slot whose pages went to a new request)."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    K, widths = KINDS[kind]
    pages = 1 + B * PMAX
    ring = _sides(rng, (LAYERS, T, B, K), widths)
    tables = _tables(rng, B, PMAX, pages)
    if stale is not None:
        tables = tables.at[stale[0]].set(tables[stale[1]])
    lens, active = jnp.asarray(lens, jnp.int32), jnp.asarray(active, bool)
    if kind != "by_kind":
        return _sides(rng, (LAYERS, pages, K, PAGE), widths), ring, tables, lens, active, None
    glob, win = (len(ids) for ids in LAYER_KINDS)
    wpages = 1 + B * RING
    kg, vg = _sides(rng, (glob, pages, K, PAGE), widths)
    kw, vw = _sides(rng, (win, wpages, K, PAGE), widths)
    ring_tables = _tables(rng, B, RING, wpages)
    if stale is not None:
        ring_tables = ring_tables.at[stale[0]].set(ring_tables[stale[1]])
    return ((kg, kw), (vg, vw)), ring, (tables, ring_tables), lens, active, LAYER_KINDS


def _bits(tree):
    return [np.asarray(a).view(np.uint16) for a in jax.tree.leaves(tree)]


def _held_to_the_scatter(case):
    pool = case[0]
    got = jax.jit(M.consolidate_ring_paged, static_argnums=5)(*case)
    want = _reference(*case)
    for before, g, w in zip(_bits(pool), _bits(got), _bits(want)):
        assert g.shape == w.shape
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])  # all but the trash page
    return _bits(pool), _bits(got)


# a row's length at the start of the dispatch, by what it exercises on pages of 8
LENGTHS = {
    "page_start": PAGE,
    "page_last": 2 * PAGE - 1,
    "mid": PAGE + 3,
    "table_last": PMAX * PAGE - 1,  # runs over the table's end: in_range
    "past_table": PMAX * PAGE + 2,  # every position out of range
    "ring_wraps": RING * PAGE - 2,  # a window ring's last entry into its first
}


@pytest.mark.parametrize("T", [1, 4, 8])
@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("where", sorted(LENGTHS))
def test_window_write_is_the_scatter_outside_the_trash_page(kind, T, where):
    lens = [LENGTHS[where], 0, LENGTHS[where] + 1, 5]
    _held_to_the_scatter(_case(kind, T, lens, [True, True, True, False]))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("T,split", [(T, split) for T in (4, 8) for split in range(1, T)])
def test_straddle_at_every_split(kind, T, split):
    """``split`` tokens in the row's page, the rest in the next."""
    lens = [2 * PAGE - split, PAGE - split, RING * PAGE - split]
    _held_to_the_scatter(_case(kind, T, lens, [True, True, True], seed=split))


@pytest.mark.parametrize("T", [1, 4, 8])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_inactive_row_with_a_stale_table_touches_no_neighbour(kind, T):
    """Row 1 retired and its table still names row 0's pages: nothing of row
    1's ring reaches them, at the very positions row 0 writes or past them."""
    for lens in ([PAGE + 5, PAGE + 5, 3], [PAGE + 5, 2 * PAGE - 1, 3]):
        case = _case(kind, T, lens, [True, False, True], stale=(1, 0), seed=T)
        _held_to_the_scatter(case)
        # and with every row off, no page but the trash page changes at all
        off = (*case[:4], jnp.zeros(3, bool), case[5])
        for before, after in zip(*_held_to_the_scatter(off)):
            np.testing.assert_array_equal(before[:, 1:], after[:, 1:])


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_clamped_window_keeps_the_rows_live_tokens(kind):
    """At the last position of a page the first window starts ``T - 1``
    positions BEFORE the row's offset: those hold the row's live tokens and
    leave with the bits they came with."""
    case = _case(kind, 8, [2 * PAGE - 1], [True])
    before, after = _held_to_the_scatter(case)
    tables = case[2][0] if isinstance(case[2], tuple) else case[2]
    page = int(tables[0, 1])
    np.testing.assert_array_equal(before[0][0, page, :, : PAGE - 1], after[0][0, page, :, : PAGE - 1])
    assert (before[0][0, page, :, PAGE - 1] != after[0][0, page, :, PAGE - 1]).any()


@pytest.mark.parametrize("T", [9, 16, 20])
@pytest.mark.parametrize("kind", ["plain", "latent"])
def test_a_ring_longer_than_a_page_goes_in_as_several(kind, T):
    _held_to_the_scatter(_case(kind, T, [3, PAGE - 1, PAGE, 0], [True, True, True, False]))


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_random_lengths_and_masks(kind):
    rng = np.random.default_rng(46)
    for trial in range(6):
        B = 6
        lens = rng.integers(0, PMAX * PAGE + 4, B).tolist()
        active = (rng.random(B) < 0.7).tolist()
        _held_to_the_scatter(_case(kind, int(rng.choice([1, 2, 4, 8])), lens, active, seed=trial))


def test_the_write_is_a_loop_of_window_updates_and_no_scatter():
    """What ``jax.jit`` lowers the write to on any backend: a ``while`` over
    the rows of ``dynamic_update_slice``s, and no scatter at all."""
    case = _case("by_kind", 4, [5, 6], [True, True])
    text = jax.jit(M.consolidate_ring_paged, static_argnums=5).lower(*case).as_text()
    assert "stablehlo.while" in text and "dynamic_update_slice" in text
    assert "scatter" not in text
