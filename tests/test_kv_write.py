"""The dispatch's paged KV write (``model.consolidate_ring_paged``): per-row
window updates in place, held to the advanced-index scatter it replaced in
PR 46, which lives on HERE as the reference.  The pool after the write is
bit-identical to the scatter's everywhere but page 0 (the trash page), for a
plain pool, for pools by cache kind with a window ring that wraps, and for
the two sides of a latent pool; at a page's start, at its last position,
at every split of a straddle, past the table's end, and with inactive rows
whose stale table names a live neighbour's pages.

Since PR 49 a K/V pool of heads narrower than a lane tile is STORED ``f``
positions a row (``model.positions_per_row``: ``[L, N, K, page / f, f *
hd]``).  Every case runs at ``f`` = 1, 2 and 4 (heads of 16, 64 and 32 on
pages of 8, 32 and 64 positions): the reference scatters
into the DECLARED pool, the write under test lands in the stored one, and
the two are compared through ``model.gather_window_paged`` over every page
but the trash page (and bit for bit in the declared shape).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from calfkit_tpu.inference import model as M

PMAX = 4  # table entries a row (global); a window layer's ring holds RING
RING = 3
LAYERS = 3
LAYER_KINDS = ((0,), (1, 2))  # pools by kind: one global layer, two window layers


def _scatter(pool_side, r, page_ids, offsets):
    """PR 45's ``model._write_tokens``, to the letter."""
    vals = jnp.transpose(r, (2, 1, 0, 3, 4)).astype(pool_side.dtype)
    return pool_side.at[:, page_ids, :, offsets].set(vals)


def _reference(pool, ring, tables, base_lens, active, layer_kinds=None):
    """PR 45's ``consolidate_ring_paged`` and ``_consolidate_by_kind``, on
    the pool as DECLARED, ``[L, N, K, page, w]``."""
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


# layout -> (kind, KV heads, the two sides' widths, f: positions a stored row)
LAYOUTS = {
    "plain": ("plain", 2, (16, 16), 1), "plain-f2": ("plain", 2, (64, 64), 2),
    "plain-f4": ("plain", 2, (32, 32), 4),
    "by_kind": ("by_kind", 2, (16, 16), 1), "by_kind-f2": ("by_kind", 2, (64, 64), 2),
    "by_kind-f4": ("by_kind", 2, (32, 32), 4),
    "latent": ("latent", 1, (32, 8), 1),  # a latent pool is stored as declared
}
def _page(layout):
    """Pages of 8 positions at f = 1; of 16 stored rows at f > 1 (32 and 64
    positions): two of the 8-row windows the write takes there."""
    f = LAYOUTS[layout][3]
    return 8 if f == 1 else 16 * f


def _stored(tree, f):
    """Declared ``[.., page, w]`` sides as the pool stores them, ``[.., page
    / f, f * w]``: the same numbers in the same order."""
    return jax.tree.map(
        lambda a: a.reshape(*a.shape[:-2], a.shape[-2] // f, f * a.shape[-1]), tree)


def _declared(tree, f):
    return jax.tree.map(
        lambda a: a.reshape(*a.shape[:-2], a.shape[-2] * f, a.shape[-1] // f), tree)


def _case(layout, T, lens, active, stale=None, seed=0):
    """(pool AS DECLARED, ring, tables, base_lens, active, layer_kinds) with
    every row's table distinct; ``stale`` = (row, neighbour): the row's
    table is the neighbour's (a retired slot whose pages went to a new
    request)."""
    rng = np.random.default_rng(seed)
    B = len(lens)
    kind, K, widths, _ = LAYOUTS[layout]
    page = _page(layout)
    pages = 1 + B * PMAX
    ring = _sides(rng, (LAYERS, T, B, K), widths)
    tables = _tables(rng, B, PMAX, pages)
    if stale is not None:
        tables = tables.at[stale[0]].set(tables[stale[1]])
    lens, active = jnp.asarray(lens, jnp.int32), jnp.asarray(active, bool)
    if kind != "by_kind":
        return _sides(rng, (LAYERS, pages, K, page), widths), ring, tables, lens, active, None
    glob, win = (len(ids) for ids in LAYER_KINDS)
    wpages = 1 + B * RING
    kg, vg = _sides(rng, (glob, pages, K, page), widths)
    kw, vw = _sides(rng, (win, wpages, K, page), widths)
    ring_tables = _tables(rng, B, RING, wpages)
    if stale is not None:
        ring_tables = ring_tables.at[stale[0]].set(ring_tables[stale[1]])
    return ((kg, kw), (vg, vw)), ring, (tables, ring_tables), lens, active, LAYER_KINDS


def _bits(tree):
    return [np.asarray(a).view(np.uint16) for a in jax.tree.leaves(tree)]


def _every_page_but_the_trash_page(tree, widths):
    """Each side's pages 1.., every layer, read through
    ``gather_window_paged`` (one row whose table names them all)."""
    sides = jax.tree.leaves(tree)
    widths = [w for w in widths for _ in range(len(sides) // 2)]
    out = []
    for side, w in zip(sides, widths):
        table = jnp.arange(1, side.shape[1], dtype=jnp.int32)[None, :]
        out += [M.gather_window_paged(side[layer], table, table.shape[1], w)
                for layer in range(side.shape[0])]
    return _bits(out)


def _held_to_the_scatter(layout, case, write=None):
    """The write under test on the STORED pool against the scatter on the
    declared one; returns (before, after) bits in the declared shape."""
    _, _, widths, f = LAYOUTS[layout]
    pool = case[0]
    write = write or jax.jit(M.consolidate_ring_paged, static_argnums=5)
    got = write(_stored(pool, f), *case[1:])
    want = _reference(*case)
    assert [a.shape for a in jax.tree.leaves(got)] == [
        a.shape for a in jax.tree.leaves(_stored(pool, f))]
    for g, w in zip(_every_page_but_the_trash_page(got, widths),
                    _every_page_but_the_trash_page(want, widths)):
        np.testing.assert_array_equal(g, w)
    for g, w in zip(_bits(_declared(got, f)), _bits(want)):
        assert g.shape == w.shape
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])  # all but the trash page
    return _bits(pool), _bits(_declared(got, f))


# a row's length at the start of the dispatch, in pages and positions, by
# what it exercises
LENGTHS = {
    "page_start": (1, 0),
    "page_last": (2, -1),
    "mid": (1, 3),
    "mid_odd_row": (1, 5),  # an odd offset: a stored row's second position at f = 2
    "table_last": (PMAX, -1),  # runs over the table's end: in_range
    "past_table": (PMAX, 2),  # every position out of range
    "ring_wraps": (RING, -2),  # a window ring's last entry into its first
}


def _length(where, page):
    pages, positions = LENGTHS[where]
    return pages * page + positions


@pytest.mark.parametrize("T", [1, 4, 8])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("where", sorted(LENGTHS))
def test_window_write_is_the_scatter_outside_the_trash_page(layout, T, where):
    n = _length(where, _page(layout))
    _held_to_the_scatter(layout, _case(layout, T, [n, 0, n + 1, 5], [True, True, True, False]))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("T,split", [(T, split) for T in (4, 8) for split in range(1, T)])
def test_straddle_at_every_split(layout, T, split):
    """``split`` tokens in the row's page, the rest in the next."""
    page = _page(layout)
    lens = [2 * page - split, page - split, RING * page - split]
    _held_to_the_scatter(layout, _case(layout, T, lens, [True, True, True], seed=split))


@pytest.mark.parametrize("T", [1, 4, 8])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_inactive_row_with_a_stale_table_touches_no_neighbour(layout, T):
    """Row 1 retired and its table still names row 0's pages: nothing of row
    1's ring reaches them, at the very positions row 0 writes or past them."""
    page = _page(layout)
    for lens in ([page + 5, page + 5, 3], [page + 5, 2 * page - 1, 3]):
        case = _case(layout, T, lens, [True, False, True], stale=(1, 0), seed=T)
        _held_to_the_scatter(layout, case)
        # and with every row off, no page but the trash page changes at all
        off = (*case[:4], jnp.zeros(3, bool), case[5])
        for before, after in zip(*_held_to_the_scatter(layout, off)):
            np.testing.assert_array_equal(before[:, 1:], after[:, 1:])


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_clamped_window_keeps_the_rows_live_tokens(layout):
    """At the last position of a page the first window starts before the
    row's offset (``T - 1`` positions at f = 1, whole stored rows at f > 1):
    those hold the row's live tokens and leave with the bits they came with."""
    page = _page(layout)
    case = _case(layout, 8, [2 * page - 1], [True])
    before, after = _held_to_the_scatter(layout, case)
    tables = case[2][0] if isinstance(case[2], tuple) else case[2]
    at = int(tables[0, 1])
    np.testing.assert_array_equal(before[0][0, at, :, : page - 1], after[0][0, at, :, : page - 1])
    assert (before[0][0, at, :, page - 1] != after[0][0, at, :, page - 1]).any()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_window_s_tail_past_the_row_s_last_token_keeps_its_bits(layout):
    """A window of whole stored rows can end PAST the row's last new token
    (f > 1): what lies there, a later token of a page this row took over
    from a retired one, or nothing yet, leaves with the bits it came with."""
    page = _page(layout)
    case = _case(layout, 1, [page + 2], [True])
    before, after = _held_to_the_scatter(layout, case)
    tables = case[2][0] if isinstance(case[2], tuple) else case[2]
    at = int(tables[0, 1])
    changed = (before[0][0, at] != after[0][0, at]).any(axis=(0, 2))  # by position of the page
    assert changed.tolist() == [p == 2 for p in range(page)]


@pytest.mark.parametrize("over", [1, 8, 12])
@pytest.mark.parametrize("layout", ["plain", "plain-f2", "plain-f4", "latent"])
def test_a_ring_longer_than_a_page_goes_in_as_several(layout, over):
    page = _page(layout)
    T = page + over
    _held_to_the_scatter(
        layout, _case(layout, T, [3, page - 1, page, 0], [True, True, True, False]))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_random_lengths_and_masks(layout):
    rng = np.random.default_rng(46)
    page = _page(layout)
    for trial in range(6):
        B = 6
        lens = rng.integers(0, PMAX * page + 4, B).tolist()
        active = (rng.random(B) < 0.7).tolist()
        _held_to_the_scatter(
            layout, _case(layout, int(rng.choice([1, 2, 3, 4, 8])), lens, active, seed=trial))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_wave_s_pages_then_the_dispatch_s_tokens_on_the_same_pool(layout):
    """``write_prefill_pages`` (a wave's landing: whole pages of its scratch)
    then ``consolidate_ring_paged`` (the next dispatch's tokens behind them)
    on the same stored pool, against page-by-page assignment and the scatter
    on the declared one."""
    kind, K, widths, f = LAYOUTS[layout]
    page = _page(layout)
    pool, ring, tables, _, active, layer_kinds = _case(layout, 4, [0, 0, 0], [True, True, False])
    rng = np.random.default_rng(48)
    prompt_pages = 2
    scratch = _sides(rng, (LAYERS, 3, K, prompt_pages * page), widths)
    lens = jnp.asarray([2 * page - 3, page + 1, 7], jnp.int32)  # where each row's prompt ended
    by_kind = isinstance(tables, tuple)
    ids = tuple(t[:, :prompt_pages] for t in tables) if by_kind else tables[:, :prompt_pages]

    def landed(side, s, page_ids):  # declared [L, N, K, page, w] <- [L, R, K, P, w]
        side = np.array(side)
        for r in range(s.shape[1]):
            for j in range(prompt_pages):
                side[:, int(page_ids[r, j])] = np.asarray(s)[:, r, :, j * page:(j + 1) * page]
        return jnp.asarray(side)

    if by_kind:
        gl, wl = (np.asarray(x) for x in layer_kinds)
        want = tuple((landed(side[0], s[gl], ids[0]), landed(side[1], s[wl], ids[1]))
                     for side, s in zip(pool, scratch))
    else:
        want = tuple(landed(side, s, ids) for side, s in zip(pool, scratch))

    def both(stored_pool, scratch, ids, ring, tables, lens, active):
        stored_pool = M.write_prefill_pages(stored_pool, scratch, ids, layer_kinds)
        return M.consolidate_ring_paged(stored_pool, ring, tables, lens, active, layer_kinds)

    case = (want, ring, tables, lens, active, layer_kinds)
    write = lambda stored_pool, *rest: jax.jit(both)(
        _stored(pool, f), scratch, ids, ring, tables, lens, active)
    _held_to_the_scatter(layout, case, write)


@pytest.mark.parametrize("layout", ["by_kind", "by_kind-f2"])
def test_the_write_is_a_loop_of_window_updates_and_no_scatter(layout):
    """What ``jax.jit`` lowers the write to on any backend: a ``while`` over
    the rows of ``dynamic_update_slice``s, and no scatter at all."""
    page, f = _page(layout), LAYOUTS[layout][3]
    case = _case(layout, 4, [page - 3, page - 2], [True, True])
    text = jax.jit(M.consolidate_ring_paged, static_argnums=5).lower(
        _stored(case[0], f), *case[1:]).as_text()
    assert "stablehlo.while" in text and "dynamic_update_slice" in text
    assert "scatter" not in text


# --------------------------------------------------------------------------- #
# the stored pool against the declared one, through EVERY writer and reader
# --------------------------------------------------------------------------- #

CHAIN_PAGE = 64  # whole sublane tiles of bfloat16 at f = 1, 2 and 4: 64, 32 and 16 stored rows
CHAIN_ENTRIES = 3  # a row's table (plain) or its ring of pages (ring)
CHAIN_WINDOW = 96  # the ring form's attention window, in positions


@pytest.mark.parametrize("form", ["plain", "ring"])
@pytest.mark.parametrize("T", [1, 4, 8, CHAIN_PAGE + 3])
@pytest.mark.parametrize("hd", [128, 64, 32])
def test_the_stored_pool_is_the_declared_one_through_every_writer_and_reader(hd, T, form):
    """ONE pool twice: as ``make_page_pool`` stores it (``f`` = 1, 2, 4 at
    heads of 128, 64, 32) and as declared, ``[L, N, K, page, hd]``, which every
    function here takes as a pool of ``f`` = 1 (they read ``f`` off the pool's
    lanes).  A wave lands (``write_prefill_pages``), a dispatch's tokens
    follow (``_write_windows``: ``T`` of 1, 4, 8 and a ring longer than a
    page; first offsets odd, even, at ``page - 1`` and straddling; an inactive
    row whose stale table names a neighbour's pages; a row past its table,
    which the ``ring`` form wraps into its first entry), and both pools hold
    the same bits outside the trash page.  ``gather_window_paged`` reads the
    same bits out of both, and the decode kernel in interpret mode, plain and
    in its window (ring) form, reads the stored pool to what the XLA law reads
    from the declared one."""
    from calfkit_tpu.inference.pallas_attention import paged_decode_attention_pallas

    page, entries, L, K, G, B = CHAIN_PAGE, CHAIN_ENTRIES, 2, 2, 2, 6
    f = M.positions_per_row(hd, page, jnp.bfloat16)
    assert f == 128 // hd
    rng = np.random.default_rng(hd + T)
    pages = 1 + B * entries
    declared = _sides(rng, (L, pages, K, page), (hd, hd))
    own = _tables(rng, B, entries, pages)
    # where each row's prompt ended: an odd and an even offset, the page's last
    # position, a straddle, (the inactive row), the table's end
    lens = jnp.asarray([page + 5, page + 6, 2 * page - 1, 2 * page - 3, page + 5,
                        entries * page - 2], jnp.int32)
    active = jnp.asarray([True, True, True, True, False, True])
    tables = own.at[4].set(own[0])  # row 4 retired: its table still names row 0's pages
    scratch = _sides(rng, (L, B, K, 2 * page), (hd, hd))
    page_ids = own[:, :2].at[4].set(0)  # the retired row's prompt goes to the trash page
    ring = _sides(rng, (L, T, B, K), (hd, hd))

    @jax.jit
    def written(pool):
        pool = M.write_prefill_pages(pool, scratch, page_ids)
        return M._write_windows(pool, ring, tables, lens, active, wraps=form == "ring")

    plain, stored = written(declared), written(_stored(declared, f))
    assert all(side.shape == (L, pages, K, page // f, f * hd) for side in stored)
    for got, want, before in zip(_bits(_declared(stored, f)), _bits(plain), _bits(declared)):
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
        assert (want[:, 1:] != before[:, 1:]).any()
    windows = [[M.gather_window_paged(side[1], tables, entries, hd) for side in pool]
               for pool in (plain, stored)]
    for got, want in zip(_bits(windows[1]), _bits(windows[0])):
        np.testing.assert_array_equal(got, want)

    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), jnp.bfloat16)
    read_lens = jnp.where(active, lens + T, 0)
    if form == "ring":
        valid = M._window_ring_valid(entries * page, read_lens, read_lens, CHAIN_WINDOW)
        starts = {"window_starts": jnp.maximum(read_lens - CHAIN_WINDOW + 1, 0)}
    else:
        read_lens = jnp.minimum(read_lens, entries * page)
        valid = jnp.arange(entries * page)[None, :] < read_lens[:, None]
        starts = {}
    o, m, z = paged_decode_attention_pallas(
        q, *stored, jnp.int32(1), tables, read_lens, wpages=entries, interpret=True, **starts)
    o2, m2, z2 = M.masked_attention_source(q, *windows[0], valid)
    live = np.asarray(read_lens) > 0
    norm = lambda o, z: np.asarray(o / jnp.maximum(z[..., None], 1e-30), np.float32)[live]
    np.testing.assert_allclose(norm(o, z), norm(o2, z2[..., 0]), atol=2e-2)
    np.testing.assert_allclose(np.asarray(m)[live], np.asarray(m2[..., 0])[live], rtol=1e-5)
