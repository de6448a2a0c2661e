"""The Pallas TPU attention kernels: paged single-query decode attention,
and a window stack's prefill chunk.

Why kernels: the XLA paged read copies every row's whole window out of the
pool before attending it (``model.gather_window_paged``), whatever the row
holds.  A kernel reads each row's LIVE pages in place: one program a row,
a loop over that row's own pages with whole-slab copies out of the pool in
HBM, bf16 operands into the MXU, mask + softmax statistics + weighted sum
fused.  Its work follows the row lengths, not the window bucket.  The K/V
body's copies run in ONE pipeline over the rows of a step (PR 55): a
program starts, past its own row's last block, the first blocks of the
next row that has pages.

The decode read has TWO bodies, because the two kinds of pool ask for
different things of one page:

- :func:`_paged_decode_kernel` reads K and V pairs of kv heads under a
  block-diagonal head mask (a dense or hybrid model's pool).  A head that
  divides a lane tile (64) is read ``f = 128 / hd`` positions a lane row,
  which is how such a pool is STORED (``model.positions_per_row``,
  ``model.make_page_pool``): the kernel takes every pool as it lies.
- :func:`_latent_decode_kernel` reads a LATENT (MLA) pool in the absorbed
  form: every head scores ONE key a token, ``q_lat . c + q_rope . k_rope``,
  and the value is ``c`` again, taken from the buffer the scores just read,
  so a token's latent crosses HBM → VMEM once a layer a step.  The ``c``
  side is read as it lies; the narrow rope side through
  :func:`latent_rope_view`, made once a dispatch.

The THIRD kernel is a multi-query one (PR 39): a WINDOW STACK's prefill and
prefill-chunk attention, :func:`_chunk_attention_kernel`.  There the XLA
source, ``model.blocked_attention``, is a loop over key blocks whose scores
(128 heads x 2,048 queries x 512 keys in float32 at command-a-plus's
widths: 537 MB a block) cross HBM several times a block; the kernel is the
same flash law with a query tile's scores, maximum, sum and output kept in
VMEM across the key axis, the G query heads of a KV head riding one program
so that a key block copied once serves them all, and each tile's first and
last key block (the window's lower bound, the causal bound and the row's
length) prefetched as scalars, so blocks outside are neither copied nor
computed.  It reads one layer's prefill scratch ``[B, K, P, hd]`` as it
lies.

Every other attention computation (prefill and prefill chunks of every
model WITHOUT window layers, speculative verify, dense decode, the
long-context lane) is the XLA source in ``model.py``: the kernels that once
stood beside these ran in no measured cell or lost where they were measured
(PERF.md section 6, PRs 25 and 29: ``attention_xla`` was 3% of the Mistral
cell's device time; a window stack's chunks were 41% of theirs).

The two decode entry points (:func:`paged_decode_attention_pallas`,
:func:`latent_decode_attention_pallas`) return *unnormalized* output plus
the softmax statistics ``(m, z)`` so the caller can fold in the fresh-token
ring (tiny, plain XLA) with the logsumexp merge the XLA path uses;
:func:`chunk_attention_pallas` returns the normalized output, as
``blocked_attention`` does.

What the TPU lowering demands, and how the kernels meet it: per-row scalars
(lengths, block tables, the layer index) ride ``PrefetchScalarGridSpec``
into SMEM, a ``(1,)`` block of a ``[B]`` array being refused; every VMEM
block's last two dims equal the array's or are (8, 128)-aligned; the pool
stays in HBM (``memory_space=pl.ANY``) and a page slab is copied whole, so
a slab is whole tiles (:func:`paged_decode_in_place_ok`,
:func:`latent_decode_in_place_ok`).

Who chooses them: ``InferenceEngine._resolved_attn_impl`` (the decode
read) and ``_resolved_chunk_attn_impl`` (a window stack's chunks), once at
construction, and nowhere else; WHICH decode body follows from what the
engine observes in its model, ``config.latent``.  ``attention_impl="auto"``
selects a kernel on a TPU, one device, a shape its rule takes (the decode
read: paged KV too; the chunks: ``config.windowed``); else the XLA source,
which is the reference.  ``"pallas"`` / ``"pallas_interpret"`` exist for
tests and bring-up: they waive the platform test alone; they NAME the
decode read, so an engine outside THAT rule is refused with
:class:`PallasShapeError`, and a chunk outside its own is served by XLA.

Status: the chunk kernel AOT-compiles for a described v5e at
command-a-plus's widths in both its forms (with and without a lower bound)
and agrees with ``blocked_attention`` in interpret mode
(``tests/test_chunk_attention.py``); PERF.md section 6, PR 39, has the
chip's numbers.  The two decode bodies AOT-compile for a described v5e, the first at TinyLlama-1.1B's,
Llama-3-8B's, Mistral-7B's, InternLM2-1.8B's and granite-4.0-h-micro's
widths, the second at Kimi-VL-A3B's (``tests/test_tpu_compile.py``), and
agree with interpret mode and the XLA path on CPU; PERF.md section 6, PRs
25, 28 and 32, has the chip's numbers.  Since PR 55 the K/V body keeps 2 to
4 slots of a block from the slab's bytes (:func:`paged_decode_slots`) and
hands a row's first copies to the row before it; under the TPU interpreter
(copies that land when waited for, NaN where none wrote, an error for a
copy left unwaited) it is the parent's result bit for bit on every order
of empty and live rows (``tests/test_paged_decode_attention.py``), and on
the chip alone at seven cells' shapes (PERF.md section 6, PR 55).  The
latent body is as it was: one block of 8 pages ahead, started by its own
row.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (kernel, "compiled" | "interpreted") -> times TRACED: evidence for
# chip_smoke.py / tests that a "pallas" path really built Mosaic kernels
KERNEL_TRACES: collections.Counter = collections.Counter()


def _note_trace(kernel: str, interpret: bool) -> None:
    KERNEL_TRACES[kernel, "interpreted" if interpret else "compiled"] += 1


class PallasShapeError(ValueError):
    """A kernel was asked for outside its rule: explicitly
    (``attention_impl="pallas"`` / ``"pallas_interpret"``) on an engine
    that is not paged, spans more than one device or has a shape outside
    :func:`paged_decode_in_place_ok` (:func:`latent_decode_in_place_ok` for
    a latent pool; refused at construction), or by a direct call with such
    a shape.  A kernel request is never quietly served by another path."""


def paged_decode_in_place_ok(head_dim: int, page: int, dtype) -> bool:
    """Whether :func:`_paged_decode_kernel` can take the pool of these shapes
    on a TPU: a page slab is copied and flattened whole out of the pool AS IT
    IS STORED (``model.make_page_pool``: ``f = model.positions_per_row``
    positions a row), so a stored row is whole lane tiles (128: a head of
    whole tiles, or ``f`` positions of a head that divides one) and a page's
    ``page / f`` rows whole sublane tiles of the cache's dtype (8 rows of 32
    bits: 16 for bf16).  What fails this reads through XLA under ``"auto"``
    and is refused under an explicit ``"pallas"``."""
    from calfkit_tpu.inference.model import LANES, positions_per_row, sublane_tile

    f = positions_per_row(head_dim, page, dtype)
    return (f * head_dim) % LANES == 0 and (page // f) % sublane_tile(dtype) == 0


# pages of one row folded per compute block of the paged decode kernel
# (tuned on the chip, PERF.md section 6): the scores of a block are one
# [H, PAGES_PER_BLOCK * K * page] product
PAGED_DECODE_PAGES_PER_BLOCK = 2
# the bytes of K and V the paged decode kernel keeps IN FLIGHT, the block it
# waits for among them (the chip's copy latency x its stream: on a v5e one
# block ahead of 256-512 KB left a row's walk at half the peak, PERF.md
# section 6, PR 55): the buffer gets as many slots of a block as hold them
PAGED_DECODE_BYTES_IN_FLIGHT = 3 * 512 * 1024
# a column no bound admits: another kv head's, in the stored column positions
_NO_COLUMN = 1 << 30


def paged_decode_slots(block_bytes: int) -> int:
    """Slots of one block (its K and V, ``block_bytes`` together) in the
    paged decode kernel's buffer: what holds ``PAGED_DECODE_BYTES_IN_FLIGHT``,
    2 (the double buffer every slab had, and a slab of 1 MB a page keeps) to
    4.  The one thing about the kernel that follows the slab's width."""
    return min(4, max(2, -(-PAGED_DECODE_BYTES_IN_FLIGHT // block_bytes)))


def _paged_decode_kernel(
    layer_ref, tables_ref, lens_ref,  # scalar-prefetch (SMEM)
    *refs,  # [starts_ref (the window form alone),] q_ref, pool_k, pool_v, outs, scratch
    wpages: int, group: int, pack: int, ring: bool = False,
):
    """One ROW of a paged decode step: a loop over that row's own live
    pages, ``ceil(len / page)`` of them, each fetched as its whole
    ``[K, page / f, f * hd]`` slab (contiguous in the pool) by an async
    copy.  Nothing past the row's length is read or computed; a row of
    length 0 starts no copy at all, waits for none and is stepped over.

    The rows of a step follow one another in ONE copy pipeline (PR 55).  The
    buffer is a ring of S slots of a block that lives across the grid's
    programs, and a cursor in SMEM (``ahead``: row, block, slot) names the
    next block nobody has started: the first program finds the first row
    that has pages and starts S - 1 blocks, and every block computed starts
    one more, its own row's next or, past the row's last, the FIRST blocks of
    the next row that has pages.  So a row's first block is in flight before
    the row's program begins, S - 1 blocks are always on their way, and each
    block is still waited for by the program of the row it belongs to, in the
    slot the order of blocks gives it.  What does not depend on the row (the
    head mask and every column's position in a block, ``cols``) is built by
    the first program and read by the rest.

    All K heads of a block are scored in ONE product: q is the row's
    ``[H, hd]`` (H = K * G, a whole sublane tile where G alone is not),
    the block's K slab is ``[P * K * page, hd]``, and a static
    block-diagonal mask keeps query head h on the columns of its own kv
    head.  The masked columns weigh exactly 0 in ``p``, so the PV product
    over the same flattened axis is each head's own weighted sum.

    ``pack`` (f) > 1 is a head narrower than a lane tile, whose pool is
    stored so (``model.positions_per_row``): a slab row holds f positions
    side by side, and
    the row's queries come f times over, copy j with q in lane block j and
    zeros elsewhere.  Copy j's scores are then those of positions
    ``f * c + j`` and lane block j of its PV rows their weighted sum (the
    other blocks are dropped): the same body at ``f * H`` rows and 128
    lanes, and the f partial results folded by the logsumexp law at the end.

    ``ring`` is the WINDOW form (a window layer's pool: the row's table is a
    ring of ``wpages`` entries, position ``p`` in entry ``(p // page) %
    wpages``): a fourth scalar array gives each row the first position its
    query may see, the walk starts at the page that holds it and wraps
    around the table, and that page's head is masked, so the work follows
    ``min(len, window)``.  Without it nothing of this is traced: the kernel
    of a model without window layers is the one it was.
    """
    if ring:
        starts_ref, *refs = refs
    q_ref, pool_k, pool_v, o_ref, m_ref, z_ref, kbuf, vbuf, sems, cols, ahead = refs
    b = pl.program_id(0)
    B = tables_ref.shape[0]
    S, P, K, rows, lanes = kbuf.shape  # slots of a block; a slab: rows of pack positions
    page = rows * pack
    H = q_ref.shape[1]  # pack copies of the row's query heads
    heads = H // pack
    C = P * K * rows
    layer = layer_ref[0]

    def walk(r):
        """Row r's walk: (the table entry it starts at, its pages)."""
        if not ring:
            return 0, jnp.minimum(lax.div(lens_ref[r] + (page - 1), page), wpages)
        # positions seen: [start, len); the walk and the mask count from the
        # first page of them
        first = lax.div(starts_ref[r], page)
        return first, jnp.minimum(
            lax.div(lens_ref[r] - first * page + (page - 1), page), wpages)

    def next_slot(slot):
        return jnp.where(slot + 1 == S, 0, slot + 1)

    def row_with_pages(r):
        """The first row at or after r that has pages; B where none has."""
        return lax.while_loop(
            lambda r: (r < B) & (walk(jnp.minimum(r, B - 1))[1] == 0), lambda r: r + 1, r)

    def copies(slot, i, n):
        """Page n of the layer into page i of a slot: its K copy, its V copy."""
        return (
            pltpu.make_async_copy(pool_k.at[layer, n], kbuf.at[slot, i], sems.at[0, slot]),
            pltpu.make_async_copy(pool_v.at[layer, n], vbuf.at[slot, i], sems.at[1, slot]),
        )

    def start_ahead():
        """Start the copies of the next block nobody has started, whichever
        row's it is, and move the cursor past it; nothing once no row is left."""
        r, blk, slot = ahead[0], ahead[1], ahead[2]

        @pl.when(r < B)
        def _():
            first, n_pages = walk(r)
            for i in range(P):  # static: a partial last block skips its tail
                @pl.when(blk * P + i < n_pages)
                def _():
                    at = blk * P + i
                    n = tables_ref[r, lax.rem(first + at, wpages) if ring else at]
                    for dma in copies(slot, i, n):
                        dma.start()

            last = (blk + 1) * P >= n_pages
            ahead[1] = jnp.where(last, 0, blk + 1)
            ahead[2] = next_slot(slot)

            @pl.when(last)
            def _():
                ahead[0] = row_with_pages(r + 1)

    @pl.when(b == 0)
    def _open():
        if P > 1:
            # a partial last block leaves buffer pages no copy ever wrote:
            # their columns are masked (p = 0), and 0 x garbage must stay 0
            vbuf[...] = jnp.zeros_like(vbuf)
        col = lax.broadcasted_iota(jnp.int32, (H, C), 1)
        row = lax.broadcasted_iota(jnp.int32, (H, C), 0)
        own_head = lax.rem(lax.div(col, rows), K) == lax.div(
            row if pack == 1 else lax.rem(row, heads), group)
        col_pos = lax.div(col, K * rows) * rows + lax.rem(col, rows)  # slab row in block
        if pack > 1:
            col_pos = col_pos * pack + lax.div(row, heads)  # copy j: positions f*c + j
        cols[...] = jnp.where(own_head, col_pos, _NO_COLUMN)
        ahead[0] = row_with_pages(0)
        ahead[1] = ahead[2] = ahead[3] = 0

        def prime(_, carry):
            start_ahead()
            return carry

        lax.fori_loop(0, S - 1, prime, 0)

    first, n_pages = walk(b)
    n_blocks = lax.div(n_pages + (P - 1), P)
    kv_len = lens_ref[b] - first * page
    if ring:
        start = starts_ref[b] - first * page
    q = q_ref[0]  # [H, lanes], the cache's dtype
    scale = 1.0 / math.sqrt(lanes // pack)  # the law of the REAL head

    def block(blk, carry):
        m_prev, z_prev, acc, slot = carry
        start_ahead()
        for i in range(P):  # this block's own copies, started a while ago
            @pl.when(blk * P + i < n_pages)
            def _():
                for dma in copies(slot, i, 0):  # a wait asks the slot and the size alone
                    dma.wait()
        k = kbuf[slot].reshape(C, lanes)
        v = vbuf[slot].reshape(C, lanes)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [H, C]
        col_pos = cols[...]
        mask = col_pos < kv_len - blk * (P * page)
        if ring:
            mask = mask & (col_pos >= start - blk * (P * page))
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # p in the cache's dtype before the PV product, z from the rounded
        # p: the law of model.masked_attention_source
        p = jnp.exp(s - m_new).astype(v.dtype)
        z_new = z_prev * alpha + jnp.sum(
            p.astype(jnp.float32), axis=-1, keepdims=True
        )
        acc = acc * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, z_new, acc, next_slot(slot)

    m, z, acc, ahead[3] = lax.fori_loop(
        0, n_blocks, block,
        (
            # the -1e29 floor of a fully masked row is where m starts
            jnp.full((H, 1), -1e29, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, lanes), jnp.float32),
            ahead[3],  # the slot of this row's first block: where the last row's walk ended
        ),
    )
    if pack > 1:
        # fold the copies (model.logsumexp_merge, unnormalized): copy j's
        # weighted sum stays in ITS lane block, for the caller to add up
        parts = [slice(j * heads, (j + 1) * heads) for j in range(pack)]
        m_all = functools.reduce(jnp.maximum, [m[part] for part in parts])
        lane_block = lax.broadcasted_iota(
            jnp.int32, (heads, lanes), 1
        ) // (lanes // pack)
        z_all = jnp.zeros_like(m_all)
        o_all = jnp.zeros((heads, lanes), jnp.float32)
        for j, part in enumerate(parts):
            w = jnp.exp(m[part] - m_all)
            z_all = z_all + z[part] * w
            o_all = jnp.where(lane_block == j, acc[part] * w, o_all)
        m, z, acc = m_all, z_all, o_all
    o_ref[0] = acc
    m_ref[0] = m
    z_ref[0] = z


def _lane_block_copies(q: jax.Array, f: int) -> jax.Array:
    """``[B, H, hd]`` → ``[B, f * H, f * hd]``: f copies of a row's queries,
    copy j's q in lane block j and zeros elsewhere (``f == 1``: q itself)."""
    if f == 1:
        return q
    B, H, hd = q.shape
    eye = jnp.eye(f, dtype=q.dtype)
    return (q[:, None, :, None, :] * eye[None, :, None, :, None]).reshape(
        B, f * H, f * hd
    )


@functools.partial(
    jax.jit, static_argnames=("wpages", "interpret", "pages_per_block", "slots")
)
def paged_decode_attention_pallas(
    q: jax.Array,  # [B, K, G, hd]
    pool_k: jax.Array,  # [L, N, K, page / f, f * hd] the WHOLE pool (no
    pool_v: jax.Array,  # slicing), as stored: model.make_page_pool
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    tables: jax.Array,  # [B, Pmax] int32 block tables
    base_lens: jax.Array,  # [B]
    *,
    wpages: int,
    interpret: bool = False,
    pages_per_block: int = PAGED_DECODE_PAGES_PER_BLOCK,
    slots: int = 0,  # 0: paged_decode_slots of a block's bytes
    window_starts: jax.Array | None = None,  # [B]: the WINDOW form (see below)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Paged decode attention → (o [B,K,G,hd] f32 unnormalized, m [B,K,G]
    f32, z [B,K,G] f32).

    ``window_starts`` selects the kernel's window form for a window layer's
    pool: ``tables`` [B, wpages] is then each row's RING of pages and row
    ``b`` attends positions ``window_starts[b] <= p < base_lens[b]`` (the
    caller's lower bound, ``max(0, q - W + 1)``).  None: the form every
    other model has, the same program as before there was a window.

    Reads each row's LIVE pages in place (:func:`_paged_decode_kernel`):
    the pool goes in whole and stays in HBM, the grid is the rows alone,
    and the work of a row follows ``base_lens[b]`` — ``wpages`` is only the
    static upper bound, so one kernel serves every window bucket.  K, V
    and q meet the MXU in the cache's dtype with float32 accumulation.

    The pool comes as it is stored, ``f`` positions of a head narrower
    than a lane tile side by side in a row: ``f`` is read off the pool's
    lanes and the queries' width, and nothing of the pool is relaid."""
    from calfkit_tpu.inference.model import positions_per_row

    B, K, G, hd = q.shape
    H = K * G
    rows, lanes = pool_k.shape[3:]
    f = lanes // hd
    if (lanes % hd or f != positions_per_row(hd, rows * f, pool_k.dtype)
            or not paged_decode_in_place_ok(hd, rows * f, pool_k.dtype)):
        raise PallasShapeError(
            f"the paged decode kernel copies a page slab whole: a head of "
            f"{hd} in stored rows of {lanes} on pages of {rows} {pool_k.dtype} "
            "rows is not whole (8, 128) tiles of the pool's stored form "
            "(paged_decode_in_place_ok)"
        )
    _note_trace("paged_decode", interpret)
    P = max(1, min(pages_per_block, wpages))
    S = slots or paged_decode_slots(2 * P * K * rows * lanes * pool_k.dtype.itemsize)
    ring = window_starts is not None
    kernel = functools.partial(
        _paged_decode_kernel, wpages=wpages, group=G, pack=f, **({"ring": True} if ring else {})
    )

    def row_map(b, *_refs):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if ring else 3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, f * H, lanes), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, lanes), row_map),
            pl.BlockSpec((1, H, 1), row_map),
            pl.BlockSpec((1, H, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, P, K, rows, lanes), pool_k.dtype),
            pltpu.VMEM((S, P, K, rows, lanes), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, S)),
            pltpu.VMEM((f * H, P * K * rows), jnp.int32),  # cols: made by the first row
            pltpu.SMEM((4,), jnp.int32),  # ahead: the copies' cursor, the walk's slot
        ],
    )
    o, m, z = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, lanes), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            # rows in order: the scratch row 0 makes serves them all, and a
            # row's first copies are started while the row before it runs
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32),
        base_lens.astype(jnp.int32),
        *((jnp.minimum(window_starts, base_lens).astype(jnp.int32),) if ring else ()),
        _lane_block_copies(q.reshape(B, H, hd).astype(pool_k.dtype), f),
        pool_k, pool_v,
    )
    if f > 1:
        o = o.reshape(B, H, f, hd).sum(axis=2)  # the copies' lane blocks
    return (
        o.reshape(B, K, G, hd), m.reshape(B, K, G), z.reshape(B, K, G)
    )


@jax.named_scope("attention")
def merged_paged_decode_attention_pallas(
    q: jax.Array,  # [B, 1, H, hd]
    pool_k: jax.Array,  # [L, N, K, page / f, f * hd], as stored
    pool_v: jax.Array,
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, Pmax]
    ring_k: jax.Array,  # [T, B, K, hd]
    ring_v: jax.Array,
    base_lens: jax.Array,  # [B]
    t: jax.Array,
    *,
    wpages: int,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for the XLA paged read of ``model.decode_step_ring_paged``:
    the main-cache source from the kernel, the (tiny) ring folded in via
    the same logsumexp merge in plain XLA."""
    from calfkit_tpu.inference.model import logsumexp_merge, ring_attention_source

    B, _, H, hd = q.shape
    K = pool_k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)

    o1, m1, z1 = paged_decode_attention_pallas(
        qg, pool_k, pool_v, layer, tables, base_lens,
        wpages=wpages, interpret=interpret,
    )
    o2, m2, z2 = ring_attention_source(qg, ring_k, ring_v, t)
    out = logsumexp_merge((o1, m1[..., None], z1[..., None]), (o2, m2, z2))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# a latent (MLA) pool, read in the absorbed form
# --------------------------------------------------------------------------- #


def latent_decode_in_place_ok(kv_lora_rank: int, rope_dim: int, page: int, dtype) -> bool:
    """Whether :func:`_latent_decode_kernel` can take these shapes on a TPU:
    a page's ``c`` slab ``[page, r]`` is copied whole as it lies, so ``r`` is
    whole lane tiles; its rope slab is copied out of
    :func:`latent_rope_view`, ``f`` parts of the page side by side
    (``model.lane_pack`` of the rope width: 2 at 64), so a row of
    the view is whole lane tiles and a PART of the page, ``page / f``
    positions, whole sublane tiles of the cache's dtype: the kernel slices
    the ``c`` slab into the same parts.  What fails this reads through XLA
    under ``"auto"`` and is refused under an explicit ``"pallas"``."""
    from calfkit_tpu.inference.model import lane_pack, sublane_tile

    f = lane_pack(rope_dim)
    return (
        kv_lora_rank % 128 == 0
        and (f * rope_dim) % 128 == 0
        and page % (f * sublane_tile(dtype)) == 0
    )


def latent_rope_view(rope_side: jax.Array) -> jax.Array:
    """The rope side of a latent pool as the latent decode kernel reads it:
    ``[L, N, 1, page / f, f * dr]``, row r of a page holding positions
    ``r, r + page / f, ..`` side by side: PART j of the page in lane block
    j.  (Not a K/V pool's stored rows, neighbours side by side
    (``model.positions_per_row``): the kernel meets part j with rows ``j *
    page / f ..`` of the ``c`` slab, a slice of whole tiles, where
    neighbours would ask for every f-th row.)

    A ``[.., page, 64]`` array does not lie row-major and lane-dense in a
    TPU's HBM (``model.positions_per_row`` has why), so this is a copy of the
    whole side, an eighth of the pool, and a caller in a loop makes it ONCE,
    outside: the engine does, per dispatch
    (``InferenceEngine._decode_fn_paged``).  The ``c`` side, eight ninths of
    the pool, is never relaid.  At ``f == 1``, and for shapes outside the
    rule, this IS the side."""
    from calfkit_tpu.inference.model import lane_pack

    L, N, one, page, dr = rope_side.shape
    f = lane_pack(dr)
    if f == 1 or page % f:
        return rope_side
    parts = rope_side.reshape(L, N, one, f, page // f, dr)
    return jnp.swapaxes(parts, 3, 4).reshape(L, N, one, page // f, f * dr)


# pages of one row folded per compute block of the latent decode kernel
# (tuned on the chip, PERF.md section 6, PR 32)
LATENT_DECODE_PAGES_PER_BLOCK = 8


def _latent_decode_kernel(
    layer_ref, tables_ref, lens_ref,  # scalar-prefetch (SMEM)
    ql_ref, qr_ref, pool_c, pool_r,  # q blocks in VMEM; the pool stays in HBM
    o_ref, m_ref, z_ref,
    cbuf, rbuf, sems,
    *, wpages: int, scale: float,
):
    """One ROW of a latent decode step: a loop over that row's own live
    pages, ``ceil(len / page)`` of them, each fetched as its ``c`` slab
    ``[page, r]`` and its rope slab ``[page / f, f * dr]`` by
    double-buffered async copies.  Nothing past the row's length is read or
    computed; a row of length 0 starts no copy at all.

    All heads score a block at once, part by part: part j of a block is
    positions ``j * page / f ..`` of each of its pages, rows of whole tiles
    of the ``c`` buffer, and lane block j of the rope buffer's rows, which
    ``qr`` copy j (``q_rope`` in lane block j, zeros elsewhere) picks out.
    The parts share ONE running max and sum (a softmax does not care for
    the order of its columns), and a part's value product reads the ``c``
    rows its scores just read.
    """
    b = pl.program_id(0)
    P, page, r = cbuf.shape[1:]
    rows, lanes = rbuf.shape[2:]  # a part of a page; f rope keys a row
    f = page // rows
    H = ql_ref.shape[1]
    C = P * rows
    layer = layer_ref[0]
    kv_len = lens_ref[b]
    n_pages = jnp.minimum(pl.cdiv(kv_len, page), wpages)
    n_blocks = pl.cdiv(n_pages, P)

    def copies(blk, slot, i):
        n = tables_ref[b, blk * P + i]
        return (
            pltpu.make_async_copy(
                pool_c.at[layer, n, 0], cbuf.at[slot, i], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                pool_r.at[layer, n, 0], rbuf.at[slot, i], sems.at[1, slot]
            ),
        )

    def for_live_pages(blk, slot, act):
        # a loop, not P copies of the body: the kernel is lowered anew in
        # every program that carries decode steps (PERF.md section 6, PR 32)
        def page(i, carry):
            for dma in copies(blk, slot, i):
                act(dma)
            return carry

        lax.fori_loop(0, jnp.minimum(P, n_pages - blk * P), page, 0)

    if P > 1:
        # a partial last block leaves buffer pages no copy ever wrote:
        # their columns are masked (p = 0), and 0 x garbage must stay 0
        @pl.when(b == 0)
        def _clear():
            cbuf[...] = jnp.zeros_like(cbuf)

    @pl.when(n_blocks > 0)
    def _first():
        for_live_pages(0, 0, lambda dma: dma.start())

    ql = ql_ref[0]  # [H, r], the cache's dtype
    qr = qr_ref[0]  # [f * H, lanes]: copy j's q_rope in lane block j
    col = lax.broadcasted_iota(jnp.int32, (H, C), 1)
    col_pos = (col // rows) * page + col % rows  # part 0's position in block

    def block(blk, carry):
        m_prev, z_prev, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            for_live_pages(blk + 1, 1 - slot, lambda dma: dma.start())

        for_live_pages(blk, slot, lambda dma: dma.wait())
        kr = rbuf[slot].reshape(C, lanes)
        live = kv_len - blk * (P * page)
        parts = []
        for j in range(f):  # static
            c = cbuf[slot, :, j * rows:(j + 1) * rows, :].reshape(C, r)
            s = (
                lax.dot_general(
                    ql, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                + lax.dot_general(
                    qr[j * H:(j + 1) * H], kr, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            ) * scale  # [H, C]
            parts.append((c, jnp.where(col_pos + j * rows < live, s, -1e30)))
        m_new = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for _, s in parts], m_prev
        )
        alpha = jnp.exp(m_prev - m_new)
        z_new, acc = z_prev * alpha, acc * alpha
        for c, s in parts:
            # p in the cache's dtype before the value product, z from the
            # rounded p: the law of model.mla_merged_decode_attention
            p = jnp.exp(s - m_new).astype(c.dtype)
            z_new = z_new + jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
            acc = acc + lax.dot_general(
                p, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
        return m_new, z_new, acc

    m, z, acc = lax.fori_loop(
        0, n_blocks, block,
        (
            # the -1e29 floor of a fully masked row is where m starts
            jnp.full((H, 1), -1e29, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, r), jnp.float32),
        ),
    )
    o_ref[0] = acc
    m_ref[0] = m
    z_ref[0] = z


@functools.partial(
    jax.jit, static_argnames=("scale", "wpages", "interpret", "pages_per_block")
)
def latent_decode_attention_pallas(
    q_lat: jax.Array,  # [B, H, r] the absorbed query, against c itself
    q_rope: jax.Array,  # [B, H, dr]
    pool_c: jax.Array,  # [L, N, 1, page, r] the WHOLE c side, as it lies
    pool_r: jax.Array,  # [L, N, 1, page, dr] the rope side, or its latent_rope_view
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    tables: jax.Array,  # [B, Pmax] int32 block tables
    base_lens: jax.Array,  # [B]
    *,
    scale: float,  # 1 / sqrt(dn + dr): the scores' law, whatever is absorbed
    wpages: int,
    interpret: bool = False,
    pages_per_block: int = LATENT_DECODE_PAGES_PER_BLOCK,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The absorbed read of the main cache → (o [B,H,r] f32 unnormalized,
    m [B,H] f32, z [B,H] f32): the first source of
    ``model.mla_merged_decode_attention``.

    Reads each row's LIVE pages in place (:func:`_latent_decode_kernel`):
    both sides go in whole and stay in HBM, the layer is an index, the grid
    is the rows alone, and the work of a row follows ``base_lens[b]``:
    ``wpages`` is only the static upper bound.  ``c``, ``k_rope`` and the
    queries meet the MXU in the cache's dtype with float32 accumulation.

    A caller in a loop passes the rope side's :func:`latent_rope_view`,
    made outside the loop; a side passed as it lies is viewed here, per call."""
    B, H, r = q_lat.shape
    dr = q_rope.shape[-1]
    page = pool_c.shape[3]
    if not latent_decode_in_place_ok(r, dr, page, pool_c.dtype):
        raise PallasShapeError(
            f"the latent decode kernel copies a page slab whole: a latent of "
            f"{r} | {dr} on pages of {page} {pool_c.dtype} positions is not "
            "whole (8, 128) tiles (latent_decode_in_place_ok)"
        )
    if pool_r.shape[3] == page:
        pool_r = latent_rope_view(pool_r)
    rows, lanes = pool_r.shape[3:]
    f = lanes // dr
    _note_trace("latent_decode", interpret)
    P = max(1, min(pages_per_block, wpages))
    kernel = functools.partial(_latent_decode_kernel, wpages=wpages, scale=scale)

    def row_map(b, *_refs):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, r), row_map),
            pl.BlockSpec((1, f * H, lanes), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, r), row_map),
            pl.BlockSpec((1, H, 1), row_map),
            pl.BlockSpec((1, H, 1), row_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, P, page, r), pool_c.dtype),
            pltpu.VMEM((2, P, rows, lanes), pool_r.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    o, m, z = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, r), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            # rows in order: the scratch cleared by row 0 serves them all
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="latent_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32),
        base_lens.astype(jnp.int32),
        q_lat.astype(pool_c.dtype),
        _lane_block_copies(q_rope.astype(pool_r.dtype), f),
        pool_c, pool_r,
    )
    return o, m[..., 0], z[..., 0]


@jax.named_scope("attention")
def merged_latent_decode_attention_pallas(
    q_lat: jax.Array,  # [B, 1, H, r]
    q_rope: jax.Array,  # [B, 1, H, dr]
    pool_c: jax.Array,  # [L, N, 1, page, r]
    pool_r: jax.Array,  # the rope side, or its latent_rope_view
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, Pmax]
    ring: tuple[jax.Array, jax.Array],  # ([T, B, 1, r], [T, B, 1, dr]) this layer's ring
    base_lens: jax.Array,  # [B]
    t: jax.Array,
    *,
    scale: float,
    wpages: int,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for ``model.mla_merged_decode_attention`` over a gathered
    window → ``sum_t P_t c_t`` [B, 1, H, r] float32: the main-cache source
    from the kernel, the (tiny) ring folded in via the same logsumexp merge
    in plain XLA."""
    from calfkit_tpu.inference.model import logsumexp_merge, mla_ring_attention_source

    q_lat, q_rope = q_lat[:, 0], q_rope[:, 0]
    o1, m1, z1 = latent_decode_attention_pallas(
        q_lat, q_rope, pool_c, pool_r, layer, tables, base_lens,
        scale=scale, wpages=wpages, interpret=interpret,
    )
    source2 = mla_ring_attention_source(q_lat, q_rope, ring, t, scale)
    return logsumexp_merge((o1, m1[..., None], z1[..., None]), source2)[:, None]


# --------------------------------------------------------------------------- #
# a window stack's prefill chunk: the scores stay in VMEM
# --------------------------------------------------------------------------- #

# query positions a program holds at once (x the G query heads of its KV head)
# and keys a block: the scores of one grid step are [G * tile, block] float32,
# 8 MB at 16 heads (tuned on the chip, PERF.md section 6, PR 39: a block of
# 1,024 walks more keys past a window's edges than one of 512 and is still a
# fifth faster, for the statistics and the output tile are rescaled once a
# step).  A chunk or a scratch that is not a multiple takes the largest tile
# that divides it (:func:`chunk_attention_tiles`).
CHUNK_ATTN_QUERY_TILE = 128
CHUNK_ATTN_KEY_BLOCK = 1024
# the kernel's VMEM: scores, ``p`` and their temporaries at G * tile = 2,048
# rows are past the 16 MiB a kernel gets by default (a v5e core has 128 MiB)
CHUNK_ATTN_VMEM_BYTES = 64 * 1024 * 1024


def chunk_attention_tiles(chunk: int, scratch: int) -> tuple[int, int]:
    """(query tile, key block) the chunk kernel runs a chunk of ``chunk``
    queries against a scratch of ``scratch`` positions with: the module's
    tile sizes, or the largest divisor of them that divides the shape (as
    ``model.blocked_attention`` takes its key block)."""
    return math.gcd(chunk, CHUNK_ATTN_QUERY_TILE), math.gcd(scratch, CHUNK_ATTN_KEY_BLOCK)


def chunk_attention_ok(head_dim: int, chunk: int, scratch: int, dtype) -> bool:
    """Whether :func:`_chunk_attention_kernel` can take these shapes on a
    TPU: a head of whole lane tiles (128), a query tile of whole sublane
    tiles of the cache's dtype (the G heads of a tile are stacked along
    them), a key block of whole lane tiles (it is the scores' minor
    dimension).  What fails this runs ``model.blocked_attention``."""
    from calfkit_tpu.inference.model import sublane_tile

    tile, block = chunk_attention_tiles(chunk, scratch)
    return head_dim % 128 == 0 and tile % sublane_tile(dtype) == 0 and block % 128 == 0


def chunk_attention_bounds(
    q_start: jax.Array,  # [B] the position of each row's first query
    seq_lens: jax.Array,  # [B] valid keys a row
    *, chunk: int, scratch: int, window: int, tile: int, block: int, xp: Any = jnp,
) -> tuple[Any, Any]:
    """The key blocks each query tile walks -> (first [B, nQ], count [B, nQ]).

    The first block holds the window's lower bound of the tile's FIRST query
    (``q - window + 1``; block 0 without a window), the last the causal bound
    of the tile's LAST query or the row's last valid key, whichever is lower.
    Blocks outside are neither copied nor computed; the blocks on an edge
    are masked by position in the kernel.  ``xp``: numpy for the host's own
    count of the walk (:func:`chunk_attention_work`)."""
    q_first = q_start[:, None] + tile * xp.arange(chunk // tile, dtype=xp.int32)[None, :]
    last_key = xp.minimum(q_first + tile - 1, seq_lens[:, None] - 1)
    first = xp.maximum(q_first - window + 1, 0) // block if window else xp.zeros_like(q_first)
    first = xp.minimum(first, scratch // block - 1)
    count = xp.maximum(last_key // block - first + 1, 0)  # (a floor: -1 // block is -1)
    steps = chunk_attention_key_steps(chunk, scratch, window, tile, block)
    return first.astype(xp.int32), xp.minimum(count, steps).astype(xp.int32)


def chunk_attention_key_steps(chunk: int, scratch: int, window: int, tile: int, block: int) -> int:
    """The grid's key axis: the most blocks one tile can need.  Without a
    window that is every block of the scratch; with one, the blocks a span of
    ``window + tile - 1`` consecutive keys can touch wherever it starts."""
    blocks = scratch // block
    if not window:
        return blocks
    return min(blocks, (window + tile - 1 + block - 2) // block + 1)


def chunk_attention_work(
    offset: int, chunk: int, scratch: int, true_lens: np.ndarray, window: int,
    window_layers: int, global_layers: int,
) -> tuple[int, int, int, int]:
    """What a launched chunk's attention HAS to compute and what walks it,
    on the host (numpy, no device work) -> (pairs a window layer needs x
    window layers, pairs a global layer needs x global layers, (query tile,
    key block) steps the kernel's bounds walk, the same steps as
    ``model.blocked_attention``'s loop walks them; both over every layer).

    The pairs are those of the rows' OWN positions, ``offset <= q <
    min(offset + chunk, true_len)``: ``sum_q min(q + 1, window)`` and
    ``sum_q (q + 1)``: the needed work, never the visited.  The walks are
    over the chunk as the programs run it (every row to ``offset + chunk``,
    padding included), with the tiles of :func:`chunk_attention_tiles`."""
    lens = true_lens.astype(np.int64)  # the host's own array: no device value comes here
    end = np.clip(lens, offset, offset + chunk)  # own positions: [offset, end), none where equal

    def below(n):  # sum of (q + 1) over q < n
        return n * (n + 1) // 2

    pairs_global = int(np.sum(below(end) - below(offset)))
    # a query under the window attends q + 1 keys, every later one the window
    under, first = np.minimum(end, window), min(offset, window)
    pairs_window = int(np.sum(
        below(under) - below(first) + (end - offset - (under - first)) * window))
    tile, block = chunk_attention_tiles(chunk, scratch)
    at = np.full((1,), offset, np.int64)  # every row of a wave runs the same walk
    visited = dense = 0
    for w, layers in ((window, window_layers), (0, global_layers)):
        first_block, count = chunk_attention_bounds(
            at, at + chunk, chunk=chunk, scratch=scratch, window=w, tile=tile, block=block, xp=np)
        loop = -(-(offset + chunk) // block) - int(first_block[0, 0])  # the chunk's span
        visited += layers * len(lens) * int(count.sum())
        dense += layers * len(lens) * (chunk // tile) * loop
    return pairs_window * window_layers, pairs_global * global_layers, visited, dense


def _chunk_attention_kernel(
    first_ref, count_ref, lens_ref, starts_ref,  # scalar-prefetch (SMEM)
    q_ref,  # [1, 1, G, tile, hd]
    k_ref, v_ref,  # [1, 1, block, hd]: key block first + kj of this row and kv head
    o_ref,  # [1, 1, G, tile, hd]
    m_sc, z_sc, acc_sc,  # VMEM scratch, carried across the key axis
    *, window: int, scale: float,
):
    """One (row, kv head, query tile) of a chunk's attention, a KEY BLOCK a
    grid step: the G query heads of the kv head stacked over the tile's
    positions score the block in ONE product ``[G * tile, hd] x [block, hd]``,
    so a key block copied once serves them all.  The running maximum, the sum
    and the output tile live in VMEM scratch across the key axis (the flash
    law of ``model.blocked_attention``, with its roundings: bf16 operands,
    float32 accumulation, scores and statistics float32, ``p`` in the cache's
    type before the PV product, ``z`` from the rounded ``p``); the scores
    never leave VMEM.

    Steps past the tile's ``count`` do nothing: their index map names the
    block already there, so nothing is copied either.  A row's queries are
    consecutive positions from ``starts[b]``."""
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    G, tile, hd = q_ref.shape[2:]
    block = k_ref.shape[2]
    rows = G * tile

    @pl.when(kj == 0)
    def _init():
        # the -1e29 floor of a fully masked query is where m starts
        m_sc[...] = jnp.full(m_sc.shape, -1e29, jnp.float32)
        z_sc[...] = jnp.zeros(z_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(kj < count_ref[b, qi])
    def _block():
        q = q_ref[0, 0].reshape(rows, hd)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [rows, block]
        q_pos = starts_ref[b] + qi * tile + lax.broadcasted_iota(jnp.int32, (tile, block), 0)
        k_pos = (first_ref[b, qi] + kj) * block + lax.broadcasted_iota(
            jnp.int32, (tile, block), 1)
        valid = (k_pos <= q_pos) & (k_pos < lens_ref[b])
        if window:
            valid = valid & (k_pos > q_pos - window)
        # one mask a position, whatever the head
        s = jnp.where(valid[None], s.reshape(G, tile, block), -1e30).reshape(rows, block)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new).astype(v.dtype)
        z_sc[...] = z_sc[...] * alpha + jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _out():
        out = acc_sc[...] / jnp.maximum(z_sc[...], 1e-30)
        o_ref[0, 0] = out.reshape(G, tile, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "tile", "block"))
def chunk_attention_pallas(
    q: jax.Array,  # [B, S, H, hd]
    k_cache: jax.Array,  # [B, K, P, hd] ONE layer's prefill scratch as it lies
    v_cache: jax.Array,
    q_start: jax.Array,  # [B] the position of each row's first query
    seq_lens: jax.Array,  # [B] valid keys a row
    *,
    window: int = 0,  # > 0: query i sees key j iff i - window < j <= i
    interpret: bool = False,
    tile: int = 0,  # 0: chunk_attention_tiles
    block: int = 0,
) -> jax.Array:
    """A prefill chunk's GQA attention -> [B, S, H, hd] in ``q``'s type:
    ``model.blocked_attention`` as ONE kernel (:func:`_chunk_attention_kernel`).

    A row's S queries are CONSECUTIVE positions from ``q_start[b]`` (a
    chunk's are: offset + 0 .. S - 1), which is what lets a tile's bounds be
    two scalars (:func:`chunk_attention_bounds`).  The grid is (row, kv head,
    query tile, key block), the key block innermost and sequential; the two
    static forms are with and without a lower bound."""
    B, S, H, hd = q.shape
    K, P = k_cache.shape[1:3]
    G = H // K
    auto = chunk_attention_tiles(S, P)
    tile, block = tile or auto[0], block or auto[1]
    if S % tile or P % block or not (interpret or chunk_attention_ok(hd, S, P, k_cache.dtype)):
        raise PallasShapeError(
            f"the chunk attention kernel takes a head of whole lane tiles, a chunk of "
            f"whole query tiles and a scratch of whole key blocks: head_dim={hd}, "
            f"{S} queries in tiles of {tile}, {P} keys in blocks of {block}, "
            f"{k_cache.dtype} (chunk_attention_ok)"
        )
    _note_trace("chunk_attention", interpret)
    q_start, seq_lens = q_start.astype(jnp.int32), seq_lens.astype(jnp.int32)
    first, count = chunk_attention_bounds(
        q_start, seq_lens, chunk=S, scratch=P, window=window, tile=tile, block=block)
    steps = chunk_attention_key_steps(S, P, window, tile, block)

    def tile_map(b, k, qi, kj, *_refs):
        return (b, k, 0, qi, 0)

    def key_map(b, k, qi, kj, first_ref, count_ref, *_refs):
        # a step past the tile's count names the block already there
        live = jnp.minimum(kj, jnp.maximum(count_ref[b, qi] - 1, 0))
        return (b, k, first_ref[b, qi] + live, 0)

    rows = G * tile
    out = pl.pallas_call(
        functools.partial(_chunk_attention_kernel, window=window, scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, K, S // tile, steps),
            in_specs=[
                pl.BlockSpec((1, 1, G, tile, hd), tile_map),
                pl.BlockSpec((1, 1, block, hd), key_map),
                pl.BlockSpec((1, 1, block, hd), key_map),
            ],
            out_specs=pl.BlockSpec((1, 1, G, tile, hd), tile_map),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, S, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_ATTN_VMEM_BYTES,
        ),
        interpret=interpret,
        name="chunk_attention",
    )(
        first, count, seq_lens, q_start,
        # the G query heads of a kv head side by side over the positions
        q.reshape(B, S, K, G, hd).transpose(0, 2, 3, 1, 4).astype(k_cache.dtype),
        k_cache, v_cache,
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
