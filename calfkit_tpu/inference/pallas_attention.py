"""Pallas TPU kernels for GQA attention over the KV cache.

Why kernels: the XLA einsum path maps GQA decode badly — per (batch, kv
head) the score matmul is [G, hd] × [hd, W], a sliver of the 128×128 MXU —
and its paged read copies every row's whole window out of the pool before
attending it.  A kernel streams the cache through VMEM once and fuses mask +
softmax statistics + weighted sum, so HBM traffic is one read of K/V.

Four kernel bodies:

- ragged over a dense window (:func:`ragged_attention_pallas`),
- ragged through the block tables (:func:`ragged_attention_paged_pallas`),
- prefill over the chunk-updated scratch (:func:`prefill_attention_pallas`),
  these three on one flash-accumulation core (:func:`_flash_update`);
- single-query paged decode that reads each row's LIVE pages in place
  (:func:`paged_decode_attention_pallas`): one program a row, a loop over
  that row's own pages with double-buffered whole-slab copies out of the
  pool in HBM, bf16 operands into the MXU.  Its work follows the row
  lengths, not the window bucket.  A head that divides a lane tile (64)
  is read ``f = 128 / hd`` positions a lane row, through a lane-dense view
  of the pool (:func:`lane_dense_pool`) that a caller in a loop makes once.

Dense single-query decode is the S=1 row of the ragged law
(:func:`decode_attention_pallas`), and so is paged decode at a head or page
shape outside :func:`paged_decode_in_place_ok`.

The source kernels return *unnormalized* output plus the softmax statistics
``(m, z)`` so the caller can fold in the fresh-token ring / verify chunk
(tiny, plain XLA) with the logsumexp merge the XLA path uses.

What the TPU lowering demands, and how every kernel here meets it:
per-row scalars (lengths, starts, block tables, the layer index) ride
``PrefetchScalarGridSpec`` into SMEM — a ``(1,)`` block of a ``[B]`` array is
refused; every VMEM block's last two dims equal the array's or are
(8, 128)-aligned; in the ragged and prefill kernels kv is a sequential grid
axis with VMEM scratch carrying the statistics, so VMEM use is independent
of the window.

Status: every entry point AOT-compiles for a described v5e at
TinyLlama-1.1B and Llama-3-8B widths, the paged decode read also at
Mistral-7B's, InternLM2-1.8B's and granite-4.0-h-micro's
(``tests/test_tpu_compile.py``), and agrees with interpret mode and the XLA
path on CPU.  ``attention_impl="auto"`` selects the paged decode read in
place on a TPU (one device, heads of whole lane tiles or of a width that
divides one: ``InferenceEngine._resolved_attn_impl``; PERF.md section 6,
PRs 25 and 28, has the chip's numbers) and XLA for every other path
(docs/inference.md); ``"pallas"`` opts in everywhere.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (kernel, "compiled" | "interpreted") -> times TRACED: evidence for
# chip_smoke.py / tests that a "pallas" path really built Mosaic kernels
KERNEL_TRACES: collections.Counter = collections.Counter()


def _note_trace(kernel: str, interpret: bool) -> None:
    KERNEL_TRACES[kernel, "interpreted" if interpret else "compiled"] += 1


# kv positions streamed per grid step of the dense ragged kernel (the
# window is a power-of-two bucket, so divisibility holds; windows smaller
# than this run as one chunk)
RAGGED_KV_CHUNK = 512


def _flash_init(acc, m_s, z_s) -> None:
    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, -1e30)
    z_s[...] = jnp.zeros_like(z_s)


def _flash_update(q, k, v, mask, acc, m_s, z_s) -> None:
    """Fold one kv chunk into the running (acc, m, z) VMEM scratch.

    q [R, hd] / k, v [C, hd] f32 values; mask [R, C] (True = attendable);
    acc [R, hd], m_s / z_s [R, 1] refs carried across the kv grid axis."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [R, C]
    scores = jnp.where(mask, scores, -1e30)
    m_new = jnp.maximum(m_s[...], jnp.max(scores, axis=-1, keepdims=True))
    m_new = jnp.maximum(m_new, -1e29)  # all-masked rows stay finite
    alpha = jnp.exp(m_s[...] - m_new)
    pexp = jnp.exp(scores - m_new)
    z_s[...] = z_s[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    acc[...] = acc[...] * alpha + lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_s[...] = m_new


# --------------------------------------------------------------------------- #
# ragged unified attention: mixed decode / prefill-chunk / verify rows
# (ISSUE 6; the Ragged Paged Attention shape, arXiv:2604.15464)
# --------------------------------------------------------------------------- #


def _ragged_step(start, kv_len, q_ref, k, v, o_ref, m_ref, z_ref, acc, m_s, z_s):
    """One (batch row, kv head, kv chunk) program of either ragged kernel.

    The q block carries ALL of a row's queries (S = the wave's padded
    q_len — 1 for decode rows, chunk for prefill rows, k+1 for verify
    rows), flattened to [S·G, hd] so one MXU matmul scores every
    (query, group) pair against the kv chunk ``k``/``v`` [C, hd].  THE
    ragged mask law (see inference/ragged.py): query j attends kv positions
    < min(kv_len, start + j + 1).  Flash accumulation across the kv grid
    axis (innermost, sequential on TPU) in VMEM scratch — the window
    streams through VMEM exactly once for the whole multi-query block.
    """
    c = pl.program_id(2)
    S, G, hd = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    C = k.shape[0]

    @pl.when(c == 0)
    def _init():
        _flash_init(acc, m_s, z_s)

    q = q_ref[0, 0].astype(jnp.float32).reshape(S * G, hd)
    kv_pos = c * C + lax.broadcasted_iota(jnp.int32, (S * G, C), 1)
    j = lax.broadcasted_iota(jnp.int32, (S * G, C), 0) // G  # query index
    mask = kv_pos < jnp.minimum(kv_len, start + j + 1)
    _flash_update(
        q, k.astype(jnp.float32), v.astype(jnp.float32), mask, acc, m_s, z_s
    )

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0, 0] = acc[...].reshape(S, G, hd)
        m_ref[0, 0] = m_s[...].reshape(S, G)
        z_ref[0, 0] = z_s[...].reshape(S, G)


def _ragged_attn_kernel(
    starts_ref, lens_ref,  # scalar-prefetch (SMEM)
    q_ref, k_ref, v_ref, o_ref, m_ref, z_ref, acc, m_s, z_s,
):
    b = pl.program_id(0)
    _ragged_step(
        starts_ref[b], lens_ref[b], q_ref, k_ref[0, 0], v_ref[0, 0],
        o_ref, m_ref, z_ref, acc, m_s, z_s,
    )


def _ragged_paged_attn_kernel(
    layer_ref, tables_ref, starts_ref, lens_ref,  # scalar-prefetch (SMEM)
    q_ref, k_ref, v_ref, o_ref, m_ref, z_ref, acc, m_s, z_s,
):
    """Paged ragged program: the block table drives page DMA (it rides the
    K/V index_map) and every one of the row's S queries scores against each
    page as it streams through — nothing is gathered or materialized."""
    b = pl.program_id(0)
    _ragged_step(
        starts_ref[b], lens_ref[b], q_ref, k_ref[0, 0, 0], v_ref[0, 0, 0],
        o_ref, m_ref, z_ref, acc, m_s, z_s,
    )


def _ragged_out(B: int, K: int, S: int, G: int, hd: int):
    """(out_specs, out_shape, scratch_shapes) shared by both ragged calls."""

    def o_map(b, k, c, *_refs):
        return (b, k, 0, 0, 0)

    def stat_map(b, k, c, *_refs):
        return (b, k, 0, 0)

    return (
        [
            pl.BlockSpec((1, 1, S, G, hd), o_map),
            # (S, G) are the array's own last two dims — the layout the
            # TPU lowering accepts for a per-(row, head) statistic
            pl.BlockSpec((1, 1, S, G), stat_map),
            pl.BlockSpec((1, 1, S, G), stat_map),
        ],
        (
            jax.ShapeDtypeStruct((B, K, S, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, K, S, G), jnp.float32),
            jax.ShapeDtypeStruct((B, K, S, G), jnp.float32),
        ),
        [
            pltpu.VMEM((S * G, hd), jnp.float32),
            pltpu.VMEM((S * G, 1), jnp.float32),
            pltpu.VMEM((S * G, 1), jnp.float32),
        ],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_attention_pallas(
    q: jax.Array,  # [B, K, S, G, hd] kv-head-major ragged queries
    k_cache: jax.Array,  # [B, K, W, hd]
    v_cache: jax.Array,
    q_starts: jax.Array,  # [B] absolute position of each row's query 0
    kv_lens: jax.Array,  # [B] valid kv length each row may attend
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Ragged unified attention over a dense window → (o [B,K,S,G,hd] f32
    unnormalized, m [B,K,S,G], z [B,K,S,G]) — one kernel serving decode
    (S=1), prefill-chunk (S=chunk), and verify (S=k+1) rows through the
    shared mask law; the logsumexp merge composes on its output."""
    _note_trace("ragged", interpret)
    B, K, S, G, hd = q.shape
    W = k_cache.shape[2]
    kv_chunk = min(RAGGED_KV_CHUNK, W)
    if W % kv_chunk:
        kv_chunk = W  # non-power-of-two window: stream it whole

    out_specs, out_shape, scratch = _ragged_out(B, K, S, G, hd)
    kv_spec = pl.BlockSpec(
        (1, 1, kv_chunk, hd), lambda b, k, c, *_refs: (b, k, c, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, W // kv_chunk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, S, G, hd), lambda b, k, c, *_refs: (b, k, 0, 0, 0)
            ),
            kv_spec,
            kv_spec,
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        _ragged_attn_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="ragged_attention",
    )(
        q_starts.astype(jnp.int32), kv_lens.astype(jnp.int32),
        q, k_cache, v_cache,
    )


@functools.partial(jax.jit, static_argnames=("wpages", "interpret"))
def ragged_attention_paged_pallas(
    q: jax.Array,  # [B, K, S, G, hd]
    pool_k: jax.Array,  # [L, N, K, page, hd] the WHOLE pool (no slicing)
    pool_v: jax.Array,
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, Pmax] int32 block tables
    q_starts: jax.Array,  # [B]
    kv_lens: jax.Array,  # [B]
    *,
    wpages: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Ragged unified attention through the block tables → (o, m, z), the
    paged analog of :func:`ragged_attention_pallas`.

    Taking the full pool (not a sliced layer) matters: slicing
    ``pool[layer]`` in XLA before a pallas_call would materialize a copy of
    the layer's pages every (layer, step); here the layer index rides the
    index_map and only the addressed pages move."""
    _note_trace("ragged_paged", interpret)
    B, K, S, G, hd = q.shape
    page = pool_k.shape[3]

    out_specs, out_shape, scratch = _ragged_out(B, K, S, G, hd)
    kv_spec = pl.BlockSpec(
        (1, 1, 1, page, hd),
        lambda b, k, p, layer_ref, tables_ref, starts_ref, lens_ref: (
            layer_ref[0], tables_ref[b, p], k, 0, 0
        ),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, K, wpages),
        in_specs=[
            pl.BlockSpec(
                (1, 1, S, G, hd), lambda b, k, p, *_refs: (b, k, 0, 0, 0)
            ),
            kv_spec,
            kv_spec,
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        _ragged_paged_attn_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="ragged_paged_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32),
        q_starts.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        q, pool_k, pool_v,
    )


# --------------------------------------------------------------------------- #
# single-query decode: the S=1 row of the ragged law (start = kv_len)
# --------------------------------------------------------------------------- #


def decode_attention_pallas(
    q: jax.Array,  # [B, K, G, hd]
    k_cache: jax.Array,  # [B, K, W, hd]
    v_cache: jax.Array,  # [B, K, W, hd]
    base_lens: jax.Array,  # [B] valid kv per row
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """→ (o [B,K,G,hd] f32 unnormalized, m [B,K,G] f32, z [B,K,G] f32)."""
    o, m, z = ragged_attention_pallas(
        q[:, :, None], k_cache, v_cache, base_lens, base_lens,
        interpret=interpret,
    )
    return o[:, :, 0], m[:, :, 0], z[:, :, 0]


def paged_decode_lane_pack(head_dim: int) -> int:
    """Positions of one kv head that share a 128-lane row in the paged decode
    kernel's view of the pool: ``128 / head_dim`` for a head that divides a
    lane tile, 1 for every other head (whole lane tiles read as they lie)."""
    return 128 // head_dim if 128 % head_dim == 0 else 1


def paged_decode_in_place_ok(head_dim: int, page: int, dtype) -> bool:
    """Whether :func:`_paged_decode_kernel` can take these shapes on a TPU:
    a page slab is copied and flattened whole, so a slab row is whole lane
    tiles (128: a head of whole tiles, or ``f`` positions of a head that
    divides one, :func:`paged_decode_lane_pack`) and the page, ``f``
    positions a row, whole sublane tiles of the cache's dtype (8 rows of 32
    bits: 16 for bf16).  What fails this runs the S = 1 row of the ragged
    paged kernel under ``"pallas"`` and XLA under ``"auto"``."""
    sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    f = paged_decode_lane_pack(head_dim)
    return (f * head_dim) % 128 == 0 and page % (f * sublanes) == 0


def lane_dense_pool(pool_side: jax.Array) -> jax.Array:
    """One side of the pool as the paged decode kernel reads it:
    ``[L, N, K, page / f, f * hd]``, row r of a page holding positions
    ``f * r .. f * r + f - 1`` side by side.

    At ``f == 1``, and for shapes outside :func:`paged_decode_in_place_ok`,
    this IS the pool (the same array, no operation).  For a narrower head it
    is a relayout.  A ``[L, N, K, page, 64]`` array does not lie row-major
    and lane-dense in a TPU's HBM: compiled for the v5e, the engine's
    programs hold the pool with the page INDEX minor-most, the row-major
    layout Mosaic asks of an operand pads the last dimension to 128 lanes,
    and a ``[K, page, 64]`` slab cannot be sliced out of that (PERF.md
    section 6, PR 28).  The copy reads and writes the whole side, so a
    caller in a loop makes it ONCE, outside: the engine does, per dispatch
    (``InferenceEngine._decode_fn_paged``).  No scope of its own: XLA's copy
    keeps none, and a device trace lists it under ``(unscoped) copy``."""
    L, N, K, page, hd = pool_side.shape
    f = paged_decode_lane_pack(hd)
    if f == 1 or not paged_decode_in_place_ok(hd, page, pool_side.dtype):
        return pool_side
    return pool_side.reshape(L, N, K, page // f, f * hd)


# pages of one row folded per compute block of the paged decode kernel
# (tuned on the chip, PERF.md section 6): the scores of a block are one
# [H, PAGES_PER_BLOCK * K * page] product
PAGED_DECODE_PAGES_PER_BLOCK = 2


def _paged_decode_kernel(
    layer_ref, tables_ref, lens_ref,  # scalar-prefetch (SMEM)
    q_ref, pool_k, pool_v,  # q block in VMEM; the pools stay in HBM
    o_ref, m_ref, z_ref,
    kbuf, vbuf, sems,
    *, wpages: int, group: int, pack: int,
):
    """One ROW of a paged decode step: a loop over that row's own live
    pages, ``ceil(len / page)`` of them, each fetched as its whole
    ``[K, page / f, f * hd]`` slab (contiguous in the pool) by a
    double-buffered async copy.  Nothing past the row's length is read or
    computed; a row of length 0 starts no copy at all.

    All K heads of a block are scored in ONE product: q is the row's
    ``[H, hd]`` (H = K * G, a whole sublane tile where G alone is not),
    the block's K slab is ``[P * K * page, hd]``, and a static
    block-diagonal mask keeps query head h on the columns of its own kv
    head.  The masked columns weigh exactly 0 in ``p``, so the PV product
    over the same flattened axis is each head's own weighted sum.

    ``pack`` (f) > 1 is a head narrower than a lane tile, read through
    :func:`lane_dense_pool`: a slab row holds f positions side by side, and
    the row's queries come f times over, copy j with q in lane block j and
    zeros elsewhere.  Copy j's scores are then those of positions
    ``f * c + j`` and lane block j of its PV rows their weighted sum (the
    other blocks are dropped): the same body at ``f * H`` rows and 128
    lanes, and the f partial results folded by the logsumexp law at the end.
    """
    b = pl.program_id(0)
    P, K, rows, lanes = kbuf.shape[1:]  # a slab: rows of pack positions
    page = rows * pack
    H = q_ref.shape[1]  # pack copies of the row's query heads
    heads = H // pack
    C = P * K * rows
    layer = layer_ref[0]
    kv_len = lens_ref[b]
    n_pages = jnp.minimum(pl.cdiv(kv_len, page), wpages)
    n_blocks = pl.cdiv(n_pages, P)

    def copies(blk, slot, i):
        n = tables_ref[b, blk * P + i]
        return (
            pltpu.make_async_copy(
                pool_k.at[layer, n], kbuf.at[slot, i], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                pool_v.at[layer, n], vbuf.at[slot, i], sems.at[1, slot]
            ),
        )

    def for_live_pages(blk, slot, act):
        for i in range(P):  # static: a partial last block skips its tail
            @pl.when(blk * P + i < n_pages)
            def _():
                for dma in copies(blk, slot, i):
                    act(dma)

    if P > 1:
        # a partial last block leaves buffer pages no copy ever wrote:
        # their columns are masked (p = 0), and 0 x garbage must stay 0
        @pl.when(b == 0)
        def _clear():
            vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(n_blocks > 0)
    def _first():
        for_live_pages(0, 0, lambda dma: dma.start())

    q = q_ref[0]  # [H, lanes], the cache's dtype
    scale = 1.0 / math.sqrt(lanes // pack)  # the law of the REAL head
    col = lax.broadcasted_iota(jnp.int32, (H, C), 1)
    row = lax.broadcasted_iota(jnp.int32, (H, C), 0)
    own_head = (col // rows) % K == (row if pack == 1 else row % heads) // group
    col_pos = (col // (K * rows)) * rows + col % rows  # slab row in block
    if pack > 1:
        col_pos = col_pos * pack + row // heads  # copy j: positions f*c + j

    def block(blk, carry):
        m_prev, z_prev, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            for_live_pages(blk + 1, 1 - slot, lambda dma: dma.start())

        for_live_pages(blk, slot, lambda dma: dma.wait())
        k = kbuf[slot].reshape(C, lanes)
        v = vbuf[slot].reshape(C, lanes)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [H, C]
        mask = own_head & (col_pos < kv_len - blk * (P * page))
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
        return m_new, z_new, acc

    m, z, acc = lax.fori_loop(
        0, n_blocks, block,
        (
            # the -1e29 floor of a fully masked row is where m starts
            jnp.full((H, 1), -1e29, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, lanes), jnp.float32),
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
    jax.jit, static_argnames=("wpages", "interpret", "pages_per_block")
)
def paged_decode_attention_pallas(
    q: jax.Array,  # [B, K, G, hd]
    pool_k: jax.Array,  # [L, N, K, page, hd] the WHOLE pool (no slicing),
    pool_v: jax.Array,  # or its lane_dense_pool view
    layer: jax.Array,  # scalar int32 — which layer's pages to read
    tables: jax.Array,  # [B, Pmax] int32 block tables
    base_lens: jax.Array,  # [B]
    *,
    wpages: int,
    interpret: bool = False,
    pages_per_block: int = PAGED_DECODE_PAGES_PER_BLOCK,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Paged decode attention → (o unnormalized, m, z), same contract as
    the dense single-query entry point.

    Reads each row's LIVE pages in place (:func:`_paged_decode_kernel`):
    the pool goes in whole and stays in HBM, the grid is the rows alone,
    and the work of a row follows ``base_lens[b]`` — ``wpages`` is only the
    static upper bound, so one kernel serves every window bucket.  K, V
    and q meet the MXU in the cache's dtype with float32 accumulation.

    A head narrower than a lane tile is read through
    :func:`lane_dense_pool`.  A caller in a loop passes that view, made
    outside the loop; a pool passed as it lies is viewed here, per call."""
    B, K, G, hd = q.shape
    H = K * G
    pool_k, pool_v = lane_dense_pool(pool_k), lane_dense_pool(pool_v)
    rows, lanes = pool_k.shape[3:]
    f = lanes // hd
    if not paged_decode_in_place_ok(hd, rows * f, pool_k.dtype):
        o, m, z = ragged_attention_paged_pallas(
            q[:, :, None], pool_k, pool_v, layer, tables, base_lens,
            base_lens, wpages=wpages, interpret=interpret,
        )
        return o[:, :, 0], m[:, :, 0], z[:, :, 0]
    _note_trace("paged_decode", interpret)
    P = max(1, min(pages_per_block, wpages))
    kernel = functools.partial(
        _paged_decode_kernel, wpages=wpages, group=G, pack=f
    )

    def row_map(b, *_refs):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
            pltpu.VMEM((2, P, K, rows, lanes), pool_k.dtype),
            pltpu.VMEM((2, P, K, rows, lanes), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
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
            # rows in order: the scratch cleared by row 0 serves them all
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32),
        base_lens.astype(jnp.int32),
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
    pool_k: jax.Array,  # [L, N, K, page, hd]
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
    """Paged analog of :func:`merged_decode_attention_pallas`: main-cache
    source from the paged kernel, ring folded in via the shared merge."""
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


@jax.named_scope("attention")
def merged_decode_attention_pallas(
    q: jax.Array,  # [B, 1, H, hd]
    k_cache: jax.Array,  # [B, K, W, hd]
    v_cache: jax.Array,
    ring_k: jax.Array,  # [T, B, K, hd]
    ring_v: jax.Array,
    base_lens: jax.Array,  # [B]
    t: jax.Array,  # current ring step
    *,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for :func:`model._merged_decode_attention` with the main-cache
    source computed by the Pallas kernel and the (tiny) ring folded in via
    the same logsumexp merge in plain XLA."""
    from calfkit_tpu.inference.model import logsumexp_merge, ring_attention_source

    B, _, H, hd = q.shape
    K = k_cache.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, hd)

    o1, m1, z1 = decode_attention_pallas(
        qg, k_cache, v_cache, base_lens, interpret=interpret
    )
    o2, m2, z2 = ring_attention_source(qg, ring_k, ring_v, t)
    out = logsumexp_merge((o1, m1[..., None], z1[..., None]), (o2, m2, z2))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# speculative verify: k+1 queries per row against (main cache ⊕ chunk)
# --------------------------------------------------------------------------- #


@jax.named_scope("attention")
def verify_attention_pallas(
    q: jax.Array,  # [B, S, H, hd] the verify chunk's queries
    k_cache: jax.Array,  # [B, K, W, hd] main-cache window
    v_cache: jax.Array,
    chunk_k: jax.Array,  # [S, B, K, hd] this layer's chunk K (ring layout)
    chunk_v: jax.Array,
    base_lens: jax.Array,  # [B]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Multi-query verify attention on the Pallas lane.

    ONE ragged-kernel call scores all S = k+1 queries against the window
    (one window DMA amortized over the whole block — the Ragged Paged
    Attention shape this used to decompose into S single-query calls);
    the (tiny) chunk's causal self-attention folds in via the shared
    logsumexp merge, exactly like the XLA path.  The verify rows reduce
    to the ragged law with start = kv_len = base_lens.
    """
    from calfkit_tpu.inference.model import logsumexp_merge, verify_chunk_source

    B, S, H, hd = q.shape
    K = k_cache.shape[1]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    o1, m1, z1 = ragged_attention_pallas(
        jnp.transpose(qg, (0, 2, 1, 3, 4)), k_cache, v_cache,
        base_lens, base_lens, interpret=interpret,
    )  # [B, K, S, G, hd] / [B, K, S, G] x2 → merge layout [B, K, G, S, ·]
    o1 = jnp.transpose(o1, (0, 1, 3, 2, 4))
    m1 = jnp.transpose(m1, (0, 1, 3, 2))[..., None]
    z1 = jnp.transpose(z1, (0, 1, 3, 2))[..., None]
    o2, m2, z2 = verify_chunk_source(qg, chunk_k, chunk_v)
    out = logsumexp_merge((o1, m1, z1), (o2, m2, z2))  # [B, K, G, S, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


@jax.named_scope("attention")
def verify_attention_paged_pallas(
    q: jax.Array,  # [B, S, H, hd]
    pool_k: jax.Array,  # [L, N, K, page, hd]
    pool_v: jax.Array,
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, Pmax]
    chunk_k: jax.Array,  # [S, B, K, hd]
    chunk_v: jax.Array,
    base_lens: jax.Array,
    *,
    wpages: int,
    interpret: bool = False,
) -> jax.Array:
    """Paged analog of :func:`verify_attention_pallas`: one ragged
    block-table kernel call reads each page exactly once for all S
    queries; the chunk folds in as the second source."""
    from calfkit_tpu.inference.model import logsumexp_merge, verify_chunk_source

    B, S, H, hd = q.shape
    K = pool_k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    o1, m1, z1 = ragged_attention_paged_pallas(
        jnp.transpose(qg, (0, 2, 1, 3, 4)), pool_k, pool_v, layer, tables,
        base_lens, base_lens, wpages=wpages, interpret=interpret,
    )
    o1 = jnp.transpose(o1, (0, 1, 3, 2, 4))
    m1 = jnp.transpose(m1, (0, 1, 3, 2))[..., None]
    z1 = jnp.transpose(z1, (0, 1, 3, 2))[..., None]
    o2, m2, z2 = verify_chunk_source(qg, chunk_k, chunk_v)
    out = logsumexp_merge((o1, m1, z1), (o2, m2, z2))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# prefill: flash attention over the (chunk-updated) cache
# --------------------------------------------------------------------------- #

# shared with model.prefill_attention's eligibility check — retune in ONE
# place after hardware profiling
PREFILL_BLOCK_Q = 128
PREFILL_KV_CHUNK = 512


class PallasShapeError(ValueError):
    """``attention_impl="pallas"`` was asked for shapes the kernel's block
    grammar cannot tile.  Raised while the jit is traced — a kernel request
    is never quietly served by the XLA path."""


def prefill_blocks(
    Sq: int, Skv: int,
    block_q: int = PREFILL_BLOCK_Q, kv_chunk: int = PREFILL_KV_CHUNK,
) -> tuple[int, int]:
    """(block_q, kv_chunk) for a prefill of Sq queries over Skv cache
    positions, or :class:`PallasShapeError` when they do not tile."""
    block_q = min(block_q, Sq)
    kv_chunk = min(kv_chunk, Skv)
    if Sq % block_q or Skv % kv_chunk:
        raise PallasShapeError(
            f"pallas prefill attention cannot tile Sq={Sq} by "
            f"block_q={block_q} and Skv={Skv} by kv_chunk={kv_chunk}: pick "
            f"a prefill_chunk / bucket that is a multiple of "
            f"{PREFILL_BLOCK_Q} / {PREFILL_KV_CHUNK} (or smaller than it), "
            'or attention_impl="xla"'
        )
    return block_q, kv_chunk


def _prefill_attn_kernel(
    lens_ref,  # scalar-prefetch (SMEM)
    qpos_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, z_s,
):
    """One (batch, kv-head, q-block, kv-chunk) program: flash accumulation
    over the kv grid axis; the scores are [BQ, kv_chunk] per query group —
    never the full [Sq, Skv] matrix the XLA path materializes."""
    b = pl.program_id(0)
    c = pl.program_id(3)
    G, BQ = q_ref.shape[2], q_ref.shape[3]
    C = k_ref.shape[2]

    @pl.when(c == 0)
    def _init():
        _flash_init(acc, m_s, z_s)

    k = k_ref[0, 0].astype(jnp.float32)  # [C, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    kv_pos = c * C + lax.broadcasted_iota(jnp.int32, (BQ, C), 1)
    # qpos block is [BQ, 1]: absolute positions of this q block
    mask = (kv_pos <= qpos_ref[0]) & (kv_pos < lens_ref[b])
    for g in range(G):  # static unroll: G is 1-8
        _flash_update(
            q_ref[0, 0, g].astype(jnp.float32), k, v, mask,
            acc.at[g], m_s.at[g], z_s.at[g],
        )

    @pl.when(c == pl.num_programs(3) - 1)
    def _emit():
        o_ref[0, 0] = acc[...] / jnp.maximum(z_s[...], 1e-30)


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_q", "kv_chunk")
)
def prefill_attention_pallas(
    q: jax.Array,  # [B, Sq, H, hd]
    k_cache: jax.Array,  # [B, K, Skv, hd]
    v_cache: jax.Array,  # [B, K, Skv, hd]
    q_pos: jax.Array,  # [B, Sq] absolute positions
    seq_lens: jax.Array,  # [B] valid kv per row
    *,
    block_q: int = PREFILL_BLOCK_Q,
    kv_chunk: int = PREFILL_KV_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Flash-attention prefill — drop-in for :func:`model.attention_xla`.

    Requires ``Sq % block_q == 0`` (or ``Sq < block_q``, which shrinks the
    block) and ``Skv % kv_chunk == 0`` (ditto); the engine's power-of-two
    prefill chunks and window buckets satisfy both.  Anything else raises
    :class:`PallasShapeError` at trace time.
    """
    B, Sq, H, hd = q.shape
    K, Skv = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    block_q, kv_chunk = prefill_blocks(Sq, Skv, block_q, kv_chunk)
    _note_trace("prefill", interpret)

    # [B, Sq, H, hd] -> [B, K, G, Sq, hd]: kv-head-major query layout
    qg = q.reshape(B, Sq, K, G, hd).transpose(0, 2, 3, 1, 4)
    q_spec = pl.BlockSpec(
        (1, 1, G, block_q, hd), lambda b, k, qi, c, *_refs: (b, k, 0, qi, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, kv_chunk, hd), lambda b, k, qi, c, *_refs: (b, k, c, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, Sq // block_q, Skv // kv_chunk),
        in_specs=[
            # positions as [B, Sq, 1]: a (block_q, 1) tail is a legal block
            # where a (1, block_q) block of [B, Sq] is not
            pl.BlockSpec(
                (1, block_q, 1), lambda b, k, qi, c, *_refs: (b, qi, 0)
            ),
            q_spec,
            kv_spec,
            kv_spec,
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, block_q, hd), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        _prefill_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, Sq, hd), jnp.float32),
        interpret=interpret,
        name="prefill_attention",
    )(seq_lens.astype(jnp.int32), q_pos.astype(jnp.int32)[..., None], qg,
      k_cache, v_cache)

    # [B, K, G, Sq, hd] -> [B, Sq, H, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)
