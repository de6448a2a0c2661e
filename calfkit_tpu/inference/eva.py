"""EVA attention (EvaByte; Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542) on the serving path: the mixer, its two caches
and what moves a row's keys from the one to the other.

    chunk j = positions c j .. c j + c - 1;  window n = positions W n .. W n + W - 1
    for every COMPLETE chunk j:   a_i  = softmax over i in chunk j of (s k_i . phi)
                                  kk_j = sum_i a_i k_i + mu;   vv_j = sum_i a_i v_i
    a query t, n = t // W, sees   L_t = {i : W n <= i <= t}        exactly, and
                                  R_t = {j : j < (W / c) n}        pooled,
    under ONE softmax over both (s = hd^-0.5; phi, mu learned, one a head).

What a row keeps of a layer (``config.CACHE_KINDS``: "window+summaries"):

- a RING of pages for one window, as a sliding-window layer's is (position
  ``p`` in entry ``(p // page) % ring``, written by the same programs), read
  under the ALIGNED lower bound ``W (t // W)`` in place of ``t - W + 1``:
  nothing is copied or freed when a window closes, the next one writes over it;
- SUMMARY pages, entry ``j`` the pooled key and value of chunk ``j``, in the
  pool and under the table a window stack's global layers have.  An entry is
  written when its chunk FILLS (by the prefill chunk that computed the chunk's
  keys, or by the decode dispatch whose tokens completed it, out of the ring)
  and read only once the query has left the chunk's window: a row at ``t``
  reads its first ``(W / c) (t // W)`` entries.  Entries of chunks not yet
  complete hold whatever was pooled of the positions written so far; no query
  sees them before they are written again, complete.

A dispatch of several decode steps that CROSSES a window's edge finds the
chunks its own earlier steps completed neither in the summary pages (the
dispatch's tokens land after its last step) nor among the exact keys it may
see: those, the last ``ceil((steps - 1) / c)`` chunks of the window just
closed at most, are pooled inside the step from the ring's pages and the
dispatch's fresh tokens (``eva_decode_step_paged``'s ``tail``), under a
``lax.cond`` that runs only in a step where some row stands past such an edge.

A prefill chunk is ONE window (``prefill_chunk == window_size``, refused
otherwise when the engine is built), so what a chunk's query sees is the
causal block of the chunk itself and, before it, every summary of the
prompt so far: the wave's scratch holds the summaries of the prompt in ONE
array a layer with room for a chunk behind them, the chunk's keys are laid
behind the summaries it may see, and the chunk kernel (or
``model.blocked_attention``) runs over that array as over any scratch:
one softmax, no new kernel.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import ModelConfig

Params = dict[str, Any]
Source = tuple[jax.Array, jax.Array, jax.Array]  # (o unnormalized, m, z)


def init_eva_params(config: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """An EVA stack's layers: the seven matrices at 1/sqrt(fan_in), the two
    norms at w = 0 (they multiply by 1 + w), ``phi`` and ``mu`` uniform in
    +-hd^-0.5 as the release initialises them.  The four attention matrices
    are held ``[L, D, H hd]`` and ``[L, H hd, D]``, the heads side by side in
    the matrix's width: held ``[L, D, H, hd]`` the TPU compiler copied each
    into that layout before the layer loop, every dispatch (compiled for the
    described v5e, PERF.md section 6, PR 54)."""
    L, D, H, hd, F = (config.n_layers, config.d_model, config.n_heads, config.head_dim,
                      config.d_ff)
    keys = jax.random.split(key, 9)

    def matrix(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    def learned(k):
        bound = hd ** -0.5
        return jax.random.uniform(k, (L, H, hd), jnp.float32, -bound, bound).astype(dtype)

    one = jnp.zeros if config.norm_plus_one else jnp.ones
    return {
        "wq": matrix(keys[0], (L, D, H * hd), D),
        "wk": matrix(keys[1], (L, D, H * hd), D),
        "wv": matrix(keys[2], (L, D, H * hd), D),
        "wo": matrix(keys[3], (L, H * hd, D), H * hd),
        "phi": learned(keys[4]),
        "mu": learned(keys[5]),
        "w_gate": matrix(keys[6], (L, D, F), D),
        "w_up": matrix(keys[7], (L, D, F), D),
        "w_down": matrix(keys[8], (L, F, D), F),
        "attn_norm": one((L, D), dtype),
        "mlp_norm": one((L, D), dtype),
    }


# --------------------------------------------------------------------------- #
# the pooling
# --------------------------------------------------------------------------- #


@jax.named_scope("pool")
def pool_chunks(
    k: jax.Array,  # [..., K, n c, hd] positions of whole chunks, in order
    v: jax.Array,
    phi: jax.Array,  # [..., K, hd] (leading dimensions as k's, or broadcast)
    mu: jax.Array,
    chunk: int,
) -> tuple[jax.Array, jax.Array]:
    """A chunk's pooled key and value -> ``[..., K, n, hd]`` x 2 in ``k``'s
    type: the weights a softmax, over the chunk, of ``s k_i . phi``, and
    ``mu`` ADDED to the pooled key.  All of it in float32 and elementwise (no
    matmul whose default precision on a TPU would round the weights to
    bfloat16): a chunk is few positions."""
    *lead, S, hd = k.shape
    k32 = k.astype(jnp.float32).reshape(*lead, S // chunk, chunk, hd)
    v32 = v.astype(jnp.float32).reshape(*lead, S // chunk, chunk, hd)
    phi = phi.astype(jnp.float32)[..., None, None, :]
    weights = jax.nn.softmax(jnp.sum(k32 * phi, axis=-1) * hd ** -0.5, axis=-1)[..., None]
    kk = jnp.sum(weights * k32, axis=-2) + mu.astype(jnp.float32)[..., None, :]
    vv = jnp.sum(weights * v32, axis=-2)
    return kk.astype(k.dtype), vv.astype(v.dtype)


# --------------------------------------------------------------------------- #
# the stack
# --------------------------------------------------------------------------- #


def head_logits(x: jax.Array, params: Params, config: ModelConfig, heads: int = 1) -> jax.Array:
    """Final norm + the first ``heads`` prediction heads -> float32 logits
    ``[B, S, heads x vocab]`` (head-major rows: head ``h`` is columns ``V h ..
    V (h + 1)``, the logits of token ``t + 1 + h``).  The served token is
    drawn from head 0, the default."""
    served = {**params, "lm_head": params["lm_head"][:, : heads * config.vocab_size]}
    return M.lm_logits(x, served, config.norm_eps, plus_one=config.norm_plus_one)


def eva_stack(
    config: ModelConfig,
    layers: Params,
    x: jax.Array,  # [B, S, D] float32: the residual stream
    carry: Any,
    attn_layer: Any,  # (carry, q, k, v, il, lp) -> (carry, attn [B, S, H, hd])
    positions: jax.Array,  # [B, S]
) -> tuple[jax.Array, Any]:
    """Run an EVA stack: one ``lax.scan`` over the layers.  The residual
    stream is float32 (``fp32_skip_add``); a norm's output, the projections
    and the SwiGLU are in the model's type.  The decode step and the prefill
    chunk differ only in ``attn_layer``."""
    eps, plus_one, dtype = config.norm_eps, config.norm_plus_one, jnp.dtype(config.dtype)
    with jax.named_scope("rope"):
        cos, sin = M.rope_tables(
            positions, *M.rope_frequencies(config.rotary_dim, config.rope_theta))

    def body(c, il):
        x, carry = c
        lp = M._layer(layers, il)
        h = M.rms_norm(x, lp["attn_norm"], eps, plus_one).astype(dtype)
        with jax.named_scope("eva"):
            with jax.named_scope("qkv"):
                def heads(name):
                    # the product as a plain [.., D] x [D, H hd] matrix product, taken
                    # apart into heads AFTER it: folded into the product, the split
                    # made the TPU compiler copy the three stacks into a layout with
                    # D minor-most before the layer loop, every dispatch (compiled
                    # for the described v5e, PERF.md section 6, PR 54)
                    return lax.optimization_barrier(h @ lp[name]).reshape(
                        *h.shape[:2], config.n_heads, config.head_dim)

                q, k, v = M.apply_rope(heads("wq"), cos, sin), M.apply_rope(heads("wk"), cos, sin), heads("wv")
            carry, attn = attn_layer(carry, q, k, v, il, lp)
            with jax.named_scope("attn_out"):
                a = attn.reshape(*attn.shape[:2], -1) @ lp["wo"]
        x = x + a.astype(jnp.float32)
        with jax.named_scope("mlp"):
            h = M.rms_norm(x, lp["mlp_norm"], eps, plus_one).astype(dtype)
            gate = jnp.einsum("bsd,df->bsf", h, lp["w_gate"])
            up = jnp.einsum("bsd,df->bsf", h, lp["w_up"])
            y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["w_down"])
        return (x + y.astype(jnp.float32), carry), None

    (x, carry), _ = lax.scan(body, (x, carry), jnp.arange(config.n_layers, dtype=jnp.int32))
    return x, carry


def _embed(params: Params, tokens: jax.Array) -> jax.Array:
    return params["embed"][tokens].astype(jnp.float32)


# --------------------------------------------------------------------------- #
# a prefill chunk: one window against the wave's scratch
# --------------------------------------------------------------------------- #


def scratch_len(config: ModelConfig, bucket: int) -> int:
    """Entries of a wave's summary scratch a layer: the summaries of a prompt
    of ``bucket`` positions with room for a chunk's keys behind those of its
    LAST chunk, in whole chunks (whole key blocks of the chunk kernel)."""
    W = config.window_size
    return -(-((bucket - W) // config.chunk_size + W) // W) * W


def make_scratch(config: ModelConfig, rows: int, bucket: int, dtype: Any) -> tuple[Any, Any]:
    """A wave's scratch, a pair (K side, V side) of pairs: ``[L, R, K,
    scratch_len, hd]`` for the summaries of the prompt so far (a chunk's keys
    are laid behind them for its attention and not kept there) and ``[L, R, K,
    W, hd]`` for the exact keys of the chunk last run, which land in the rows'
    rings when it was the prompt's last."""
    lead = (config.n_layers, rows, config.n_kv_heads)
    summaries = M.cache_sides(config, (*lead, scratch_len(config, bucket)), dtype)
    exact = M.cache_sides(config, (*lead, config.window_size), dtype)
    return tuple(zip(summaries, exact))


def eva_forward(params, config, tokens, positions, kv_cache, chunk_attn_impl="xla",
                heads: int = 1):
    """``model.forward`` for an EVA stack: ONE prefill chunk, a whole window
    ``[R, W]`` at ``positions[:, 0]`` (a multiple of ``W``), against the
    wave's scratch (:func:`make_scratch`).  A layer lays the chunk's keys
    behind the summaries its queries may see, attends over that array as over
    a scratch whose first query stands at ``offset / c`` (so every summary
    before it is seen and the chunk's own keys causally), pools the chunk and
    writes its summaries where the keys lay."""
    (ks, kx), (vs, vx) = kv_cache
    c = config.chunk_size
    S = tokens.shape[1]
    if S != config.window_size:
        raise ValueError(
            f"an EVA stack's prefill chunk is one window: {S} tokens, window_size "
            f"{config.window_size}")
    seen = positions[:, 0] // c  # [R] summaries every query of the chunk sees
    q_pos = seen[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    lens = seen + S

    def attn_layer(cache, q, k, v, il, lp):
        ks, kx, vs, vx = cache
        ks_l = lax.dynamic_index_in_dim(ks, il, 0, keepdims=False)
        vs_l = lax.dynamic_index_in_dim(vs, il, 0, keepdims=False)
        with jax.named_scope("attention"):
            k_all, v_all = M._insert_chunk(ks_l, k, seen), M._insert_chunk(vs_l, v, seen)
            if chunk_attn_impl.startswith("pallas"):
                from calfkit_tpu.inference.pallas_attention import chunk_attention_pallas

                attn = chunk_attention_pallas(
                    q, k_all, v_all, seen, lens,
                    interpret=chunk_attn_impl == "pallas_interpret")
            else:
                attn = M.blocked_attention(q, k_all, v_all, q_pos, lens)
        kc, vc = jnp.swapaxes(k, 1, 2).astype(ks.dtype), jnp.swapaxes(v, 1, 2).astype(vs.dtype)
        kk, vv = pool_chunks(kc, vc, lp["phi"], lp["mu"], c)
        with jax.named_scope("kv_write"):
            ks_l = M._insert_chunk(ks_l, jnp.swapaxes(kk, 1, 2), seen)
            vs_l = M._insert_chunk(vs_l, jnp.swapaxes(vv, 1, 2), seen)
            cache = (lax.dynamic_update_index_in_dim(ks, ks_l, il, 0),
                     lax.dynamic_update_index_in_dim(kx, kc, il, 0),
                     lax.dynamic_update_index_in_dim(vs, vs_l, il, 0),
                     lax.dynamic_update_index_in_dim(vx, vc, il, 0))
        return cache, attn

    x, (ks, kx, vs, vx) = eva_stack(
        config, params["layers"], _embed(params, tokens), (ks, kx, vs, vx), attn_layer, positions)
    return head_logits(x, params, config, heads), ((ks, kx), (vs, vx))


def write_prefill_pages(pool, scratch, page_ids):
    """A wave's landing: the summaries of each row's prompt into its summary
    pages (``page_ids[0]`` [R, pages of summaries]) and the exact keys of the
    last chunk into its ring (``page_ids[1]`` [R, W / page]: the trash page
    for a page that holds none of the row's own tokens)."""
    (kg, kw), (vg, vw) = pool
    (ks, kx), (vs, vx) = scratch
    page = kg.shape[3] * (kg.shape[4] // ks.shape[4])
    n = page_ids[0].shape[1] * page
    kg, vg = M.write_prefill_pages((kg, vg), (ks[:, :, :, :n], vs[:, :, :, :n]), page_ids[0])
    kw, vw = M.write_prefill_pages((kw, vw), (kx, vx), page_ids[1])
    return (kg, kw), (vg, vw)


# --------------------------------------------------------------------------- #
# a decode step
# --------------------------------------------------------------------------- #


def window_start(q_pos: jax.Array, window: int) -> jax.Array:
    """The first position a query at ``q_pos`` sees exactly: its ALIGNED
    window's (a sliding one would begin at ``q_pos - window + 1``)."""
    return window * (q_pos // window)


def summaries_in_pages(start: jax.Array, base_lens: jax.Array, chunk: int) -> jax.Array:
    """Summary entries a query whose window begins at ``start`` sees that are
    in the row's pages: the chunks of the windows BEFORE its own (never its
    own window's, however many are complete), those of them that were complete
    before the dispatch began."""
    return jnp.minimum(start, base_lens) // chunk


def ring_valid(ring_tokens: int, base_lens: jax.Array, start: jax.Array) -> jax.Array:
    """``model._window_ring_valid`` under the ALIGNED lower bound -> [B, R
    page]: entry ``r`` holds the newest position ``p < base`` with ``p = r``
    (mod the ring's tokens); attendable iff it lies in the query's own
    window, ``p >= start`` (``start = W (q // W)``)."""
    r = jnp.arange(ring_tokens, dtype=jnp.int32)[None, :]
    newest = r + ring_tokens * ((base_lens[:, None] - 1 - r) // ring_tokens)
    return (newest >= 0) & (newest >= start[:, None])


def fresh_source(qg, ring_k, ring_v, t, first) -> Source:
    """``model.ring_attention_source`` with a lower bound a row: the
    dispatch's fresh tokens ``first[b] <= t' <= t`` (those before ``first``
    lie in a window the query has left).  The step's own token is always
    among them."""
    T = ring_k.shape[0]
    scale = 1.0 / math.sqrt(qg.shape[-1])
    s = M._einsum_f32("bkgh,tbkh->bkgt", qg, ring_k) * scale
    tt = jnp.arange(T, dtype=jnp.int32)
    valid = (tt[None, :] <= t) & (tt[None, :] >= first[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m).astype(ring_k.dtype)
    z = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    return M._einsum_f32("bkgt,tbkh->bkgh", p, ring_v), m, z


@jax.named_scope("merge")
def merge_sources(*sources: Source) -> jax.Array:
    """``model.logsumexp_merge`` over any number of (o unnormalized, m, z):
    ONE softmax over everything the sources attended.  A source that saw
    nothing has z = 0 at a finite floor of m and weighs nothing."""
    m = sources[0][1]
    for _, mi, _ in sources[1:]:
        m = jnp.maximum(m, mi)
    o = z = 0.0
    for oi, mi, zi in sources:
        w = jnp.exp(mi - m)
        o, z = o + oi * w, z + zi * w
    return o / z


def read_chunks(pool_side, table, chunk_ids, chunk: int, layer=None):
    """Whole chunks out of the rows' rings of pages -> ``[L, B, K, n c, hd]``
    (``[B, K, n c, hd]`` of ONE ``layer``): chunk ``chunk_ids[b, j]`` [B, n] of
    row ``b`` (``pool_side`` [L, N, K, page, hd], stored as declared).  A chunk
    lies in one page at a whole multiple of its length (``c`` divides the
    page), so each is ONE window read of the stored pool, in a loop over (row,
    chunk) as ``model._write_windows`` writes: a gather over (page, offset)
    would have the TPU compiler relay the whole side into a layout of its own,
    every step (compiled for the described v5e, PERF.md section 6, PR 54).
    Chunks below 0 read chunk 0 (the caller masks them)."""
    L, _, K, page, hd = pool_side.shape
    B, n = chunk_ids.shape
    entries = table.shape[1]
    layers, first = (L, 0) if layer is None else (1, layer)

    def one(i, out):
        b, j = i // n, i % n
        at = jnp.maximum(chunk_ids[b, j], 0) * chunk
        page_id = table[b, (at // page) % entries]
        got = lax.dynamic_slice(
            pool_side, (first, page_id, 0, at % page, 0), (layers, 1, K, chunk, hd))
        return lax.dynamic_update_slice(out, got, (0, b, 0, j * chunk, 0))

    out = lax.fori_loop(
        0, B * n, one, jnp.zeros((layers, B, K, n * chunk, hd), pool_side.dtype))
    return out if layer is None else out[0]


def write_entries(pool_side, values, table, first, active):
    """``values`` [L, n, B, K, hd] into each row's pages at entries ``first[b]
    .. first[b] + n - 1`` (``pool_side`` [L, N, K, page, hd]): a loop over (row,
    entry), each a read-modify-write of the aligned group of rows (a whole
    sublane tile of the pool's type, a page at most) that holds the entry, so
    that the window is whole tiles of the stored pool and nothing of the side
    is relaid.  A row that is not ``active`` and an entry past the row's table
    write to the trash page."""
    L, _, K, page, hd = pool_side.shape
    n, B = values.shape[1:3]
    entries = table.shape[1]
    group = min(page, M.sublane_tile(pool_side.dtype))
    rows = jnp.arange(group, dtype=jnp.int32)[None, None, None, :, None]

    def one(i, side):
        b, j = i // n, i % n
        entry = first[b] + j
        at = entry // page
        page_id = jnp.where(active[b] & (at < entries), table[b, jnp.minimum(at, entries - 1)], 0)
        corner = (0, page_id, 0, (entry % page) // group * group, 0)
        old = lax.dynamic_slice(side, corner, (L, 1, K, group, hd))
        new = lax.dynamic_slice(values, (0, j, b, 0, 0), (L, 1, 1, K, hd)).reshape(L, 1, K, 1, hd)
        return lax.dynamic_update_slice(
            side, jnp.where(rows == entry % group, new.astype(side.dtype), old), corner)

    return lax.fori_loop(0, B * n, one, pool_side)


def paged_source(qg, sides, layer, table, lens, pages: int, attn_impl: str, valid,
                 starts=None) -> Source:
    """One attention source over a row's pages of ONE layer of a pool (its K
    and V ``sides``, stored as declared): the paged decode kernel in place
    (``lens`` entries a row; its window form from ``starts`` where given), or
    through XLA the gathered pages under ``valid(entries gathered)`` [B, n]."""
    if attn_impl.startswith("pallas"):
        from calfkit_tpu.inference.pallas_attention import paged_decode_attention_pallas

        o, m, z = paged_decode_attention_pallas(
            qg, *sides, layer, table, lens, wpages=pages,
            interpret=attn_impl == "pallas_interpret",
            **({} if starts is None else {"window_starts": starts}))
        return o, m[..., None], z[..., None]
    k, v = (M.gather_window_paged(
        lax.dynamic_index_in_dim(side, layer, 0, keepdims=False), table, pages, qg.shape[-1])
        for side in sides)
    return M.masked_attention_source(qg, k, v, valid(k.shape[2]))


def tail_chunks(steps: int, chunk: int) -> int:
    """Chunks of a window just closed that a dispatch of ``steps`` steps can
    have completed itself before the step that reads them."""
    return max(1, -(-(steps - 1) // chunk))


def eva_decode_step_paged(params, config, tokens, pool, tables, ring, t, base_lens,
                          wpages, attn_impl, active):
    """``model.decode_step_ring_paged`` for an EVA stack: the pool's sides and
    the tables are pairs (summaries, ring).  A layer's query at ``q = base +
    t`` reads, under ONE softmax (:func:`merge_sources`): its row's ring under
    the aligned lower bound; the first ``min(start, base) / c`` entries of its
    summary pages; the dispatch's fresh tokens of its own window; and, past an
    edge the dispatch itself crossed, the chunks its earlier steps completed
    (module docstring)."""
    (ksum, kwin), (vsum, vwin) = pool
    ts, tw = tables
    W, c, hd = config.window_size, config.chunk_size, config.head_dim
    if kwin.shape[4] != hd:
        raise ValueError("an EVA stack's ring is read by position: a pool that packs "
                         f"{kwin.shape[4] // hd} positions of a head of {hd} a row is not described")
    q_pos = base_lens + t
    start = window_start(q_pos, W)
    pooled = summaries_in_pages(start, base_lens, c)
    live = base_lens if active is None else jnp.where(active, base_lens, 0)
    n_sum = pooled if active is None else jnp.where(active, pooled, 0)
    nt = tail_chunks(ring[0].shape[1], c)
    tail_ids = start[:, None] // c - nt + jnp.arange(nt, dtype=jnp.int32)[None, :]  # [B, nt]
    tail_seen = tail_ids >= (base_lens // c)[:, None]  # completed by THIS dispatch's tokens
    if active is not None:
        tail_seen = tail_seen & active[:, None]

    def attn_layer(fresh, q, k, v, il, lp):
        fk, fv = fresh
        fk = lax.dynamic_update_slice(fk, k[:, 0].astype(fk.dtype)[None, None], (il, t, 0, 0, 0))
        fv = lax.dynamic_update_slice(fv, v[:, 0].astype(fv.dtype)[None, None], (il, t, 0, 0, 0))
        rk = lax.dynamic_index_in_dim(fk, il, 0, keepdims=False)  # [T, B, K, hd]
        rv = lax.dynamic_index_in_dim(fv, il, 0, keepdims=False)
        B, _, H, _ = q.shape
        K = rk.shape[2]
        qg = q.reshape(B, K, H // K, hd)
        with jax.named_scope("attention"):
            with jax.named_scope("window"):
                window = paged_source(
                    qg, (kwin, vwin), il, tw, live, tw.shape[1], attn_impl,
                    lambda n: ring_valid(n, base_lens, start), starts=start)
                own = fresh_source(qg, rk, rv, t, start - base_lens)
            with jax.named_scope("summary"):
                summary = paged_source(
                    qg, (ksum, vsum), il, ts, n_sum, wpages, attn_impl,
                    lambda n: jnp.arange(n)[None, :] < pooled[:, None])

                def tail(_):
                    # the tail chunks' positions: out of the ring's pages, but out of
                    # the dispatch's fresh tokens where they are among those
                    at = (tail_ids[:, :1] * c
                          + jnp.arange(nt * c, dtype=jnp.int32)[None, :])  # [B, nt c]
                    new = jnp.clip(at - base_lens[:, None], 0, rk.shape[0] - 1)
                    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
                    fresh = (at >= base_lens[:, None])[:, None, :, None]

                    def side(pool_side, ring_side):
                        kept = read_chunks(pool_side, tw, tail_ids, c, il)  # [B, K, nt c, hd]
                        mine = jnp.swapaxes(ring_side[new, rows], 1, 2).astype(kept.dtype)
                        return jnp.where(fresh, mine, kept)

                    kk, vv = pool_chunks(side(kwin, rk), side(vwin, rv), lp["phi"], lp["mu"], c)
                    return M.masked_attention_source(qg, kk, vv, tail_seen)

                def no_tail(_):
                    lead = qg.shape[:3]
                    return (jnp.zeros(qg.shape, jnp.float32),
                            jnp.full((*lead, 1), -1e29, jnp.float32),
                            jnp.zeros((*lead, 1), jnp.float32))

                closed = lax.cond(jnp.any(tail_seen), tail, no_tail, None)
        out = merge_sources(window, own, summary, closed)
        return (fk, fv), out.reshape(B, 1, H, hd).astype(q.dtype)

    x, ring = eva_stack(
        config, params["layers"], _embed(params, tokens), tuple(ring), attn_layer, q_pos[:, None])
    return head_logits(x, params, config), ring


@jax.named_scope("kv_write")
def consolidate(params, config, pool, ring, tables, base_lens, active):
    """``model.consolidate_ring_paged`` for an EVA stack: the dispatch's
    tokens land in the rows' rings as a window stack's do, and then the
    chunks they can have COMPLETED are pooled out of the ring's pages and
    written to the summary pages, ``ceil(T / c)`` entries a row from chunk
    ``base // c`` on (an entry whose chunk is not complete yet is written
    again by the dispatch that completes it, and seen by no query before:
    module docstring): :func:`read_chunks` and :func:`write_entries`, loops
    of window reads and writes of the stored pools."""
    (ksum, kwin), (vsum, vwin) = pool
    ts, tw = tables
    c = config.chunk_size
    kwin, vwin = M._write_windows((kwin, vwin), ring, tw, base_lens, active, wraps=True)
    T = ring[0].shape[1]
    nt = -(-T // c)
    first = base_lens // c  # [B] the first chunk the dispatch can have completed
    chunk_ids = first[:, None] + jnp.arange(nt, dtype=jnp.int32)[None, :]
    layers = params["layers"]
    kc, vc = read_chunks(kwin, tw, chunk_ids, c), read_chunks(vwin, tw, chunk_ids, c)
    kk, vv = pool_chunks(kc, vc, layers["phi"][:, None], layers["mu"][:, None], c)  # [L, B, K, nt, hd]
    kk, vv = jnp.transpose(kk, (0, 3, 1, 2, 4)), jnp.transpose(vv, (0, 3, 1, 2, 4))
    return ((write_entries(ksum, kk, ts, first, active), kwin),
            (write_entries(vsum, vv, ts, first, active), vwin))
