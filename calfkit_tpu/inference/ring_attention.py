"""Ring attention: sequence-parallel causal attention over an ``sp`` mesh axis.

The long-context scaling path (SURVEY §5 long-context; the build brief makes
sequence/context parallelism first-class): when a prompt is too long for one
chip's HBM (activations + KV), shard the SEQUENCE over devices and rotate
K/V blocks around the ring with ``ppermute`` while each device keeps its
query shard resident.  Per rotation step every device computes one
(Q-block × K/V-block) partial attention and folds it into a running
flash-style (o·z, m, z) accumulator; after ``sp`` rotations each device
holds exact attention output for its own query block.

Design notes (tpu-first, not a port):

- expressed with ``shard_map`` so the collective schedule is explicit —
  ppermute rides ICI neighbor links, never DCN, and XLA can overlap the
  rotation's communication with the current block's compute;
- causal + validity masking is decided per (query-block, kv-block) pair
  from absolute positions and per-sequence lengths;
- the final rotation is skipped (its result would be discarded): n-1
  ppermute hops move every block all the way around;
- the accumulator is the same (unnormalized o, max, z) triple used by the
  decode kernels (:func:`model.logsumexp_merge`) — one merge law everywhere;
- block layout is ``[sp, block, ...]``: block i on device i is sequence
  positions ``[i·block, (i+1)·block)`` — contiguous shards, so the output
  reassembles with a plain reshape;
- the transformer block math in :func:`prefill_sequence_parallel` is the
  SAME helpers (:func:`model.attn_qkv` / :func:`model.attn_out_mlp` /
  :func:`model.lm_logits`) the dense prefill and decode paths use.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def ring_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    mesh: Mesh,
    *,
    axis: str = "sp",
    seq_lens: jax.Array | None = None,  # [B] valid tokens; None = all S
) -> jax.Array:
    """Causal GQA attention with the sequence dimension sharded over
    ``axis``; → [B, S, H, hd] sharded the same way.

    ``seq_lens`` masks ragged batches: positions ≥ a row's length neither
    attend usefully nor get attended (their outputs are garbage and must be
    ignored by the caller, exactly like the dense path's pad positions).
    Requires ``S % mesh.shape[axis] == 0``.
    """
    sp = mesh.shape[axis]
    B, S, H, hd = q.shape
    if S % sp:
        raise ValueError(f"sequence {S} must divide over {axis}={sp}")
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    spec = P(None, axis, None, None)
    len_spec = P(None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec, len_spec),
        out_specs=spec,
        check_vma=False,
    )
    def ring(q_blk, k_blk, v_blk, lens):
        # q_blk: [B, S/sp, H, hd] — this device's query block (resident)
        # k_blk/v_blk: rotating K/V block, starts as our own
        my_idx = lax.axis_index(axis)
        n = lax.psum(1, axis)
        blk = q_blk.shape[1]
        scale = 1.0 / math.sqrt(hd)
        Kh = k_blk.shape[2]
        G = H // Kh
        qg = (q_blk * scale).astype(jnp.float32).reshape(B, blk, Kh, G, hd)
        q_pos = my_idx * blk + jnp.arange(blk)  # absolute query positions

        def fold(acc, kc, vc, r):
            o, m, z = acc
            # kv block r originated on device (my_idx - r) mod n
            src = (my_idx - r) % n
            kv_pos = src * blk + jnp.arange(blk)
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs",
                qg,
                kc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )  # [B, K, G, blk_q, blk_kv]
            causal = kv_pos[None, :] <= q_pos[:, None]  # [blk_q, blk_kv]
            valid = kv_pos[None, :] < lens[:, None]  # [B, blk_kv]
            mask = causal[None] & valid[:, None]  # [B, blk_q, blk_kv]
            s = jnp.where(mask[:, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            m_new = jnp.maximum(m_new, -1e29)  # all-masked steps stay finite
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            z_new = z * alpha + jnp.sum(p, axis=-1, keepdims=True)
            o_new = o * alpha + jnp.einsum(
                "bkgqs,bskh->bkgqh",
                p,
                vc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return o_new, m_new, z_new

        def step(carry, r):
            acc, kc, vc = carry
            acc = fold(acc, kc, vc, r)
            # rotate K/V one hop around the ring (device d -> d+1)
            perm = [(d, (d + 1) % n) for d in range(n)]
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (acc, kc, vc), None

        acc0 = (
            jnp.zeros((B, Kh, G, blk, hd), jnp.float32),
            jnp.full((B, Kh, G, blk, 1), -1e30, jnp.float32),
            jnp.zeros((B, Kh, G, blk, 1), jnp.float32),
        )
        # n-1 rotating steps + one final fold WITHOUT the rotation (its
        # result would be discarded — that last ppermute pair is pure waste)
        (acc, kc, vc), _ = lax.scan(step, (acc0, k_blk, v_blk), jnp.arange(n - 1))
        o, m, z = fold(acc, kc, vc, n - 1)
        out = o / jnp.maximum(z, 1e-30)  # [B, K, G, blk, hd]
        out = jnp.moveaxis(out, 3, 1).reshape(B, blk, H, hd)
        return out.astype(q_blk.dtype)

    return ring(q, k, v, seq_lens.astype(jnp.int32))


def single_device_causal_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    seq_lens: jax.Array | None = None,
) -> jax.Array:
    """The dense reference the ring must match — a thin wrapper over the
    serving path's :func:`model.attention_xla` (one attention math)."""
    from calfkit_tpu.inference.model import attention_xla

    B, S, _, _ = q.shape
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return attention_xla(
        q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), positions, seq_lens
    )


# --------------------------------------------------------------------------- #
# sequence-parallel prefill
# --------------------------------------------------------------------------- #


def prefill_sequence_parallel(
    params: dict,
    config,
    tokens: jax.Array,  # [B, S] int32 — S divides the sp axis
    mesh: Mesh,
    *,
    axis: str = "sp",
    seq_lens: jax.Array | None = None,  # [B] true prompt lengths (ragged)
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Run a long-prompt prefill with the sequence sharded over ``axis``.

    Activations AND the produced KV stay sequence-sharded on device
    throughout (each chip holds S/sp of every layer's K/V); only attention
    communicates, via the ring.  Returns:

    - ``last_logits`` [B, V] — logits at each row's LAST VALID position
      (``seq_lens - 1``), what sampling needs;
    - ``(k, v)`` [L, B, K, S, hd] sequence-sharded over ``axis``; positions
      ≥ a row's length hold garbage exactly like the dense path's scratch
      (mask with ``seq_lens`` downstream).

    Reference seam: this is the long-context entry SURVEY §5 prescribes
    leaving block-wise; the serving engine uses it when a prompt exceeds
    single-chip prefill capacity.
    """
    B, S = tokens.shape
    sp = mesh.shape[axis]
    if S % sp:
        raise ValueError(f"prompt length {S} must divide over {axis}={sp}")
    if seq_lens is None:
        seq_lens = jnp.full((B,), S, jnp.int32)

    tok_spec = P(None, axis)
    tokens = jax.device_put(tokens, NamedSharding(mesh, tok_spec))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    positions = jax.device_put(positions, NamedSharding(mesh, tok_spec))

    try:
        fn = _prefill_sp_jit(config, mesh, axis)
    except TypeError:
        # unhashable config/mesh: fall back to an uncached jit (correct,
        # just re-traced per call) rather than narrowing the contract
        fn = _build_prefill_sp(config, mesh, axis)
    return fn(params, tokens, positions, seq_lens.astype(jnp.int32))


@functools.lru_cache(maxsize=32)
def _prefill_sp_jit(config, mesh: Mesh, axis: str):
    """One traced+compiled sp prefill per (config, mesh, axis) — eager
    re-tracing of the L-layer scan per call would dominate short prompts."""
    return _build_prefill_sp(config, mesh, axis)


def _build_prefill_sp(config, mesh: Mesh, axis: str):
    from calfkit_tpu.inference import model as M

    eps = config.norm_eps

    def fn(params, tokens, positions, seq_lens):
        S = tokens.shape[1]
        x = params["embed"][tokens]  # [B, S, D] sequence-sharded (gather)
        cos, sin = M.rope_tables(positions, *M.rope_frequencies(config.head_dim, config.rope_theta))

        def layer_body(x, lp):
            q, k, v = M.attn_qkv(x, lp, cos, sin, eps)
            attn = ring_attention(q, k, v, mesh, axis=axis, seq_lens=seq_lens)
            return M.attn_out_mlp(x, attn, lp, eps), (k, v)

        x, (ks, vs) = lax.scan(layer_body, x, params["layers"])
        # ks/vs: [L, B, S, K, hd] sequence-sharded; cache wants K-major
        k_cache = jnp.swapaxes(ks, 2, 3)  # [L, B, K, S, hd]
        v_cache = jnp.swapaxes(vs, 2, 3)

        # gather the last-valid hidden state FIRST, then the head:
        # full-sequence logits would materialize [B, S, V] (gigabytes at
        # 128k vocab and long S) for one row each
        idx = jnp.clip(seq_lens - 1, 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        last_logits = M.lm_logits(x_last, params, eps)[:, 0]
        return last_logits, (k_cache, v_cache)

    return jax.jit(fn)


# --------------------------------------------------------------------------- #
# context-parallel decode over a sequence-sharded prefix
# --------------------------------------------------------------------------- #


def context_parallel_attention(
    q: jax.Array,  # [B, 1, H, hd] one decode step's queries
    k_prefix: jax.Array,  # [B, K, S, hd] sequence-sharded over `axis` (dim 2)
    v_prefix: jax.Array,
    prefix_lens: jax.Array,  # [B] valid prefix tokens
    mesh: Mesh,
    *,
    axis: str = "sp",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Decode attention over a prefix that STAYS sequence-sharded.

    Each device scores its own shard (no rotation needed — a decode query
    attends everywhere, so partial (o, m, z) merge exactly via the global
    max + rescaled sums: two psum/pmax collectives instead of moving any
    KV).  Returns the (unnormalized o [B,K,G,hd], m [B,K,G,1], z [B,K,G,1])
    triple for :func:`model.logsumexp_merge` with the fresh-token source —
    the seam that makes ring-prefilled caches directly decodable.
    """
    B, _, H, hd = q.shape
    Kh = k_prefix.shape[1]
    G = H // Kh
    S = k_prefix.shape[2]
    sp = mesh.shape[axis]
    if S % sp:
        raise ValueError(f"prefix length {S} must divide over {axis}={sp}")
    blk = S // sp

    q_spec = P(None, None, None, None)
    kv_spec = P(None, None, axis, None)
    len_spec = P(None)
    out_spec = P(None, None, None, None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, len_spec),
        out_specs=(out_spec, out_spec, out_spec),
        check_vma=False,
    )
    def cp(qr, kb, vb, lens):
        from calfkit_tpu.inference.model import masked_attention_source

        my_idx = lax.axis_index(axis)
        qg = qr[:, 0].reshape(B, Kh, G, hd)
        pos = my_idx * blk + jnp.arange(blk)  # this shard's absolute span
        valid = pos[None, :] < lens[:, None]  # [B, blk]
        o, m, z = masked_attention_source(qg, kb, vb, valid)
        # exact global merge: rescale every shard to the global max, sum
        m_all = lax.pmax(m, axis)
        w = jnp.exp(m - m_all)
        o_all = lax.psum(o * w, axis)
        z_all = lax.psum(z * w, axis)
        return o_all, m_all, z_all

    return cp(q, k_prefix, v_prefix, prefix_lens.astype(jnp.int32))


def decode_with_sharded_prefix(
    params: dict,
    config,
    first_token: jax.Array,  # [B] the token sampled from the prefill logits
    prefix: tuple[jax.Array, jax.Array],  # [L, B, K, S, hd] sharded over axis
    prefix_lens: jax.Array,  # [B]
    mesh: Mesh,
    steps: int,
    *,
    axis: str = "sp",
) -> jax.Array:
    """Greedy-decode ``steps`` tokens directly against a ring-prefilled,
    still-sequence-sharded KV prefix — no resharding, no consolidation.

    One-shot convenience over :func:`decode_sp_dispatch` (the serving
    engine's carried unit): fresh K/V accumulates in a small replicated
    cache merged with the context-parallel prefix source via the shared
    logsumexp law.  → [B, steps] int32 greedy tokens.
    """
    k_prefix, v_prefix = prefix
    B = first_token.shape[0]
    L, Kh, hd = config.n_layers, config.n_kv_heads, config.head_dim
    fresh = (
        jnp.zeros((L, B, Kh, steps, hd), jnp.float32),
        jnp.zeros((L, B, Kh, steps, hd), jnp.float32),
    )
    toks, _last, _fresh = decode_sp_dispatch(
        params, config, first_token, (k_prefix, v_prefix), prefix_lens,
        fresh, jnp.int32(0), mesh, steps, axis=axis,
    )
    return toks


def decode_sp_dispatch(
    params: dict,
    config,
    token: jax.Array,  # [B] last sampled token (enters this dispatch)
    prefix: tuple[jax.Array, jax.Array],  # [L, B, K, S, hd] sharded over axis
    prefix_lens: jax.Array,  # [B]
    fresh: tuple[jax.Array, jax.Array],  # [L, B, K, cap, hd] replicated carry
    t0: jax.Array,  # scalar int32: fresh tokens already generated
    mesh: Mesh,
    steps: int,
    *,
    axis: str = "sp",
) -> tuple[jax.Array, jax.Array, tuple[jax.Array, jax.Array]]:
    """One long-lane decode DISPATCH: ``steps`` greedy tokens against a
    sequence-sharded prefix, carrying the replicated fresh cache across
    dispatches (this is the serving engine's long-context unit of work —
    the analog of the short lane's ring-buffer decode tick).

    → (toks [B, steps], last_token [B], fresh) with fresh slots
    [t0, t0+steps) filled; the cap bounds total generation per request.
    """
    k_prefix, v_prefix = prefix
    cap = fresh[0].shape[3]
    try:
        fn = _decode_sp_jit(
            config, mesh, axis, steps, token.shape[0], cap
        )
    except TypeError:  # unhashable config/mesh: uncached fallback
        fn = _build_decode_sp(
            config, mesh, axis, steps, token.shape[0], cap
        )
    return fn(
        params, token, k_prefix, v_prefix, prefix_lens,
        fresh[0], fresh[1], jnp.asarray(t0, jnp.int32),
    )


@functools.lru_cache(maxsize=32)
def _decode_sp_jit(config, mesh: Mesh, axis: str, steps: int, B: int, cap: int):
    """One compile per (config, mesh, axis, steps, B, cap) — the multi-step
    decode program is seconds of trace+compile per shape."""
    return _build_decode_sp(config, mesh, axis, steps, B, cap)


def _build_decode_sp(config, mesh: Mesh, axis: str, steps: int, B: int,
                     cap: int):
    from calfkit_tpu.inference import model as M

    Kh, hd, eps = config.n_kv_heads, config.head_dim, config.norm_eps

    def fn(params, first_token, k_prefix, v_prefix, prefix_lens,
           fresh_k0, fresh_v0, t0):
        def one_step(carry, i):
            token, fresh = carry
            fresh_k, fresh_v = fresh
            t = t0 + i  # global fresh index: carries across dispatches
            positions = (prefix_lens + t)[:, None]
            x = params["embed"][token[:, None]]
            cos, sin = M.rope_tables(positions, *M.rope_frequencies(hd, config.rope_theta))

            def layer_body(x, inputs):
                lp, kp, vp, fk, fv = inputs
                q, k, v = M.attn_qkv(x, lp, cos, sin, eps)
                fk = lax.dynamic_update_slice(
                    fk, jnp.swapaxes(k, 1, 2).astype(fk.dtype), (0, 0, t, 0)
                )
                fv = lax.dynamic_update_slice(
                    fv, jnp.swapaxes(v, 1, 2).astype(fv.dtype), (0, 0, t, 0)
                )
                o1, m1, z1 = context_parallel_attention(
                    q, kp, vp, prefix_lens, mesh, axis=axis
                )
                qg = q.reshape(B, Kh, -1, hd)
                o2, m2, z2 = M.ring_attention_source(
                    qg,
                    jnp.transpose(fk, (2, 0, 1, 3)),  # -> [cap, B, K, hd]
                    jnp.transpose(fv, (2, 0, 1, 3)),
                    t,
                )
                attn = M.logsumexp_merge((o1, m1, z1), (o2, m2, z2))
                attn = attn.reshape(B, 1, -1, hd).astype(x.dtype)
                return M.attn_out_mlp(x, attn, lp, eps), (fk, fv)

            x, (fresh_k, fresh_v) = lax.scan(
                layer_body,
                x,
                (params["layers"], k_prefix, v_prefix, fresh_k, fresh_v),
            )
            logits = M.lm_logits(x, params, eps)[:, -1]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, (fresh_k, fresh_v)), nxt

        (last, fresh), toks = lax.scan(
            one_step, (first_token, (fresh_k0, fresh_v0)), jnp.arange(steps)
        )
        return jnp.swapaxes(toks, 0, 1), last, fresh  # toks [B, steps]

    return jax.jit(fn, donate_argnums=(5, 6))
