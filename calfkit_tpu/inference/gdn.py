"""The Gated DeltaNet mixer (linear attention by the gated delta rule), in
the two forms the engine needs and that must agree.

Per value head, with ``S`` of ``d_k x d_v`` float32 numbers, zero for a new
sequence (HF ``modeling_qwen3_next``: ``torch_recurrent_gated_delta_rule``):

    S <- exp(g_t) S;  u = S^T k_t;  S <- S + k_t (x) (beta_t (v_t - u));  o_t = S^T q_t

with ``q, k`` L2-normalised per head, ``q`` over ``sqrt(d_k)``, ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.

- :func:`gdn_step` is the one-token recurrence of ``decode_loop``, on the
  rows' carried conv tail and ``S``.  Its pass over ``S`` is
  ``(exp(g) S)^T [k | q]`` in one reduction, then ``S' = exp(g) S + k (x)
  delta`` in place, and ``o = (exp(g) S)^T q + (k . q) delta``, which is
  ``S'^T q`` without reading ``S'`` again.  :func:`delta_step_xla` is that
  pass in XLA, the reference and what a CPU runs: the reduction has to see
  all of a head's ``S`` before the update can write, so XLA reads ``S``
  twice and writes it, over every slot.  ``pallas_gdn.delta_step_pallas`` is
  the same arithmetic with a head's tile held in VMEM between the two: ONE
  read and one write of the ACTIVE rows (chosen by
  ``InferenceEngine._resolved_ssm_impl``).  Rows that are not ``active``
  keep both states bit for bit.
- :func:`gdn_chunk` is the chunkwise form of ``chunk_loop`` and ``prefill``:
  blocks of ``gdn_chunk_size`` positions, inside a block the unit lower
  triangular system ``(I + tril(diag(beta) K K^T . decay, -1)) X = [beta v |
  beta exp(g) k]`` solved once, between blocks a short recurrence over ``S``,
  entered with the state the chunk before left and leaving the state the
  next one takes.  Positions at or past a row's ``n_valid`` are padding:
  their ``g`` and ``beta`` are zero (decay one, no update) and the conv tail
  is read at ``n_valid``, not at the chunk's end, so padding moves neither
  state.

Layout (stacked on axis 0 over the DeltaNet layers; ``C`` = 2 d_key +
d_value, the conv's channels ``q | k | v``, heads in order within each):
    w_in [Lg, C + d_value + 2 Hv, D]   fused q | k | v | z | b | a projection,
                                       no bias, [out, in] as the Mamba leaves
                                       keep theirs.  HF interleaves these per
                                       KEY head (``in_proj_qkvz``: q, k, v of
                                       its value heads, z of them; ``in_proj_ba``
                                       likewise); the loader undoes that once,
                                       which changes no product
    conv_w [Lg, d_conv, C]             depthwise causal conv, tap-major, no bias
    A_log, dt_bias [Lg, Hv] float32
    norm [Lg, d_v]                     the gated RMSNorm's weight (times w, not 1 + w)
    w_out [Lg, d_value, D]
    mixer_norm [Lg, D]                 the RMSNorm before the mixer
State (per slot, the pair the engine carries for Mamba-2 too): S [Lg, B, Hv,
d_k, d_v] in ``config.state_dtype``; conv [Lg, d_conv - 1, B, C] in the
activations' type, oldest input first.

**Kimi Delta Attention** (``config.kda``; Kimi Linear, arXiv:2510.26692, as
``bailing_hybrid`` selects it) is the same rule with the decay INSIDE the
products: one log-decay a key CHANNEL, ``g`` [.., H, d_k],

    S <- Diag(exp(g_t)) S;  u = S^T k_t;  S <- S + k_t (x) (beta_t (v_t - u));  o_t = S^T q_t

with ``g = kda_lower_bound sigmoid(exp(A_log[head]) (a + dt_bias))`` in
``[kda_lower_bound, 0)`` (the bounded gate), ``a = h W_alpha`` ONE full matrix
(``w_alpha`` [Lg, H d_k, D], under the scope ``decay``), ``dt_bias`` [Lg, H,
d_k], as many key heads as value heads, and ONE output gate a head:
``rmsnorm(o) w sigmoid(z[head])``, ``z`` [.., H] (so ``w_in`` is q | k | v | z |
b, ``C + 2 H`` wide).  The step is a scale by key ROW where it was a scale by
head (:func:`delta_step_xla` takes either ``g``).  The chunk form
(:func:`delta_chunks_by_channel`) is two-level: the weight of the pair ``(i,
j)`` is ``sum_c k_ic k_jc exp(G_ic - G_jc)`` (``G`` the block's running sum of
``g``), which is a matrix product only as ``(k_i exp(G_i - R)) . (k_j exp(R -
G_j))`` for some reference ``R``, and ``exp(R - G_j)`` overflows float32 over
a block of 64 at ``g`` down to -5 a position (e^320).  So ``R`` is the running
sum at the FIRST position of ``i``'s sub-block of ``kda_sub_block`` (16)
positions: for ``j`` in an earlier sub-block ``R - G_j <= 0`` is an exact
decay, for ``j`` in the same one it is at most 15 x 5 = 75 (e^75 = 3.7e32 <
3.4e38), and a later ``j`` is masked BEFORE the exponential.  One product a
sub-block against the block's keys as that sub-block sees them: the FLOPs of
the one-level form.

Everything after the input projection is float32 arithmetic (the conv, the
norms, the decays, both products with ``S``, the triangular solve, the gated
norm); the two big matmuls take and give the activations' type.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from calfkit_tpu.inference.config import ModelConfig
from calfkit_tpu.inference.mamba import _layer_of

Params = dict[str, Any]
_HI = lax.Precision.HIGHEST  # float32 einsums of the recurrence: no bf16 passes
_L2_EPS = 1e-6  # HF's l2norm: x * rsqrt(sum x^2 + eps)


def init_gdn_params(config: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """Random DeltaNet leaves: matrices at 1/sqrt(fan_in); ``A`` uniform in
    0-16 and ``dt_bias`` the inverse softplus of a ``dt`` log-uniform in
    0.001-0.1, as HF initialises them, so that some heads forget in tens of
    tokens and others remember for thousands."""
    c = config
    Lg, D, Hv = c.n_recurrent_layers, c.d_model, c.gdn_n_v_heads
    keys = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    dt = jnp.exp(
        jax.random.uniform(keys[3], (Lg, Hv), jnp.float32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    out = {
        "w_in": normal(keys[0], (Lg, c.gdn_d_in_proj, D), D),
        "conv_w": normal(keys[1], (Lg, c.gdn_d_conv, c.gdn_conv_dim), c.gdn_d_conv),
        "A_log": jnp.log(jax.random.uniform(keys[2], (Lg, Hv), jnp.float32, 1e-3, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "norm": jnp.ones((Lg, c.gdn_d_v), dtype),
        "w_out": normal(keys[4], (Lg, c.gdn_value_dim, D), c.gdn_value_dim),
        "mixer_norm": jnp.zeros((Lg, D), dtype) if c.norm_plus_one else jnp.ones((Lg, D), dtype),
    }
    if c.kda:
        # the bounded gate's argument exp(A_log) (a + dt_bias): A in 0.25-4 and a
        # bias a CHANNEL in +-2 beside a's unit spread, so that sigmoid leaves
        # neither end and the channels of one head forget at different rates
        out["w_alpha"] = normal(jax.random.fold_in(key, 5), (Lg, c.gdn_key_dim, D), D)
        out["A_log"] = jax.random.uniform(
            keys[2], (Lg, Hv), jnp.float32, math.log(0.25), math.log(4.0))
        out["dt_bias"] = jax.random.uniform(
            jax.random.fold_in(key, 6), (Lg, Hv, c.gdn_d_k), jnp.float32, -2.0, 2.0)
    return out


def _in_proj(h: jax.Array, lp: Params, c: ModelConfig):
    """[.., D] -> qkv [.., C], z [.., d_value], b, a [.., Hv] (activation type)."""
    with jax.named_scope("in_proj"):
        out = jnp.einsum("...d,ed->...e", h, lp["w_in"])
        C, dv, Hv = c.gdn_conv_dim, c.gdn_value_dim, c.gdn_n_v_heads
        if c.kda:  # z is ONE gate a head; a is w_alpha's product (_decay)
            return out[..., :C], out[..., C:C + Hv], out[..., C + Hv:], None
        return (out[..., :C], out[..., C:C + dv], out[..., C + dv:C + dv + Hv],
                out[..., C + dv + Hv:])


def _decay(h: jax.Array, lp: Params, c: ModelConfig) -> jax.Array:
    """Kimi Delta Attention's log-decay, one a key channel: [.., D] -> g [..,
    H, d_k] float32 in ``[kda_lower_bound, 0)``."""
    with jax.named_scope("decay"):
        a = jnp.einsum("...d,ed->...e", h, lp["w_alpha"], preferred_element_type=jnp.float32)
        a = a.reshape(*a.shape[:-1], c.gdn_n_v_heads, c.gdn_d_k)
        rate = jnp.exp(lp["A_log"].astype(jnp.float32))[:, None]
        return c.kda_lower_bound * jax.nn.sigmoid(
            rate * (a + lp["dt_bias"].astype(jnp.float32)))


def _l2(x: jax.Array) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)


def _heads(qkv_act: jax.Array, b: jax.Array, a: jax.Array, lp: Params, c: ModelConfig):
    """Activated conv output [.., C] float32 -> q, k [.., Hv, d_k] (normalised,
    ``q`` over sqrt(d_k), a key head repeated for its value heads), v [.., Hv,
    d_v], beta, g [.., Hv] float32.  ``a`` of a Kimi Delta Attention layer IS
    its ``g`` [.., H, d_k] (:func:`_decay`)."""
    Hk, Hv, dk, dv = c.gdn_n_k_heads, c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v
    lead = qkv_act.shape[:-1]
    kd = c.gdn_key_dim
    q = _l2(qkv_act[..., :kd].reshape(*lead, Hk, dk)) * (1.0 / math.sqrt(dk))
    k = _l2(qkv_act[..., kd:2 * kd].reshape(*lead, Hk, dk))
    q = jnp.repeat(q, Hv // Hk, axis=-2)  # value head j reads key head j // (Hv / Hk)
    k = jnp.repeat(k, Hv // Hk, axis=-2)
    v = qkv_act[..., 2 * kd:].reshape(*lead, Hv, dv)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    if c.kda:
        return q, k, v, beta, a
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    return q, k, v, beta, g


def _gate_out(o: jax.Array, z: jax.Array, lp: Params, c: ModelConfig, out_dtype: Any):
    """rmsnorm(o) * w * silu(z) per value head (Kimi Delta Attention: times
    sigmoid(z), ONE z a head), then the output projection."""
    with jax.named_scope("gate_norm"):
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        y = o * lax.rsqrt(var + c.norm_eps) * lp["norm"].astype(jnp.float32)
        if c.kda:
            gate = jax.nn.sigmoid(z.astype(jnp.float32))[..., None]
        else:
            gate = jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
        y = (y * gate).reshape(*o.shape[:-2], c.gdn_value_dim).astype(out_dtype)
    with jax.named_scope("out_proj"):
        return jnp.einsum("...e,ed->...d", y, lp["w_out"])


def delta_step_xla(
    all_S: jax.Array,  # [Lg, B, Hv, d_k, d_v] the stacked state
    im: jax.Array,  # which layer's slice
    q: jax.Array,  # [B, Hv, d_k]
    k: jax.Array,  # [B, Hv, d_k]
    v: jax.Array,  # [B, Hv, d_v]
    beta: jax.Array,  # [B, Hv]
    g: jax.Array,  # [B, Hv] log decay, <= 0; or one a key channel [B, Hv, d_k]
    active: jax.Array | None,  # [B] bool; None: every row advances
) -> tuple[jax.Array, jax.Array]:
    """The decode step's pass over layer ``im``'s ``S``, in XLA -> (o [B,
    Hv, d_v] float32, the state): one reduction reads ``S`` for both
    products, one fusion reads it again and writes the update in place."""
    S_old = _layer_of(all_S, im)
    by_channel = g.ndim == 3  # the decay scales S by key ROW, not by head
    S = S_old.astype(jnp.float32) * (
        jnp.exp(g)[..., None] if by_channel else jnp.exp(g)[..., None, None])
    both = jnp.einsum("bhkv,bhkj->bhjv", S, jnp.stack([k, q], axis=-1), precision=_HI)
    delta = (v - both[:, :, 0]) * beta[..., None]
    o = both[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    new_S = (S + k[..., :, None] * delta[..., None, :]).astype(S_old.dtype)
    if active is not None:
        new_S = jnp.where(active[:, None, None, None], new_S, S_old)
    return o, lax.dynamic_update_index_in_dim(all_S, new_S, im, 0)


def gdn_step(
    h: jax.Array,  # [B, 1, D] the normed stream
    lp: Params,  # one DeltaNet layer's leaves
    state: tuple[jax.Array, jax.Array],  # (S [Lg, B, Hv, d_k, d_v], conv [Lg, d_conv - 1, B, C])
    im: jax.Array,  # which DeltaNet layer this is: its slice of ``state``
    active: jax.Array | None,  # [B] bool; None: every row advances
    config: ModelConfig,
    ssm_impl: str = "xla",  # InferenceEngine._resolved_ssm_impl: "xla" | "pallas" | "pallas_interpret"
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One token through the mixer -> (out [B, 1, D], state).  The layer's
    slice of the stacked state is read and rewritten INSIDE the ``conv`` and
    ``state`` scopes: the device time of touching the state has to read
    under those names, whatever implements the pass: :func:`delta_step_xla`,
    which is the reference, or the kernel that reads and writes the active
    rows' ``S`` once (``pallas_gdn.delta_step_pallas``)."""
    c = config
    all_S, all_conv = state
    qkv, z, b, a = _in_proj(h[:, 0], lp, c)
    if c.kda:
        a = _decay(h[:, 0], lp, c)
    with jax.named_scope("conv"):
        conv = _layer_of(all_conv, im)
        window = jnp.concatenate([conv, qkv[None].astype(conv.dtype)], axis=0)  # [d_conv, B, C]
        w = lp["conv_w"].astype(jnp.float32)
        act = jax.nn.silu(jnp.einsum("kbc,kc->bc", window.astype(jnp.float32), w, precision=_HI))
        new_conv = window[1:]
        if active is not None:
            new_conv = jnp.where(active[None, :, None], new_conv, conv)
        all_conv = lax.dynamic_update_index_in_dim(all_conv, new_conv, im, 0)
    with jax.named_scope("state"):
        heads = _heads(act, b, a, lp, c)
        if ssm_impl.startswith("pallas"):
            from calfkit_tpu.inference.pallas_gdn import delta_step_pallas

            o, all_S = delta_step_pallas(
                all_S, im, *heads, active, interpret=ssm_impl == "pallas_interpret")
        else:
            o, all_S = delta_step_xla(all_S, im, *heads, active)
    return _gate_out(o, z, lp, c, h.dtype)[:, None], (all_S, all_conv)


def delta_chunks(
    q: jax.Array,  # [B, T, H, d_k] float32, normalised and scaled
    k: jax.Array,  # [B, T, H, d_k]
    v: jax.Array,  # [B, T, H, d_v]
    beta: jax.Array,  # [B, T, H], zero at padding
    g: jax.Array,  # [B, T, H] log decay, zero at padding
    S0: jax.Array,  # [B, H, d_k, d_v] float32
    block: int,
) -> tuple[jax.Array, jax.Array]:
    """The chunkwise form of the gated delta rule over T positions, entered
    with ``S0`` -> (o [B, T, H, d_v], S_T)."""
    B, T, H, dk = q.shape
    Q = block if T % block == 0 else T
    nc = T // Q

    def blocks(x):  # [B, T, H, ..] -> [nc, B, H, Q, ..]: a block's faces last
        x = x.reshape(B, nc, Q, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v = blocks(q), blocks(k), blocks(v)
    beta, g = blocks(beta), blocks(g)  # [nc, B, H, Q]
    gc = jnp.cumsum(g, axis=-1)  # inclusive: decay from the block's start through i
    i, j = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(i >= j, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    # u_i = beta_i (v_i - sum_{j<i} decay_ij (k_i . k_j) u_j - exp(gc_i) S_in^T k_i):
    # unit lower triangular in u, solved for both right-hand sides at once
    A = jnp.einsum("cbhik,cbhjk->cbhij", k_beta, k, precision=_HI) * decay
    A = jnp.where(i > j, A, 0.0) + jnp.eye(Q)
    rhs = jnp.concatenate([v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1)
    solved = lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)
    value, k_cum = solved[..., : v.shape[-1]], solved[..., v.shape[-1]:]
    local = jnp.einsum("cbhik,cbhjk->cbhij", q, k, precision=_HI) * decay  # j <= i
    to_end = jnp.exp(gc[..., -1:] - gc)  # [nc, B, H, Q]

    def over_blocks(S, inputs):
        q_c, k_c, value_c, k_cum_c, local_c, gc_c, to_end_c = inputs
        u = value_c - jnp.einsum("bhik,bhkv->bhiv", k_cum_c, S, precision=_HI)
        o = (jnp.einsum("bhik,bhkv->bhiv", q_c * jnp.exp(gc_c)[..., None], S, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", local_c, u, precision=_HI))
        S = S * jnp.exp(gc_c[..., -1])[..., None, None] + jnp.einsum(
            "bhik,bhiv->bhkv", k_c * to_end_c[..., None], u, precision=_HI)
        return S, o

    S, o = lax.scan(over_blocks, S0, (q, k, value, k_cum, local, gc, to_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, nc, Q, H, d_v]
    return o.reshape(B, T, H, -1), S


def delta_chunks_by_channel(
    q: jax.Array,  # [B, T, H, d_k] float32, normalised and scaled
    k: jax.Array,  # [B, T, H, d_k]
    v: jax.Array,  # [B, T, H, d_v]
    beta: jax.Array,  # [B, T, H], zero at padding
    g: jax.Array,  # [B, T, H, d_k] log decay a key channel, zero at padding
    S0: jax.Array,  # [B, H, d_k, d_v] float32
    block: int,
    sub: int,  # positions of a sub-block: sub x max |g| stays under float32's e^88
) -> tuple[jax.Array, jax.Array]:
    """:func:`delta_chunks` with the decay a key CHANNEL (Kimi Delta
    Attention): the two-level form of the module's text -> (o [B, T, H, d_v],
    S_T).  ``T`` is padded to whole sub-blocks (to whole blocks past one
    block) with positions that move nothing."""
    B, T, H, dk = q.shape
    unit = block if T > block else sub
    Tp = -(-T // unit) * unit
    if Tp != T:
        pad = lambda x: jnp.pad(x, ((0, 0), (0, Tp - T)) + ((0, 0),) * (x.ndim - 2))  # noqa: E731
        q, k, v, beta, g = pad(q), pad(k), pad(v), pad(beta), pad(g)
    Q = min(block, Tp)
    nc, ns = Tp // Q, Q // sub

    def blocks(x):  # [B, Tp, H, ..] -> [nc, B, H, Q, ..]: a block's faces last
        x = x.reshape(B, nc, Q, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, beta, g = blocks(q), blocks(k), blocks(v), blocks(beta), blocks(g)
    gc = jnp.cumsum(g, axis=-2)  # [nc, B, H, Q, dk] inclusive: decay from the block's start
    i, j = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    # R: the running sum at the first position of each sub-block [.., ns, dk]
    ref = gc[..., ::sub, :]
    # a position against its own sub-block's reference: exponent in (-sub |g|, 0]
    to_ref = jnp.exp(gc - jnp.repeat(ref, sub, axis=-2))
    # the block's keys as sub-block a sees them: exact decays for the earlier
    # ones, at most e^((sub - 1) |g|) inside a, nothing of a later sub-block
    seen = (jnp.arange(Q) // sub)[None, :] <= jnp.arange(ns)[:, None]  # [ns, Q]
    from_ref = jnp.exp(jnp.where(
        seen[..., None], ref[..., :, None, :] - gc[..., None, :, :], -jnp.inf))
    k_seen = k[..., None, :, :] * from_ref  # [nc, B, H, ns, Q, dk]

    def pairs(x):  # [.., Q, dk] -> sum_c x_ic k_jc exp(G_ic - G_jc) [.., Q, Q]
        x = (x * to_ref).reshape(*x.shape[:-2], ns, sub, dk)
        return jnp.einsum("cbhask,cbhajk->cbhasj", x, k_seen, precision=_HI).reshape(
            *x.shape[:-3], Q, Q)

    k_beta = k * beta[..., None]
    A = jnp.where(i > j, pairs(k_beta), 0.0) + jnp.eye(Q)
    into = jnp.exp(gc)  # a position against the block's start: S_in's decay
    rhs = jnp.concatenate([v * beta[..., None], k_beta * into], axis=-1)
    solved = lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)
    value, k_cum = solved[..., : v.shape[-1]], solved[..., v.shape[-1]:]
    local = jnp.where(i >= j, pairs(q), 0.0)
    k_end = k * jnp.exp(gc[..., -1:, :] - gc)  # a key as the block's end sees it

    def over_blocks(S, inputs):
        q_in, k_end_c, value_c, k_cum_c, local_c, end_c = inputs
        u = value_c - jnp.einsum("bhik,bhkv->bhiv", k_cum_c, S, precision=_HI)
        o = (jnp.einsum("bhik,bhkv->bhiv", q_in, S, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", local_c, u, precision=_HI))
        S = S * end_c[..., None] + jnp.einsum("bhik,bhiv->bhkv", k_end_c, u, precision=_HI)
        return S, o

    S, o = lax.scan(
        over_blocks, S0, (q * into, k_end, value, k_cum, local, jnp.exp(gc[..., -1, :])))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)  # [B, nc, Q, H, d_v]
    return o.reshape(B, Tp, H, -1)[:, :T], S


def gdn_chunk(
    h: jax.Array,  # [B, T, D] the normed stream
    lp: Params,
    state: tuple[jax.Array, jax.Array],  # the rows' stacked (S, conv) entering the chunk
    im: jax.Array,  # which DeltaNet layer this is
    n_valid: jax.Array,  # [B] positions of the chunk that are the row's own
    config: ModelConfig,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """T positions through the mixer -> (out [B, T, D], state), the layer's
    states as they stand after each row's ``n_valid`` own positions."""
    c = config
    B, T, _ = h.shape
    K = c.gdn_d_conv
    all_S, all_conv = state
    qkv, z, b, a = _in_proj(h, lp, c)
    if c.kda:
        a = _decay(h, lp, c)
    with jax.named_scope("conv"):
        conv = _layer_of(all_conv, im)
        ext = jnp.concatenate([jnp.swapaxes(conv, 0, 1), qkv.astype(conv.dtype)], axis=1)
        w = lp["conv_w"].astype(jnp.float32)
        ext32 = ext.astype(jnp.float32)
        act = jax.nn.silu(sum(ext32[:, j:j + T] * w[j] for j in range(K)))
        # the last d_conv - 1 inputs the row really had
        new_conv = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(ext, n_valid)
        all_conv = lax.dynamic_update_index_in_dim(
            all_conv, jnp.swapaxes(new_conv, 0, 1), im, 0)
    with jax.named_scope("state"):
        S_old = _layer_of(all_S, im)
        q, k, v, beta, g = _heads(act, b, a, lp, c)
        own = (jnp.arange(T, dtype=jnp.int32)[None, :] < n_valid[:, None])[..., None]
        beta = jnp.where(own, beta, 0.0)
        if c.kda:
            o, S = delta_chunks_by_channel(
                q, k, v, beta, jnp.where(own[..., None], g, 0.0), S_old.astype(jnp.float32),
                c.gdn_chunk_size, c.kda_sub_block)
        else:
            o, S = delta_chunks(
                q, k, v, beta, jnp.where(own, g, 0.0), S_old.astype(jnp.float32),
                c.gdn_chunk_size)
        all_S = lax.dynamic_update_index_in_dim(all_S, S.astype(S_old.dtype), im, 0)
    return _gate_out(o, z, lp, c, h.dtype), (all_S, all_conv)
