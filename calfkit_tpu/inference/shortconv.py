"""The gated short convolution (LFM2's ``conv`` mixer), in the two forms the
engine needs and that must agree.

    B | C | x = h W_in                  D -> 3 D, no bias, thirds in that order
    u = B * x
    v_t = sum_j w[:, j] * u_{t - (K - 1) + j}     depthwise, causal, K = conv_L_cache
                                                  taps, ``u`` zero before the sequence,
                                                  NO activation, no bias
    y = (C * v) W_out

What a sequence leaves behind is ``u`` at its last ``K - 1`` positions and
NOTHING else: two numbers a channel at the published three taps.  It is the
conv side of the state pair the engine carries for every recurrent kind
(``[Lc, K - 1, B, D]`` in the activations' type, oldest input first: the
layout of Mamba-2's and the delta rule's tails); the pair's matrix side is
empty (``ModelConfig.recurrent_state_shapes``), and neither form touches it.

- :func:`shortconv_step` is the one-token form of ``decode_loop``: the tail
  and the token's own ``u`` under the taps.  Rows that are not ``active``
  keep their tail bit for bit.
- :func:`shortconv_chunk` is the form of ``chunk_loop`` and ``prefill``:
  entered with the tail the chunk before left (zero for a new sequence), it
  leaves the tail as it stands after each row's ``n_valid`` OWN positions:
  a chunk of one token, a row that is all padding and a chunk that starts
  mid-sequence each read the ``K - 1`` inputs that end at ``n_valid``, never
  the chunk's end.

Precision: the two matmuls take and give the activations' type; ``u`` is the
float32 product rounded ONCE to the tail's type, in both forms alike (the
step reads it back from the tail, so the chunk form sums the same numbers);
the taps are summed in float32 (``_SUM_DTYPE``), and ``C * v`` is float32
before its one rounding.

Layout (stacked on axis 0 over the conv layers):
    w_in [Lc, 3 D, D]      fused B | C | x projection, [out, in] as HF keeps it
                           and as the other recurrent mixers keep theirs
    conv_w [Lc, K, D]      depthwise taps, tap-major: tap j sees the input
                           K - 1 - j positions back (HF: conv.conv.weight
                           [D, 1, K], transposed once by the loader)
    w_out [Lc, D, D]       [in, out]
    mixer_norm [Lc, D]     the RMSNorm before the mixer (operator_norm)
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
_HI = lax.Precision.HIGHEST  # the float32 sum over the taps: no bf16 passes
_SUM_DTYPE = jnp.float32  # what the taps are summed in (``precision`` of the configuration)


def init_shortconv_params(config: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """Random conv-mixer leaves: the matrices at 1/sqrt(fan_in), the taps at
    1/sqrt(taps), the norm at 1."""
    c = config
    Lc, D, K = c.n_recurrent_layers, c.d_model, c.conv_L_cache
    keys = jax.random.split(key, 3)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    return {
        "w_in": normal(keys[0], (Lc, 3 * D, D), D),
        "conv_w": normal(keys[1], (Lc, K, D), K),
        "w_out": normal(keys[2], (Lc, D, D), D),
        "mixer_norm": jnp.ones((Lc, D), dtype),
    }


def _in_proj(h: jax.Array, lp: Params) -> tuple[jax.Array, jax.Array, jax.Array]:
    """[.., D] normed stream -> B, C, x [.., D] (activation type)."""
    with jax.named_scope("in_proj"):
        bcx = jnp.einsum("...d,ed->...e", h, lp["w_in"])
        D = bcx.shape[-1] // 3
        return bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]


def _gated_input(gate_b: jax.Array, x: jax.Array, tail_dtype: Any) -> jax.Array:
    """``u = B * x``: the float32 product, rounded once to the tail's type."""
    return (gate_b.astype(jnp.float32) * x.astype(jnp.float32)).astype(tail_dtype)


def _gate_out(v: jax.Array, gate_c: jax.Array, lp: Params, out_dtype: Any) -> jax.Array:
    """``(C * v) W_out``: the gate in float32, one rounding, the projection."""
    with jax.named_scope("out_proj"):
        y = (gate_c.astype(jnp.float32) * v.astype(jnp.float32)).astype(out_dtype)
        return jnp.einsum("...e,ed->...d", y, lp["w_out"])


def shortconv_step(
    h: jax.Array,  # [B, 1, D] the normed stream
    lp: Params,  # one conv layer's leaves
    state: tuple[jax.Array, jax.Array],  # (empty [Lc, B, 0], tail [Lc, K - 1, B, D])
    im: jax.Array,  # which conv layer this is: its slice of the tail
    active: jax.Array | None,  # [B] bool; None: every row advances
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One token through the mixer -> (out [B, 1, D], state).  The layer's
    slice of the stacked tail is read and rewritten INSIDE the ``conv`` scope."""
    empty, all_tail = state
    gate_b, gate_c, x = _in_proj(h[:, 0], lp)
    with jax.named_scope("conv"):
        tail = _layer_of(all_tail, im)  # [K - 1, B, D]
        u = _gated_input(gate_b, x, tail.dtype)
        window = jnp.concatenate([tail, u[None]], axis=0)  # [K, B, D]
        v = jnp.einsum("kbc,kc->bc", window.astype(_SUM_DTYPE),
                       lp["conv_w"].astype(_SUM_DTYPE), precision=_HI)
        new_tail = window[1:]
        if active is not None:
            new_tail = jnp.where(active[None, :, None], new_tail, tail)
        all_tail = lax.dynamic_update_index_in_dim(all_tail, new_tail, im, 0)
    return _gate_out(v, gate_c, lp, h.dtype)[:, None], (empty, all_tail)


def shortconv_chunk(
    h: jax.Array,  # [B, T, D] the normed stream
    lp: Params,
    state: tuple[jax.Array, jax.Array],  # the rows' stacked (empty, tail) entering the chunk
    im: jax.Array,  # which conv layer this is
    n_valid: jax.Array,  # [B] positions of the chunk that are the row's own
    config: ModelConfig,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """T positions through the mixer -> (out [B, T, D], state), the layer's
    tail as it stands after each row's ``n_valid`` own positions."""
    T, K = h.shape[1], config.conv_L_cache
    empty, all_tail = state
    gate_b, gate_c, x = _in_proj(h, lp)
    with jax.named_scope("conv"):
        tail = _layer_of(all_tail, im)
        u = _gated_input(gate_b, x, tail.dtype)
        ext = jnp.concatenate([jnp.swapaxes(tail, 0, 1), u], axis=1)  # [B, K - 1 + T, D]
        w = lp["conv_w"].astype(_SUM_DTYPE)
        wide = ext.astype(_SUM_DTYPE)
        v = sum(wide[:, k:k + T] * w[k] for k in range(K))
        # the last K - 1 inputs the row REALLY had: those that end at n_valid
        new_tail = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(ext, n_valid)
        all_tail = lax.dynamic_update_index_in_dim(
            all_tail, jnp.swapaxes(new_tail, 0, 1), im, 0)
    return _gate_out(v, gate_c, lp, h.dtype), (empty, all_tail)
