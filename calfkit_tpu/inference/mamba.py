"""The Mamba-2 mixer, in the two forms the engine needs and that must agree.

- :func:`mamba_step` is the one-token recurrence of ``decode_loop``: per
  head ``S = exp(dt A) S + dt x (x) B`` and ``y = S C + D x``, on the rows'
  carried conv and SSM state.  Rows that are not ``active`` keep both
  states bit for bit.
- :func:`mamba_chunk` is the chunked (SSD) scan of ``chunk_loop`` and
  ``prefill``: blocks of ``mamba_chunk_size`` positions, a masked
  ``[Q, Q]`` product inside a block and a short recurrence over block end
  states between them, entered with the state the chunk before left and
  leaving the state the next one takes.  Positions at or past a row's
  ``n_valid`` are padding: their ``dt`` is zero (decay one, input nothing)
  and the conv state is read at ``n_valid``, not at the chunk's end, so
  padding moves neither state.

Layout (stacked on axis 0 over the Mamba layers, ``C`` = conv_dim):
    w_in [Lm, d_inner + C + H, D]   fused z | xBC | dt projection, no bias, kept
                                    [out, in] as HF keeps it: the layout the v5e's
                                    compiler wants for the decode step (given
                                    [in, out] it copied all 1.26 GB every dispatch)
    conv_w [Lm, d_conv, C], conv_b [Lm, C]   depthwise causal conv, tap-major
    A_log, D, dt_bias [Lm, H] float32
    norm [Lm, d_inner]              the gated RMSNorm's weight
    w_out [Lm, d_inner, D]
    mixer_norm [Lm, D]              the RMSNorm before the mixer
State (per slot): ssm [Lm, B, H, P, N] in ``config.state_dtype``; conv
[Lm, d_conv - 1, B, C] in the activations' type, oldest input first (the
batch is second to last so that the last two dimensions tile without
padding on the TPU: three rows of taps would pad to sixteen).

Everything after the input projection is float32 arithmetic (the conv,
the softplus, the decays, the state update, the gated norm); the two big
matmuls take and give the activations' type like every other matmul of
the model.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from calfkit_tpu.inference.config import ModelConfig

Params = dict[str, Any]
_HI = lax.Precision.HIGHEST  # float32 einsums of the scan: no bf16 passes


def init_mamba_params(config: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """Random Mamba-2 leaves: matrices at 1/sqrt(fan_in); ``A`` uniform in
    1-16 and ``dt`` log-uniform in 0.001-0.1 (``dt_bias`` its inverse
    softplus), the family's own initial ranges, so that some heads forget
    in tens of tokens and others remember for thousands."""
    c = config
    Lm, D, H = c.n_mamba_layers, c.d_model, c.mamba_n_heads
    keys = jax.random.split(key, 6)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    dt = jnp.exp(
        jax.random.uniform(keys[3], (Lm, H), jnp.float32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    return {
        "w_in": normal(keys[0], (Lm, c.mamba_d_in_proj, D), D),
        "conv_w": normal(keys[1], (Lm, c.mamba_d_conv, c.mamba_conv_dim), c.mamba_d_conv),
        "conv_b": jnp.zeros((Lm, c.mamba_conv_dim), dtype),
        "A_log": jnp.log(jax.random.uniform(keys[2], (Lm, H), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((Lm, H), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "norm": jnp.ones((Lm, c.mamba_d_inner), dtype),
        "w_out": normal(keys[4], (Lm, c.mamba_d_inner, D), c.mamba_d_inner),
        "mixer_norm": jnp.ones((Lm, D), dtype),
    }


def make_recurrent_state(config: ModelConfig, rows: int) -> tuple[jax.Array, jax.Array]:
    """Zeroed (ssm, conv) state for ``rows`` sequences: what a sequence
    that has seen no token carries.  A Gated DeltaNet layer's pair has the
    same layout (its matrix state is the delta rule's ``S``: gdn.py)."""
    matrix, conv = config.recurrent_state_shapes(rows)
    return (jnp.zeros(matrix, jnp.dtype(config.state_dtype)),
            jnp.zeros(conv, jnp.dtype(config.dtype)))


def _in_proj(h: jax.Array, lp: Params, c: ModelConfig):
    """[.., D] -> z [.., d_inner], xBC [.., C], dt [.., H] (activation type)."""
    with jax.named_scope("in_proj"):
        zxbcdt = jnp.einsum("...d,ed->...e", h, lp["w_in"])
        d_inner, cd = c.mamba_d_inner, c.mamba_conv_dim
        return zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + cd], zxbcdt[..., d_inner + cd:]


def _split_xbc(xbc: jax.Array, c: ModelConfig):
    """Activated conv output [.., C] -> x [.., G, E, P], B, C [.., G, N]
    (E = heads per group)."""
    G, N, P = c.mamba_n_groups, c.mamba_d_state, c.mamba_d_head
    d_inner = c.mamba_d_inner
    lead = xbc.shape[:-1]
    x = xbc[..., :d_inner].reshape(*lead, G, c.mamba_n_heads // G, P)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(*lead, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(*lead, G, N)
    return x, Bm, Cm


def _head_terms(dt_raw: jax.Array, lp: Params, c: ModelConfig):
    """dt = softplus(dt + dt_bias) [.., G, E] and A = -exp(A_log) [G, E]."""
    G = c.mamba_n_groups
    E = c.mamba_n_heads // G
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    return dt.reshape(*dt.shape[:-1], G, E), A.reshape(G, E)


def _gate_out(y: jax.Array, z: jax.Array, lp: Params, c: ModelConfig, out_dtype: Any):
    """rmsnorm(y * silu(z)) * w_norm per group, then the output projection."""
    with jax.named_scope("gate_norm"):
        G = c.mamba_n_groups
        y = y * jax.nn.silu(z.astype(jnp.float32))
        grouped = y.reshape(*y.shape[:-1], G, c.mamba_d_inner // G)
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        y = (grouped * lax.rsqrt(var + c.norm_eps)).reshape(y.shape)
        y = (y * lp["norm"].astype(jnp.float32)).astype(out_dtype)
    with jax.named_scope("out_proj"):
        return jnp.einsum("...e,ed->...d", y, lp["w_out"])


def _layer_of(stacked: jax.Array, im: jax.Array) -> jax.Array:
    return lax.dynamic_index_in_dim(stacked, im, 0, keepdims=False)


def ssm_step_xla(
    all_ssm: jax.Array,  # [Lm, B, H, P, N] the stacked state
    im: jax.Array,  # which layer's slice
    decay: jax.Array,  # [B, G, E] exp(dt A)
    dtx: jax.Array,  # [B, G, E, P] dt x
    Bm: jax.Array,  # [B, G, N]
    Cm: jax.Array,  # [B, G, N]
    active: jax.Array | None,  # [B] bool; None: every row advances
) -> tuple[jax.Array, jax.Array]:
    """The decode step's pass over layer ``im``'s SSM state, in XLA -> (y
    [B, G, E, P] float32 without the skip term, the state).  The update is
    fused into the in-place write; the readout is a second fusion that
    reads the slice again.  The reference ``pallas_ssm.ssm_step_pallas`` is
    held to, argument for argument."""
    ssm = _layer_of(all_ssm, im)
    B, G, E, P = dtx.shape
    S = ssm.astype(jnp.float32).reshape(B, G, E, P, ssm.shape[-1])
    S = S * decay[..., None, None] + dtx[..., None] * Bm[:, :, None, None, :]
    y = jnp.einsum("bgepn,bgn->bgep", S, Cm, precision=_HI)
    new_ssm = S.reshape(ssm.shape).astype(ssm.dtype)
    if active is not None:
        new_ssm = jnp.where(active[:, None, None, None], new_ssm, ssm)
    return y, lax.dynamic_update_index_in_dim(all_ssm, new_ssm, im, 0)


def mamba_step(
    h: jax.Array,  # [B, 1, D] the normed stream
    lp: Params,  # one Mamba layer's leaves
    state: tuple[jax.Array, jax.Array],  # (ssm [Lm, B, H, P, N], conv [Lm, d_conv - 1, B, C])
    im: jax.Array,  # which Mamba layer this is: its slice of ``state``
    active: jax.Array | None,  # [B] bool; None: every row advances
    config: ModelConfig,
    ssm_impl: str = "xla",  # InferenceEngine._resolved_ssm_impl: "xla" | "pallas" | "pallas_interpret"
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One token through the mixer -> (out [B, 1, D], state).  The layer's
    slice of the stacked state is read and rewritten INSIDE the ``conv``
    and ``ssm`` scopes, and the device time of touching the state has to
    read under those names.  The SSM state's pass is the XLA body below
    (the update fused into the in-place write, the readout a second fusion
    that reads the slice again), which is the reference, or the kernel that
    does both in one pass (``pallas_ssm.ssm_step_pallas``)."""
    c = config
    B = h.shape[0]
    all_ssm, all_conv = state
    z, xbc, dt_raw = _in_proj(h[:, 0], lp, c)
    with jax.named_scope("conv"):
        conv = _layer_of(all_conv, im)
        window = jnp.concatenate([conv, xbc[None].astype(conv.dtype)], axis=0)  # [d_conv, B, C]
        w = lp["conv_w"].astype(jnp.float32)
        pre = jnp.einsum("kbc,kc->bc", window.astype(jnp.float32), w, precision=_HI)
        xbc_act = jax.nn.silu(pre + lp["conv_b"].astype(jnp.float32))
        new_conv = window[1:]
        if active is not None:
            new_conv = jnp.where(active[None, :, None], new_conv, conv)
        all_conv = lax.dynamic_update_index_in_dim(all_conv, new_conv, im, 0)
    with jax.named_scope("ssm"):
        x, Bm, Cm = _split_xbc(xbc_act, c)  # [B, G, E, P], [B, G, N]
        dt, A = _head_terms(dt_raw, lp, c)  # [B, G, E], [G, E]
        G, E = A.shape
        decay = jnp.exp(dt * A)
        dtx = dt[..., None] * x
        if ssm_impl.startswith("pallas"):
            from calfkit_tpu.inference.pallas_ssm import ssm_step_pallas

            y, all_ssm = ssm_step_pallas(
                all_ssm, im, decay, dtx, Bm, Cm, active,
                interpret=ssm_impl == "pallas_interpret",
            )
        else:
            y, all_ssm = ssm_step_xla(all_ssm, im, decay, dtx, Bm, Cm, active)
        y = y + lp["D"].astype(jnp.float32).reshape(G, E)[None, :, :, None] * x
    out = _gate_out(y.reshape(B, c.mamba_d_inner), z, lp, c, h.dtype)
    return out[:, None], (all_ssm, all_conv)


def ssd_scan(
    x: jax.Array,  # [B, T, G, E, P] float32
    dt: jax.Array,  # [B, T, G, E] float32, zero at padding
    A: jax.Array,  # [G, E] negative
    Bm: jax.Array,  # [B, T, G, N]
    Cm: jax.Array,  # [B, T, G, N]
    S0: jax.Array,  # [B, G, E, P, N] float32
    block: int,
) -> tuple[jax.Array, jax.Array]:
    """The chunked (SSD) form of the recurrence over T positions, entered
    with ``S0`` -> (y [B, T, G, E, P] without the skip term, S_T)."""
    B, T, G, E, P = x.shape
    Q = block if T % block == 0 else T
    nc = T // Q
    # head-major blocks, so that the [Q, Q] and [Q, P] faces are the last
    # two dimensions (whole tiles on the TPU)
    a = jnp.moveaxis((dt * A).reshape(B, nc, Q, G, E), 2, -1)  # [B, nc, G, E, Q], <= 0
    xdt = jnp.moveaxis((x * dt[..., None]).reshape(B, nc, Q, G, E, P), 2, 4)  # [.., E, Q, P]
    Bm = jnp.moveaxis(Bm.reshape(B, nc, Q, G, -1), 2, 3)  # [B, nc, G, Q, N]
    Cm = jnp.moveaxis(Cm.reshape(B, nc, Q, G, -1), 2, 3)
    acs = jnp.cumsum(a, axis=-1)  # inclusive: decay from the block's start through i
    # inside a block: position i takes j <= i at decay exp(acs_i - acs_j)
    seg = acs[..., :, None] - acs[..., None, :]  # [B, nc, G, E, Q(i), Q(j)]
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcgin,bcgjn->bcgij", Cm, Bm, precision=_HI)
    y = jnp.einsum("bcgeij,bcgejp->bcgeip", cb[:, :, :, None] * decay, xdt, precision=_HI)
    # what each block adds to the state by its end, and the state entering it
    to_end = jnp.exp(acs[..., -1:] - acs)  # [B, nc, G, E, Q]
    added = jnp.einsum("bcgejp,bcgjn->bcgepn", xdt * to_end[..., None], Bm, precision=_HI)
    through = jnp.exp(acs[..., -1])  # [B, nc, G, E] a whole block's decay

    def over_blocks(S, inputs):
        keep, add = inputs
        return keep[..., None, None] * S + add, S

    S_end, S_in = lax.scan(
        over_blocks, S0, (jnp.moveaxis(through, 1, 0), jnp.moveaxis(added, 1, 0))
    )
    S_in = jnp.moveaxis(S_in, 0, 1)  # [B, nc, G, E, P, N]
    carried = jnp.einsum("bcgin,bcgepn->bcgeip", Cm, S_in, precision=_HI)
    y = y + carried * jnp.exp(acs)[..., None]
    return jnp.moveaxis(y, 4, 2).reshape(B, T, G, E, P), S_end


def mamba_chunk(
    h: jax.Array,  # [B, T, D] the normed stream
    lp: Params,
    state: tuple[jax.Array, jax.Array],  # the rows' stacked (ssm, conv) entering the chunk
    im: jax.Array,  # which Mamba layer this is
    n_valid: jax.Array,  # [B] positions of the chunk that are the row's own
    config: ModelConfig,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """T positions through the mixer -> (out [B, T, D], state), the layer's
    states as they stand after each row's ``n_valid`` own positions."""
    c = config
    B, T, _ = h.shape
    K = c.mamba_d_conv
    all_ssm, all_conv = state
    z, xbc, dt_raw = _in_proj(h, lp, c)
    with jax.named_scope("conv"):
        conv = _layer_of(all_conv, im)
        ext = jnp.concatenate([jnp.swapaxes(conv, 0, 1), xbc.astype(conv.dtype)], axis=1)
        w = lp["conv_w"].astype(jnp.float32)
        ext32 = ext.astype(jnp.float32)
        pre = sum(ext32[:, k:k + T] * w[k] for k in range(K))
        xbc_act = jax.nn.silu(pre + lp["conv_b"].astype(jnp.float32))
        # the last d_conv - 1 inputs the row really had
        new_conv = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(ext, n_valid)
        all_conv = lax.dynamic_update_index_in_dim(
            all_conv, jnp.swapaxes(new_conv, 0, 1), im, 0)
    with jax.named_scope("ssm"):
        ssm = _layer_of(all_ssm, im)
        x, Bm, Cm = _split_xbc(xbc_act, c)
        dt, A = _head_terms(dt_raw, lp, c)
        own = jnp.arange(T, dtype=jnp.int32)[None, :] < n_valid[:, None]
        dt = jnp.where(own[:, :, None, None], dt, 0.0)
        G, E = A.shape
        S0 = ssm.astype(jnp.float32).reshape(B, G, E, c.mamba_d_head, c.mamba_d_state)
        y, S = ssd_scan(x, dt, A, Bm, Cm, S0, c.mamba_chunk_size)
        y = y + lp["D"].astype(jnp.float32).reshape(G, E)[None, None, :, :, None] * x
        all_ssm = lax.dynamic_update_index_in_dim(
            all_ssm, S.reshape(ssm.shape).astype(ssm.dtype), im, 0)
    out = _gate_out(y.reshape(B, T, c.mamba_d_inner), z, lp, c, h.dtype)
    return out, (all_ssm, all_conv)
