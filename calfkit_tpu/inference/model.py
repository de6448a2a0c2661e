"""Pure-functional Llama-family decoder in JAX.

TPU-first design decisions (NOT a port of any torch modeling file):

- params are a plain pytree of ``jax.Array`` so GSPMD shardings attach
  directly (see :mod:`calfkit_tpu.inference.sharding`);
- the whole forward is expressed in batched einsums — every FLOP lands on
  the MXU; no data-dependent Python control flow anywhere under ``jit``;
- layers run under ``lax.scan`` over a stacked-parameter pytree, so compile
  time is O(1) in depth and XLA schedules one fused layer body;
- KV cache updates are functional (``dynamic_update_slice``) — the engine
  owns cache buffers and threads them through jit;
- attention is GQA on XLA einsums (the reference, CPU-testable); the ONE
  kernel is the Pallas paged decode read (:func:`decode_step_ring_paged`).

Weight layout (per layer, stacked on axis 0 across layers):
    attn: wq [L, D, H, hd], wk/wv [L, D, K, hd], wo [L, H, hd, D]
    mlp:  w_gate/w_up [L, D, F], w_down [L, F, D]
    norms: attn_norm/mlp_norm [L, D]
    top:   embed [V, D], final_norm [D], lm_head [D, V] (absent when tied)

A hybrid stack (``config.layer_types``: Mamba-2 layers beside attention,
the SwiGLU MLP in every layer) keeps its layers in three stacked groups,
``layers = {"attn": wq wk wv wo attn_norm [La, ..], "mamba": see mamba.py
[Lm, ..], "mlp": w_gate w_up w_down mlp_norm [L, ..]}``, and runs them under
ONE ``lax.scan`` over the repeats of the layer period (``_hybrid_stack``),
the period's layers unrolled inside the body.  Only the attention layers
keep K and V (``config.n_kv_layers``); the Mamba layers' conv and SSM state
travels as ``state = (ssm [Lm, B, H, P, N], conv [Lm, d_conv-1, B, C])``.
A description without ``layer_types`` never reaches that code.

A Gated DeltaNet hybrid (``"gdn"`` among ``layer_types``: Qwen3-Next) runs
through the same ``_hybrid_stack``, with ``layers = {"attn": wq (q | gate a
head) wk wv wo attn_norm q_norm k_norm [La, ..], "gdn": see gdn.py [Lg, ..],
"moe": see moe.py [L, ..]}``: the recurrent mixer is the delta rule on the
same state pair, the attention mixer is gated (per-head q and k norms, the
rotation on the leading part of a head, ``o * sigmoid(gate)``), every norm
multiplies by ``1 + w``, and EVERY layer's FFN is the expert block, its
counters threaded through the scan as ``_latent_stack`` threads them.

A window stack (``"window"`` among ``layer_types``: Cohere2-MoE,
command-a-plus; Mellum 2) keeps ``layers = {"attn": wq wk wv wo attn_norm [L,
..], "moe": see moe.py [L, ..]}`` and runs under ONE ``lax.scan`` over the
period's repeats (``_window_stack``), the block the description names:
command-a-plus's ONE LayerNorm a layer, whose output the attention and the
expert block both read (the parallel block), rotary on the window layers and
no position at all on the global ones; Mellum 2's sequential RMSNorm block
with its second norm (``mlp_norm``), an untied head, and the rotation BY
KIND: the plain law on the window layers and the global layers' own
(``config.rope_scaling_global``: YaRN), a cos/sin table a kind, each built
once a step under ``rope/window`` and ``rope/global``.  Its caches come BY
KIND (``config.CACHE_KINDS``): every paged thing of such a model is a pair
``(global, window)``: each side of the pool ``([Lg, Ng, K, page / f, f *
hd], [Lw, Nw, K, page / f, f * hd])`` (as stored, :func:`positions_per_row`:
``f`` = 1 at heads of 128), the block tables ``([B, Pg], [B, R])``, a wave's
destination pages.  A row's window table is a RING of ``R`` pages: position
``p`` of a window layer lies in table entry ``(p // page) % R``, so a row
that grows writes over (gives back) what its window has left behind, and a
read takes a LOWER bound beside the causal one (``_window_ring_valid``, the
window form of the Pallas decode kernel, ``blocked_attention``).  The
prefill scratch and the decode ring of fresh tokens stay one pair over all
the layers, in stack order.  A description without a window layer never
reaches that code.

A latent-attention stack (``config.kv_lora_rank``: DeepSeek-V3's MLA, with
routed experts after the leading dense layers when ``n_routed_experts``)
keeps its layers in up to three stacked groups,
``layers = {"attn": wq w_kva kv_norm w_uk w_uv wo attn_norm [L, ..],
"dense": w_gate w_up w_down mlp_norm [Ld, ..], "moe": see moe.py [Lm, ..]}``,
runs the leading dense layers unrolled and the expert layers under ONE
``lax.scan`` (``_latent_stack``).  What a token leaves behind there is
``[c | k_rope]`` after the norm and the rotation, ``kv_lora_rank +
qk_rope_head_dim`` numbers a layer and nothing else.  Every cache here (page
pool, decode ring, prefill scratch) is a pair of arrays of the layout
``[.., heads, .., width]``: K and V per head, or, for a latent model, the
latent's two parts ``(c, k_rope)`` with heads 1 (apart, so that the wide
part is whole lane tiles), and the functions that move cache bytes map over
the pair whatever its widths.  The PAGE POOL of K and V pairs alone is
STORED ``[.., heads, page / f, f * width]`` where the head is narrower than
a lane tile (:func:`positions_per_row`, ``f`` positions side by side in a
row: the same numbers in the same order, the form both the decode kernel
and the write's loop take as it lies on a TPU); its readers and writers
(:func:`gather_window_paged`, the paged decode kernel,
:func:`consolidate_ring_paged`, :func:`write_prefill_pages`) read ``f`` off
the pool's lanes and the head's width, which the ring, the scratch, the
queries or ``config.cache_dims`` carry.  The mixer has two algebras that
must agree: *expanded* in ``forward`` (prefill and chunks: ``k_nope`` and
``v`` expanded from the window's ``c``, the published form) and *absorbed* in
the decode step (``W_uk`` folded into the query and ``W_uv`` applied after
the read: 16 query heads against ONE key of 512 + 64 numbers whose first
512 are also the value).  A description without ``kv_lora_rank`` never reaches
that code either.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from calfkit_tpu.inference.config import ATTENTION, WINDOW, ModelConfig
from calfkit_tpu.inference.gdn import gdn_chunk, gdn_step, init_gdn_params
from calfkit_tpu.inference.mamba import (
    init_mamba_params,
    mamba_chunk,
    mamba_step,
)
from calfkit_tpu.inference.moe import init_moe_params, moe_ffn
from calfkit_tpu.inference.quant import dequant as _w
from calfkit_tpu.inference.shortconv import (
    init_shortconv_params,
    shortconv_chunk,
    shortconv_step,
)

Params = dict[str, Any]


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #


def init_params(config: ModelConfig, key: jax.Array, dtype: Any = None) -> Params:
    """Random-init params (He-ish scaling); the loader overwrites these with
    checkpoint weights when one is given."""
    dtype = dtype or jnp.dtype(config.dtype)
    L, D, H, K, hd, F, V = (
        config.n_layers,
        config.d_model,
        config.n_heads,
        config.n_kv_heads,
        config.head_dim,
        config.d_ff,
        config.vocab_size,
    )
    keys = jax.random.split(key, 8)

    def norm_init(k, shape, fan_in, gain=1.0):
        scale = gain / math.sqrt(fan_in)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    if config.latent:
        r, dn, dr, dv = (config.kv_lora_rank, config.qk_nope_head_dim,
                         config.qk_rope_head_dim, config.v_head_dim)
        Ld = config.n_dense_layers
        akeys = jax.random.split(keys[1], 5)
        # a Kimi Delta Attention hybrid: latent attention in its attention
        # layers alone (one gate a head on their output), the delta rule's
        # leaves beside them; else every layer's mixer is latent attention
        La = config.n_kv_layers
        layers: Params = {
            "attn": {
                "wq": norm_init(akeys[0], (La, D, H, dn + dr), D),
                "w_kva": norm_init(akeys[1], (La, D, r + dr), D),
                "kv_norm": jnp.ones((La, r), dtype),
                "w_uk": norm_init(akeys[2], (La, r, H, dn), r),
                "w_uv": norm_init(akeys[3], (La, r, H, dv), r),
                "wo": norm_init(akeys[4], (La, H, dv, D), H * dv),
                "attn_norm": jnp.ones((La, D), dtype),
                **({"w_z": norm_init(jax.random.fold_in(keys[1], 5), (La, D, H), D)}
                   if config.attn_output_gate else {}),
            },
            **({"gdn": init_gdn_params(config, jax.random.split(keys[3])[0], dtype)}
               if config.kda else {}),
            "dense": {
                "w_gate": norm_init(keys[5], (Ld, D, F), D),
                "w_up": norm_init(keys[6], (Ld, D, F), D),
                "w_down": norm_init(keys[7], (Ld, F, D), F),
                "mlp_norm": jnp.ones((Ld, D), dtype),
            },
        }
        if config.moe:
            layers["moe"] = init_moe_params(config, keys[2], dtype)
        return {
            "embed": norm_init(keys[0], (V, D), D),
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
            **({} if config.tie_embeddings else {
                "lm_head": norm_init(jax.random.split(keys[0])[0], (D, V), D)}),
        }

    if config.eva:
        # an EvaByte-style stack (eva.py): a head of num_pred_heads x vocab rows
        from calfkit_tpu.inference.eva import init_eva_params

        return {
            "embed": norm_init(keys[0], (V, D), D),
            "layers": init_eva_params(config, keys[1], dtype),
            "final_norm": (jnp.zeros if config.norm_plus_one else jnp.ones)((D,), dtype),
            "lm_head": norm_init(
                jax.random.split(keys[0])[0], (D, config.num_pred_heads * V), D),
        }

    if config.windowed:
        layers = {
            "attn": {
                "wq": norm_init(keys[1], (L, D, H, hd), D),
                "wk": norm_init(keys[2], (L, D, K, hd), D),
                "wv": norm_init(keys[3], (L, D, K, hd), D),
                "wo": norm_init(keys[4], (L, H, hd, D), H * hd),
                "attn_norm": jnp.ones((L, D), dtype),
            },
            "moe": init_moe_params(config, keys[5], dtype),
        }
        if config.parallel_block:  # ONE norm a layer
            layers["moe"].pop("mlp_norm")
        return {
            "embed": norm_init(keys[0], (V, D), D),
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
            **({} if config.tie_embeddings else {
                "lm_head": norm_init(jax.random.split(keys[0])[0], (D, V), D)}),
        }

    if config.expert_hybrid:
        # a hybrid whose FFN is the expert block (a Kimi Delta Attention one
        # took the latent branch above): norms that multiply by (1 + w) start
        # at w = 0; W_q gives q | gate a head when the output is gated.  Beside
        # the attention layers the Gated DeltaNet group, or the short
        # convolutions' with the leading dense layers' SwiGLU
        La = config.n_kv_layers
        one = jnp.zeros if config.norm_plus_one else jnp.ones
        q_out = hd * (2 if config.attn_output_gate else 1)
        layers = {
            "attn": {
                "wq": norm_init(keys[1], (La, D, H, q_out), D),
                "wk": norm_init(keys[2], (La, D, K, hd), D),
                "wv": norm_init(keys[3], (La, D, K, hd), D),
                "wo": norm_init(keys[4], (La, H, hd, D), H * hd),
                "attn_norm": one((La, D), dtype),
                **({"q_norm": one((La, hd), dtype), "k_norm": one((La, hd), dtype)}
                   if config.qk_norm else {}),
            },
            "moe": init_moe_params(config, keys[5], dtype),
        }
        mixer_key = jax.random.split(keys[1])[0]
        if config.shortconv:
            Ld = config.n_dense_layers
            layers["conv"] = init_shortconv_params(config, mixer_key, dtype)
            layers["dense"] = {
                "w_gate": norm_init(keys[6], (Ld, D, F), D),
                "w_up": norm_init(keys[7], (Ld, D, F), D),
                "w_down": norm_init(jax.random.split(keys[7])[0], (Ld, F, D), F),
                "mlp_norm": jnp.ones((Ld, D), dtype),
            }
        else:
            layers["gdn"] = init_gdn_params(config, mixer_key, dtype)
        return {
            "embed": norm_init(keys[0], (V, D), D),
            "layers": layers,
            "final_norm": one((D,), dtype),
            **({} if config.tie_embeddings else {
                "lm_head": norm_init(jax.random.split(keys[0])[0], (D, V), D)}),
        }

    if config.layer_types:
        # A hybrid stack: with a TIED head, embedding_multiplier 12 and
        # residual_multiplier 0.22, matrices at 1/sqrt(fan_in) leave the
        # stream nearly equal to the input token's embedding, and the
        # argmax is that token again whatever the layers compute.  So the
        # matrices that write to the stream are drawn 1/residual_multiplier
        # larger: every layer's update is then as large a share of the
        # stream as in a decoder without the multiplier, and the layers
        # decide the logits.
        La, out = config.n_kv_layers, 1.0 / config.residual_multiplier
        mamba = init_mamba_params(config, jax.random.split(keys[1])[0], dtype)
        mamba["w_out"] = (mamba["w_out"].astype(jnp.float32) * out).astype(dtype)
        return {
            "embed": norm_init(keys[0], (V, D), D),
            "layers": {
                "attn": {
                    "wq": norm_init(keys[1], (La, D, H, hd), D),
                    "wk": norm_init(keys[2], (La, D, K, hd), D),
                    "wv": norm_init(keys[3], (La, D, K, hd), D),
                    "wo": norm_init(keys[4], (La, H, hd, D), H * hd, out),
                    "attn_norm": jnp.ones((La, D), dtype),
                },
                "mamba": mamba,
                "mlp": {
                    "w_gate": norm_init(keys[5], (L, D, F), D),
                    "w_up": norm_init(keys[6], (L, D, F), D),
                    "w_down": norm_init(keys[7], (L, F, D), F, out),
                    "mlp_norm": jnp.ones((L, D), dtype),
                },
            },
            "final_norm": jnp.ones((D,), dtype),
            **({} if config.tie_embeddings else {
                "lm_head": norm_init(jax.random.split(keys[0])[0], (D, V), D)}),
        }

    params: Params = {
        "embed": norm_init(keys[0], (V, D), D),
        "layers": {
            "wq": norm_init(keys[1], (L, D, H, hd), D),
            "wk": norm_init(keys[2], (L, D, K, hd), D),
            "wv": norm_init(keys[3], (L, D, K, hd), D),
            "wo": norm_init(keys[4], (L, H, hd, D), H * hd),
            "w_gate": norm_init(keys[5], (L, D, F), D),
            "w_up": norm_init(keys[6], (L, D, F), D),
            "w_down": norm_init(keys[7], (L, F, D), F),
            "attn_norm": jnp.ones((L, D), dtype),
            "mlp_norm": jnp.ones((L, D), dtype),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = norm_init(jax.random.split(keys[0])[0], (D, V), D)
    return params


# --------------------------------------------------------------------------- #
# building blocks
# --------------------------------------------------------------------------- #


def rms_norm(x: jax.Array, weight: jax.Array, eps: float, plus_one: bool = False) -> jax.Array:
    """``plus_one``: the norm multiplies by ``1 + w`` (``config.norm_plus_one``)."""
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * lax.rsqrt(var + eps)
    weight = weight.astype(jnp.float32)
    if plus_one:
        weight = 1.0 + weight
    return (normed * weight).astype(orig_dtype)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """``w * (x - mean) / sqrt(var + eps)`` in float32: a weight and NO bias
    (Cohere's LayerNorm; not an RMSNorm: the mean goes)."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float, scaling: Any = None) -> tuple[jax.Array, float]:
    """A rotation law as ``(inv_freq [hd/2] float32, scale)``: the plain law
    ``theta^(-2i/hd)`` with scale 1, or under ``scaling`` (a
    ``config.RopeScaling``) YaRN's: the plain frequency below the ramp, that
    over ``factor`` above it, and cos and sin both times ``scaling.scale``."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if scaling is None or scaling.rope_type == "default":
        return freqs, 1.0
    low, high = scaling.correction_range(head_dim, theta)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * freqs + ramp * (freqs / scaling.factor), scaling.scale


def rope_tables(
    positions: jax.Array, freqs: jax.Array, scale: float = 1.0
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for ``positions`` [..., seq] → [..., seq, hd/2] of the
    law ``(freqs, scale)`` (:func:`rope_frequencies`)."""
    angles = positions[..., None].astype(jnp.float32) * freqs
    if scale == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs. x: [B, S, N, hd]; cos/sin: [B, S, hd/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _einsum_f32(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """einsum with fp32 accumulation.  TPU: ``preferred_element_type`` (MXU
    accumulates fp32 natively, no input copies).  CPU XLA lacks the
    bf16×bf16→f32 dot kernel, so inputs upcast there (tests only)."""
    if a.dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _gqa_scores_mask(
    q_pos: jax.Array, kv_len: int, seq_lens: jax.Array
) -> jax.Array:
    """Causal + length mask [B, Sq, Skv] (True = attendable)."""
    kv_pos = jnp.arange(kv_len)[None, None, :]
    causal = kv_pos <= q_pos[:, :, None]
    valid = kv_pos < seq_lens[:, None, None]
    return causal & valid


def attn_qkv(
    x: jax.Array,  # [B, S, D]
    lp: Params,  # one layer's params
    cos: jax.Array | None,
    sin: jax.Array | None,
    eps: float,
    q_scale: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The block's attention front half: norm → QKV projections → rope.

    Shared by prefill, decode, and the sequence-parallel ring — ONE place
    for the projection math.  ``cos`` None: no rotary embedding.
    ``q_scale`` multiplies the queries, for a model whose scores are not
    scaled by 1/sqrt(head_dim): every attention core keeps that one law
    and the queries carry the ratio (:func:`_q_scale`).
    """
    with jax.named_scope("qkv"):
        h = rms_norm(x, lp["attn_norm"], eps)
        q = jnp.einsum("bsd,dnh->bsnh", h, _w(lp["wq"]))
        k = jnp.einsum("bsd,dkh->bskh", h, _w(lp["wk"]))
        v = jnp.einsum("bsd,dkh->bskh", h, _w(lp["wv"]))
        if q_scale is not None:
            q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
        if cos is None:
            return q, k, v
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def gated_attn_qkv(
    x: jax.Array,  # [B, S, D]
    lp: Params,  # one gated-attention layer's leaves
    cos: jax.Array,
    sin: jax.Array,
    config: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array | None]:
    """The gated attention's front half (Qwen3-Next) -> (q, k, v, gate):
    norm -> ``[q | gate]`` a head from W_q, K, V -> RMSNorm over each query
    and key head -> the rotation on the FIRST ``rotary_dim`` of a head, the
    rest left as it is.  ``gate`` [B, S, H, hd] is the output gate's logits
    (None without one): :func:`attn_out_gate` applies it after the read."""
    c = config
    eps, plus, hd, rot = c.norm_eps, c.norm_plus_one, c.head_dim, c.rotary_dim
    with jax.named_scope("qkv"):
        h = rms_norm(x, lp["attn_norm"], eps, plus)
        q = jnp.einsum("bsd,dnh->bsnh", h, lp["wq"])
        k = jnp.einsum("bsd,dkh->bskh", h, lp["wk"])
        v = jnp.einsum("bsd,dkh->bskh", h, lp["wv"])
        q, gate = (q[..., :hd], q[..., hd:]) if c.attn_output_gate else (q, None)
    if c.qk_norm:
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, lp["q_norm"], eps, plus)
            k = rms_norm(k, lp["k_norm"], eps, plus)
    if cos is not None:
        q = jnp.concatenate([apply_rope(q[..., :rot], cos, sin), q[..., rot:]], axis=-1)
        k = jnp.concatenate([apply_rope(k[..., :rot], cos, sin), k[..., rot:]], axis=-1)
    return q, k, v, gate


def attn_out_gate(attn: jax.Array, gate: jax.Array | None) -> jax.Array:
    """``o * sigmoid(gate)`` (float32), before the output projection."""
    if gate is None:
        return attn
    with jax.named_scope("out_gate"):
        return (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
            attn.dtype)


def _q_scale(config: ModelConfig) -> float | None:
    """``attention_multiplier`` over the cores' own 1/sqrt(head_dim)."""
    if config.attention_multiplier is None:
        return None
    return float(config.attention_multiplier) * math.sqrt(config.head_dim)


def _hybrid_qkv(x, lp, cos, sin, config: ModelConfig):
    """A hybrid stack's attention front half -> (q, k, v, gate or None)."""
    if config.expert_hybrid:
        return gated_attn_qkv(x, lp, cos, sin, config)
    return (*attn_qkv(x, lp, cos, sin, config.norm_eps, _q_scale(config)), None)


def _positions_tables(config: ModelConfig, positions: jax.Array):
    if config.position_embedding == "none":
        return None, None
    return rope_tables(positions, *rope_frequencies(config.rotary_dim, config.rope_theta))


def _embed(params: Params, config: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]  # [B, S, D] gather
    if config.embedding_multiplier != 1.0:
        x = (x.astype(jnp.float32) * config.embedding_multiplier).astype(x.dtype)
    return x


def attn_out_mlp(
    x: jax.Array,  # [B, S, D] residual stream
    attn: jax.Array,  # [B, S, H, hd]
    lp: Params,
    eps: float,
    residual: float = 1.0,
) -> jax.Array:
    """The block's back half: output projection + residual + SwiGLU MLP."""
    with jax.named_scope("attn_out"):
        x = _add(x, jnp.einsum("bsnh,nhd->bsd", attn, _w(lp["wo"])), residual)
    return mlp_residual(x, lp, eps, residual)


def _add(x: jax.Array, update: jax.Array, residual: float) -> jax.Array:
    """``x + residual * update`` (the multiplier applied in float32)."""
    if residual == 1.0:
        return x + update
    return x + (update.astype(jnp.float32) * residual).astype(x.dtype)


def mlp_residual(x: jax.Array, lp: Params, eps: float, residual: float = 1.0) -> jax.Array:
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], eps)
        gate = jnp.einsum("bsd,df->bsf", h, _w(lp["w_gate"]))
        up = jnp.einsum("bsd,df->bsf", h, _w(lp["w_up"]))
        return _add(
            x,
            jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, _w(lp["w_down"])),
            residual,
        )


def lm_logits(
    x: jax.Array, params: Params, eps: float, scaling: float = 1.0, plus_one: bool = False,
    norm: str = "rms",
) -> jax.Array:
    """Final norm + (tied or untied) LM head; ``scaling`` divides the logits."""
    with jax.named_scope("lm_head"):
        if norm == "layer":
            x = layer_norm(x, params["final_norm"], eps)
        else:
            x = rms_norm(x, params["final_norm"], eps, plus_one)
        head = params.get("lm_head")
        if head is None:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, _w(head))
        if scaling != 1.0:
            logits = (logits.astype(jnp.float32) / scaling).astype(logits.dtype)
        return logits


def _layer(group: Params, i: Any) -> Params:
    """ONE layer's leaves, sliced from a stacked group where they are used:
    XLA reads such a slice in place, inside the matmul that takes it.
    Scanning over the period's layers as ``xs`` instead made it copy a
    period's weights before every use (compiled for the v5e: 1.5 GB written
    and read again a period)."""
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), group)


def _hybrid_stack(
    config: ModelConfig,
    layers: Params,
    x: jax.Array,
    carry: Any,
    attn_layer: Any,  # (carry, x, lp, ia) -> (carry, attn [B, S, H, hd])
    mamba_layer: Any,  # (carry, h, lp, im) -> (carry, y [B, S, D])
    stats: Any = None,  # moe.py's counters (a stack with routed experts), or None
    valid: jax.Array | None = None,  # [B, S] bool: the tokens that are real
    moe_step_impl: str = "xla",  # a decode step's routed products (moe.moe_ffn)
) -> tuple[jax.Array, Any, Any]:
    """Run a hybrid stack: ONE ``lax.scan`` over the repeats of the layer
    period, the period's layers unrolled in its body, so compile time
    follows the period and not the depth.  ``ia`` / ``im`` are the layer's
    index among the attention / recurrent (Mamba-2 or Gated DeltaNet)
    layers (traced), which is where its K and V / its recurrent state live
    in ``carry``.  The decode step and the prefill chunk differ only in the
    two callbacks.  A stack whose FFN is the expert block (``config.moe``)
    threads the experts' counters through the scan and returns them third."""
    eps, rm = config.norm_eps, config.residual_multiplier
    period = config.layer_period
    n = config.n_layers // len(period)
    a_per = period.count(ATTENTION)
    m_per = len(period) - a_per
    if config.expert_hybrid:
        return _expert_hybrid_stack(
            config, layers, x, carry, attn_layer, mamba_layer, stats, valid, moe_step_impl)

    def body(c, p):
        x, carry = c
        ja = jm = 0
        for j, kind in enumerate(period):
            mlp_lp = _layer(layers["mlp"], p * len(period) + j)
            if kind == ATTENTION:
                ia = p * a_per + ja
                lp = {**_layer(layers["attn"], ia), **mlp_lp}
                carry, attn = attn_layer(carry, x, lp, ia)
                x = attn_out_mlp(x, attn, lp, eps, rm)
                ja += 1
            else:
                im = p * m_per + jm
                lp = _layer(layers["mamba"], im)
                with jax.named_scope("mamba"):
                    h = rms_norm(x, lp["mixer_norm"], eps)
                    carry, y = mamba_layer(carry, h, lp, im)
                    x = _add(x, y, rm)
                x = mlp_residual(x, mlp_lp, eps, rm)
                jm += 1
        return (x, carry), None

    (x, carry), _ = lax.scan(body, (x, carry), jnp.arange(n, dtype=jnp.int32))
    return x, carry, stats


def _recurrent_mixer(config: ModelConfig):
    """An expert hybrid's recurrent kind -> (the scope its mixer reads under,
    the group its leaves stand in)."""
    if config.shortconv:
        return jax.named_scope("shortconv"), "conv"
    return jax.named_scope("gdn"), "gdn"


def _expert_hybrid_stack(config, layers, x, carry, attn_layer, recurrent_layer, stats, valid,
                         moe_step_impl="xla"):
    """:func:`_hybrid_stack` for a hybrid whose FFN is the expert block
    (``config.expert_hybrid``: a delta rule, Gated DeltaNet or Kimi Delta
    Attention, or a gated short convolution as the recurrent mixer): the same
    one scan over the period's repeats with its layers unrolled in the body;
    the norms multiply by ``1 + w`` where the description says so, the
    recurrent mixer reads under the scope and from the group of leaves its
    kind names (:func:`_recurrent_mixer`), and every layer's FFN is the
    expert block -> (x, carry, stats).  A stack with leading dense layers
    (``config.stack_plan``) runs its head unrolled before the scan, a leading
    layer's FFN the SwiGLU of ``d_ff``; a stack with a latent pool reads its
    attention layers under ``mla``."""
    eps, plus = config.norm_eps, config.norm_plus_one
    head, period = config.stack_plan
    a_per = period.count(ATTENTION)
    m_per = len(period) - a_per
    nd = config.first_k_dense

    def layer(x, carry, stats, kind, il, ia, im, dense=False):
        if kind == ATTENTION:
            lp = _layer(layers["attn"], ia)
            with jax.named_scope("mla") if config.latent else contextlib.nullcontext():
                carry, attn = attn_layer(carry, x, lp, ia)
                with jax.named_scope("attn_out"):
                    x = x + jnp.einsum("bsnh,nhd->bsd", attn, lp["wo"])
        else:
            scope, group = _recurrent_mixer(config)
            lp = _layer(layers[group], im)
            with scope:
                carry, y = recurrent_layer(
                    carry, rms_norm(x, lp["mixer_norm"], eps, plus), lp, im)
                x = x + y
        if dense:  # a leading layer: one SwiGLU of d_ff
            return mlp_residual(x, _layer(layers["dense"], il), eps), carry, stats
        m = il - nd if nd else il
        lp = _layer(layers["moe"], m)
        with jax.named_scope("mlp"):
            y, stats = moe_ffn(
                rms_norm(x, lp["mlp_norm"], eps, plus), lp, config, stats, valid, m,
                layers["moe"], moe_step_impl)
        return x + y, carry, stats

    ja = jm = 0
    for il, kind in enumerate(config.layer_types[:head]):
        x, carry, stats = layer(x, carry, stats, kind, il, ja, jm, dense=il < nd)
        ja, jm = ja + (kind == ATTENTION), jm + (kind != ATTENTION)
    a0, m0 = ja, jm

    def body(c, p):
        x, carry, stats = c
        ja = jm = 0
        for j, kind in enumerate(period):
            # the head's layers come first of every index; a stack without a head
            # adds nothing (its traced program stays what it was before heads)
            il = p * len(period) + j + head if head else p * len(period) + j
            if kind == ATTENTION:
                ia = p * a_per + ja + a0 if a0 else p * a_per + ja
                x, carry, stats = layer(x, carry, stats, kind, il, ia, None)
                ja += 1
            else:
                im = p * m_per + jm + m0 if m0 else p * m_per + jm
                x, carry, stats = layer(x, carry, stats, kind, il, None, im)
                jm += 1
        return (x, carry, stats), None

    (x, carry, stats), _ = lax.scan(
        body, (x, carry, stats),
        jnp.arange((config.n_layers - head) // len(period), dtype=jnp.int32))
    return x, carry, stats


# --------------------------------------------------------------------------- #
# latent attention (MLA) and the stack it lives in
# --------------------------------------------------------------------------- #


def mla_project(
    x: jax.Array,  # [B, S, D]
    lp: Params,  # one layer's attention leaves
    cos: jax.Array,
    sin: jax.Array,
    config: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The mixer's front half → (q_nope [B,S,H,dn], q_rope [B,S,H,dr]
    rotated, c [B,S,r], k_rope [B,S,dr]): ``c`` AFTER the norm and
    ``k_rope`` AFTER the rotation, which is what the cache keeps of a token."""
    c = config
    r, dn = c.kv_lora_rank, c.qk_nope_head_dim
    h = rms_norm(x, lp["attn_norm"], c.norm_eps)
    with jax.named_scope("q_proj"):
        q = jnp.einsum("bsd,dnh->bsnh", h, lp["wq"])
        q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
    with jax.named_scope("kv_latent"):
        kva = jnp.einsum("bsd,dc->bsc", h, lp["w_kva"])
        latent = rms_norm(kva[..., :r], lp["kv_norm"], c.kv_norm_eps)
        k_rope = apply_rope(kva[..., None, r:], cos, sin)[:, :, 0]
    return q_nope, q_rope, latent, k_rope


@jax.named_scope("attention")
def mla_attention_expanded(
    q_nope: jax.Array,  # [B, Sq, H, dn]
    q_rope: jax.Array,  # [B, Sq, H, dr]
    c_w: jax.Array,  # [B, W, r] the rows' cached latents ...
    k_rope: jax.Array,  # [B, W, dr] ... and their rotated rope keys
    lp: Params,
    q_pos: jax.Array,  # [B, Sq]
    seq_lens: jax.Array,  # [B]
    config: ModelConfig,
) -> jax.Array:
    """The published form: ``k_nope`` and ``v`` of every head expanded from
    the window's ``c``, the one ``k_rope`` beside every head's ``k_nope``,
    scores over ``sqrt(dn + dr)`` → [B, Sq, H, dv]."""
    k_nope = jnp.einsum("bwc,cnh->bwnh", c_w, lp["w_uk"])
    v = jnp.einsum("bwc,cnh->bwnh", c_w, lp["w_uv"])
    scores = (
        _einsum_f32("bqnh,bwnh->bnqw", q_nope, k_nope)
        + _einsum_f32("bqnh,bwh->bnqw", q_rope, k_rope)
    ) / math.sqrt(config.head_dim)
    mask = _gqa_scores_mask(q_pos, c_w.shape[1], seq_lens)
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(c_w.dtype)
    return _einsum_f32("bnqw,bwnh->bqnh", probs, v).astype(q_nope.dtype)


def mla_absorb_query(q_nope: jax.Array, lp: Params) -> jax.Array:
    """``q_nope W_uk^T`` → [B, S, H, r]: the query against ``c`` itself."""
    with jax.named_scope("absorb"):
        return jnp.einsum("bsnh,cnh->bsnc", q_nope, lp["w_uk"])


def mla_absorb_out(o_lat: jax.Array, lp: Params) -> jax.Array:
    """The read gives ``sum_t P_t c_t``; ``W_uv`` takes it to the heads'
    values → [B, S, H, dv]."""
    with jax.named_scope("absorb"):
        return jnp.einsum("bsnc,cnh->bsnh", o_lat.astype(lp["w_uv"].dtype), lp["w_uv"])


def mla_head_gate(attn: jax.Array, x: jax.Array, lp: Params, config: ModelConfig) -> jax.Array:
    """``o_head * sigmoid(h W_z)[head]`` before ``W_o``: ONE gate a head
    (``attn_output_gate`` of a latent-attention layer), in float32; ``attn``
    [B, S, H, dv] as it is without the leaf."""
    if "w_z" not in lp:
        return attn
    with jax.named_scope("out_gate"):
        h = rms_norm(x, lp["attn_norm"], config.norm_eps)
        gate = _einsum_f32("bsd,dn->bsn", h, lp["w_z"])
        return (attn.astype(jnp.float32) * jax.nn.sigmoid(gate)[..., None]).astype(attn.dtype)


@jax.named_scope("attention")
def mla_merged_decode_attention(
    q_lat: jax.Array,  # [B, 1, H, r]
    q_rope: jax.Array,  # [B, 1, H, dr]
    window: tuple[jax.Array, jax.Array],  # ([B, 1, W, r], [B, 1, W, dr]) main pages
    ring: tuple[jax.Array, jax.Array],  # ([T, B, 1, r], [T, B, 1, dr]) this layer's ring
    base_lens: jax.Array,  # [B]
    t: jax.Array,  # current step (ring slots 0..t valid)
    scale: float,  # 1 / sqrt(dn + dr): the scores' law, whatever is absorbed
) -> jax.Array:
    """The absorbed read → ``sum_t P_t c_t`` [B, 1, H, r] float32: every
    head scores ONE key a token, ``q_lat . c + q_rope . k_rope``, and the
    value is ``c`` again; softmax over (main cache ⊕ ring) by the same
    two-source logsumexp merge as :func:`_merged_decode_attention`."""
    q_lat, q_rope = q_lat[:, 0], q_rope[:, 0]  # [B, H, ..]
    source1 = mla_window_attention_source(q_lat, q_rope, window, base_lens, scale)
    source2 = mla_ring_attention_source(q_lat, q_rope, ring, t, scale)
    return logsumexp_merge(source1, source2)[:, None]


def mla_window_attention_source(
    q_lat: jax.Array,  # [B, H, r]
    q_rope: jax.Array,  # [B, H, dr]
    window: tuple[jax.Array, jax.Array],  # ([B, 1, W, r], [B, 1, W, dr]) main pages
    base_lens: jax.Array,  # [B]
    scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The absorbed read's main-cache source over gathered windows →
    (o unnormalized, m, z): the law the latent decode kernel
    (``pallas_attention._latent_decode_kernel``) keeps, in its roundings."""
    c_w, r_w = window[0][:, 0], window[1][:, 0]
    s1 = (_einsum_f32("bhc,bwc->bhw", q_lat, c_w) + _einsum_f32("bhr,bwr->bhw", q_rope, r_w)) * scale
    valid1 = jnp.arange(c_w.shape[1])[None, :] < base_lens[:, None]
    s1 = jnp.where(valid1[:, None, :], s1, -1e30)
    m1 = jnp.maximum(jnp.max(s1, axis=-1, keepdims=True), -1e29)  # empty rows stay finite
    p1 = jnp.exp(s1 - m1).astype(c_w.dtype)
    z1 = jnp.sum(p1.astype(jnp.float32), axis=-1, keepdims=True)
    return _einsum_f32("bhw,bwc->bhc", p1, c_w), m1, z1


def mla_ring_attention_source(
    q_lat: jax.Array,  # [B, H, r]
    q_rope: jax.Array,  # [B, H, dr]
    ring: tuple[jax.Array, jax.Array],  # ([T, B, 1, r], [T, B, 1, dr]) this layer's ring
    t: jax.Array,  # ring slots 0..t valid
    scale: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The absorbed read's fresh-token source (tiny: T ≤ steps a dispatch) →
    (o unnormalized, m, z): shared by the XLA and Pallas merged reads, as
    :func:`ring_attention_source` is for K and V."""
    c_r, r_r = ring[0][:, :, 0], ring[1][:, :, 0]
    s2 = (_einsum_f32("bhc,tbc->bht", q_lat, c_r) + _einsum_f32("bhr,tbr->bht", q_rope, r_r)) * scale
    s2 = jnp.where((jnp.arange(c_r.shape[0]) <= t)[None, None, :], s2, -1e30)
    m2 = jnp.max(s2, axis=-1, keepdims=True)
    p2 = jnp.exp(s2 - m2).astype(c_r.dtype)
    z2 = jnp.sum(p2.astype(jnp.float32), axis=-1, keepdims=True)
    o2 = _einsum_f32("bht,tbc->bhc", p2, c_r)
    return o2, m2, z2


def _latent_stack(
    config: ModelConfig,
    layers: Params,
    x: jax.Array,
    carry: Any,
    mixer: Any,  # (carry, x, lp, i) -> (carry, attn [B, S, H, dv])
    stats: Any,  # moe.py's counters, or None
    valid: jax.Array | None,  # [B, S] bool: the tokens that are real
    moe_step_impl: str = "xla",  # a decode step's routed products (moe.moe_ffn)
) -> tuple[jax.Array, Any, Any]:
    """Run a latent-attention stack: the leading dense layers unrolled,
    then ONE ``lax.scan`` over the expert layers, so compile time does not
    follow the depth.  ``i`` is the layer's index in the stack, which is
    where its latent lives in ``carry``.  The decode step and the prefill
    chunk differ only in ``mixer``."""
    eps = config.norm_eps
    nd = config.n_dense_layers

    def attend(carry, x, i):
        lp = _layer(layers["attn"], i)
        with jax.named_scope("mla"):
            carry, attn = mixer(carry, x, lp, i)
            with jax.named_scope("attn_out"):
                x = x + jnp.einsum("bsnh,nhd->bsd", attn, lp["wo"])
        return carry, x

    for i in range(nd):
        carry, x = attend(carry, x, i)
        x = mlp_residual(x, _layer(layers["dense"], i), eps)
    if not config.moe:
        return x, carry, stats

    def body(c, m):
        x, carry, stats = c
        carry, x = attend(carry, x, nd + m)
        lp = _layer(layers["moe"], m)
        with jax.named_scope("mlp"):
            y, stats = moe_ffn(
                rms_norm(x, lp["mlp_norm"], eps), lp, config, stats, valid, m, layers["moe"],
                moe_step_impl)
        return (x + y, carry, stats), None

    (x, carry, stats), _ = lax.scan(
        body, (x, carry, stats), jnp.arange(config.n_moe_layers, dtype=jnp.int32)
    )
    return x, carry, stats


def _rope_dim_tables(config: ModelConfig, positions: jax.Array):
    return rope_tables(positions, *rope_frequencies(config.qk_rope_head_dim, config.rope_theta))


def _mla_chunk_mixer(cache, x, lp, i, cos, sin, config, positions, seq_lens, W, insert_at):
    """A latent layer over a chunk (the expanded algebra): the fresh latents
    go into layer ``i``'s rows of ``cache`` (c side, rope side) -> (cache,
    attn [B, S, H, dv])."""
    q_nope, q_rope, *fresh = mla_project(x, lp, cos, sin, config)
    pages = tuple(
        _insert_chunk(lax.dynamic_index_in_dim(side, i, 0, keepdims=False),
                      part[:, :, None, :], insert_at)
        for side, part in zip(cache, fresh))
    attn = mla_attention_expanded(
        q_nope, q_rope, pages[0][:, 0, :W], pages[1][:, 0, :W], lp, positions, seq_lens,
        config)
    cache = tuple(lax.dynamic_update_index_in_dim(side, page, i, 0)
                  for side, page in zip(cache, pages))
    return cache, attn


def _mla_step_mixer(ring, x, lp, i, t, cos, sin, config, attn_source):
    """A latent layer's decode step (the absorbed algebra): the fresh latent
    goes to slot ``t`` of layer ``i``'s ring, ``attn_source`` reads (main
    cache (+) ring) with ``c`` as key AND value -> (ring, out [B, 1, H, dv])."""
    q_nope, q_rope, *fresh = mla_project(x, lp, cos, sin, config)
    ring = tuple(
        lax.dynamic_update_slice(
            side, part[:, 0].astype(side.dtype)[None, None, :, None, :], (i, t, 0, 0, 0))
        for side, part in zip(ring, fresh))
    o_lat = attn_source(
        i, (mla_absorb_query(q_nope, lp), q_rope),
        *(lax.dynamic_index_in_dim(side, i, 0, keepdims=False) for side in ring), None)
    return ring, mla_absorb_out(o_lat, lp)


def _latent_forward(params, config, tokens, positions, kv_cache, seq_lens, W, insert_at,
                    stats, n_valid):
    """``forward`` for a latent-attention stack (the expanded algebra)."""
    # kv_cache: ([L, B, 1, Smax, r], [L, B, 1, Smax, dr])
    x = params["embed"][tokens]
    cos, sin = _rope_dim_tables(config, positions)
    valid = None
    if n_valid is not None:
        valid = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :] < n_valid[:, None]

    def mixer(cache, x, lp, i):
        return _mla_chunk_mixer(
            cache, x, lp, i, cos, sin, config, positions, seq_lens, W, insert_at)

    x, cache, stats = _latent_stack(
        config, params["layers"], x, tuple(kv_cache), mixer, stats, valid)
    logits = lm_logits(x, params, config.norm_eps)
    if stats is None:
        return logits, cache
    return logits, cache, stats


def _latent_decode_step(params, config, tokens, ring, t, base_lens, attn_source, stats,
                        active, moe_step_impl="xla"):
    """One decode step of a latent-attention stack (the absorbed algebra):
    the fresh latent goes to the ring, ``attn_source`` reads (main cache ⊕
    ring) with ``c`` as key AND value."""
    # ring: ([L, T, B, 1, r], [L, T, B, 1, dr])
    positions = (base_lens + t)[:, None]
    x = params["embed"][tokens]
    cos, sin = _rope_dim_tables(config, positions)
    valid = None if active is None else active[:, None]

    def mixer(ring, x, lp, i):
        return _mla_step_mixer(ring, x, lp, i, t, cos, sin, config, attn_source)

    x, ring, stats = _latent_stack(
        config, params["layers"], x, tuple(ring), mixer, stats, valid, moe_step_impl)
    logits = lm_logits(x, params, config.norm_eps)
    if stats is None:
        return logits, ring
    return logits, ring, stats


def attention_xla(
    q: jax.Array,  # [B, Sq, H, hd]
    k_cache: jax.Array,  # [B, K, Skv, hd]  (kv-head-major: contiguous scans)
    v_cache: jax.Array,  # [B, K, Skv, hd]
    q_pos: jax.Array,  # [B, Sq] absolute positions of the queries
    seq_lens: jax.Array,  # [B] total valid kv per sequence
) -> jax.Array:
    """GQA attention over the cache, masked by position/length.

    The XLA path: one batched einsum pair the compiler fuses tightly; used
    for prefill, chunks and the long-context lane.
    The cache is kv-head-major ([B, K, S, hd]) so each head's scan over S is
    a contiguous HBM stream, and accumulation is fp32 via
    ``preferred_element_type`` — the bf16 cache is never materialized as an
    fp32 copy (HBM is the decode bottleneck).
    """
    B, Sq, H, hd = q.shape
    K = k_cache.shape[1]
    G = H // K  # query heads per kv head
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, K, G, hd)
    scores = _einsum_f32("bqkgh,bksh->bkgqs", qg, k_cache) * scale
    mask = _gqa_scores_mask(q_pos, k_cache.shape[2], seq_lens)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(k_cache.dtype)
    out = _einsum_f32("bkgqs,bksh->bqkgh", probs, v_cache)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


# the scope a device trace groups a prefill or chunk layer's attention by
prefill_attention = jax.named_scope("attention")(attention_xla)


# --------------------------------------------------------------------------- #
# window layers beside global ones (Cohere2-MoE): the stack and its reads
# --------------------------------------------------------------------------- #

# the scope a device trace groups each kind's attention core by, under
# ``attention``: ``decode_loop/.../attention/window`` and ``.../attention/global``
def _kind_scope(kind: str) -> Any:
    return jax.named_scope("window") if kind == WINDOW else jax.named_scope("global")


# keys a chunk's attention holds scores for at once (blocked_attention)
CHUNK_KEY_BLOCK = 512


def _window_ring_valid(
    ring_tokens: int,  # R x page: the positions a row's ring of pages holds
    base_lens: jax.Array,  # [B] keys written so far (positions 0 .. base - 1)
    q_pos: jax.Array,  # [B] the query's position
    window: int,
) -> jax.Array:
    """Which entries of a row's window ring a query may attend -> [B, R page].

    Entry ``r`` holds the NEWEST position ``p < base`` with ``p = r (mod R
    page)``: the ring is written in order, so what lay there before is what
    the window has given back.  Attendable iff such a ``p`` exists and lies
    inside the lower bound, ``p > q_pos - window`` (the causal bound holds by
    construction: ``p < base <= q_pos``)."""
    r = jnp.arange(ring_tokens, dtype=jnp.int32)[None, :]
    newest = r + ring_tokens * ((base_lens[:, None] - 1 - r) // ring_tokens)  # < 0: never written
    return (newest >= 0) & (newest > q_pos[:, None] - window)


def blocked_attention(
    q: jax.Array,  # [B, S, H, hd]
    k_cache: jax.Array,  # [B, K, P, hd] (the scratch of one layer)
    v_cache: jax.Array,
    q_pos: jax.Array,  # [B, S] absolute positions of the queries
    seq_lens: jax.Array,  # [B] valid kv per row
    window: int = 0,  # > 0: query i sees key j iff i - window < j <= i
    block: int = 0,  # keys a block; 0: CHUNK_KEY_BLOCK
) -> jax.Array:
    """A chunk's GQA attention a KEY BLOCK at a time with the running
    maximum (the flash law of :func:`logsumexp_merge`): at 128 query heads
    the scores of every head over a whole context are ``128 x chunk x
    context x 4 B`` (8.6 GB for 1,024 x 16,384), so only ``block`` keys'
    scores exist at once.  The loop runs over the blocks that CAN be seen:
    to the longest row's length, and for a window layer from the block that
    holds the first query's lower bound, so a window layer's chunk reads
    ``window + chunk`` keys whatever the context.  Same roundings as
    :func:`attention_xla` (scores and statistics float32, ``p`` in the
    cache's type before the PV product)."""
    B, S, H, hd = q.shape
    K, P = k_cache.shape[1:3]
    kb = math.gcd(P, block or CHUNK_KEY_BLOCK)
    qg = q.reshape(B, S, K, H // K, hd)
    scale = 1.0 / math.sqrt(hd)
    hi = (jnp.max(seq_lens) + kb - 1) // kb
    lo = jnp.maximum(jnp.min(q_pos) - window + 1, 0) // kb if window else 0

    def body(j, carry):
        m, z, o = carry
        kj = lax.dynamic_slice_in_dim(k_cache, j * kb, kb, axis=2)
        vj = lax.dynamic_slice_in_dim(v_cache, j * kb, kb, axis=2)
        s = _einsum_f32("bskgh,bkwh->bkgsw", qg, kj) * scale
        kv_pos = j * kb + jnp.arange(kb, dtype=jnp.int32)[None, None, :]
        valid = (kv_pos <= q_pos[:, :, None]) & (kv_pos < seq_lens[:, None, None])
        if window:
            valid = valid & (kv_pos > q_pos[:, :, None] - window)
        s = jnp.where(valid[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new).astype(k_cache.dtype)
        z = z * alpha + jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
        o = o * alpha + _einsum_f32("bkgsw,bkwh->bkgsh", p, vj)
        return m_new, z, o

    lead = (B, K, H // K, S)
    m, z, o = lax.fori_loop(lo, hi, body, (
        jnp.full((*lead, 1), -1e29, jnp.float32),  # a fully masked query stays finite
        jnp.zeros((*lead, 1), jnp.float32),
        jnp.zeros((*lead, hd), jnp.float32),
    ))
    out = o / jnp.maximum(z, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def _window_qkv(h, lp, cos, sin):
    """The two kinds' shared front half on the layer's ONE normed input:
    plain GQA projections, no bias, no q/k norm; the rotation over the whole
    head where the kind has one (``cos`` None: none)."""
    with jax.named_scope("qkv"):
        q = jnp.einsum("bsd,dnh->bsnh", h, lp["wq"])
        k = jnp.einsum("bsd,dkh->bskh", h, lp["wk"])
        v = jnp.einsum("bsd,dkh->bskh", h, lp["wv"])
        if cos is None:
            return q, k, v
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _window_stack(
    config: ModelConfig,
    layers: Params,
    x: jax.Array,
    carry: Any,
    attn_layer: Any,  # (carry, kind, q, k, v, il, ik) -> (carry, attn [B, S, H, hd])
    positions: jax.Array,  # [B, S]
    stats: Any,
    valid: jax.Array | None,
    moe_step_impl: str = "xla",  # a decode step's routed products (moe.moe_ffn)
) -> tuple[jax.Array, Any, Any]:
    """Run a window stack: ONE ``lax.scan`` over the repeats of the layer
    period (W W W G), its layers unrolled in the body.  The block is the one
    the description names: ``config.norm`` (LayerNorm or RMSNorm) and, with
    ``config.parallel_block``, ``h = norm(x); x = x + Attn(h) + FFN(h)`` (ONE
    norm, one residual add); else the sequential block with its second norm.
    ``il`` is the layer's index in the stack (its rows in the scratch and the
    fresh-token ring), ``ik`` its index among the layers of its KIND (its
    pages in that kind's pool).  The decode step and the prefill chunk differ
    only in ``attn_layer``."""
    eps = config.norm_eps
    period = config.layer_period
    norm = {"layer": layer_norm, "rms": rms_norm}[config.norm]
    # the rotation BY KIND: each kind that rotates gets its own table, built
    # once a step outside the scan (the window layers by the plain law; the
    # global ones, where they rotate, by theirs: config.rope_scaling_global)
    laws = {WINDOW: None}
    if config.position_embedding == "rope":
        laws[ATTENTION] = config.rope_scaling_global
    tables = {WINDOW: (None, None), ATTENTION: (None, None)}
    for kind, scaling in laws.items():
        with jax.named_scope("rope"), _kind_scope(kind):
            tables[kind] = rope_tables(
                positions, *rope_frequencies(config.rotary_dim, config.rope_theta, scaling))

    def body(c, p):
        x, carry, stats = c
        seen = {WINDOW: 0, ATTENTION: 0}
        for j, kind in enumerate(period):
            il = p * len(period) + j
            ik = p * period.count(kind) + seen[kind]
            seen[kind] += 1
            lp, mp = _layer(layers["attn"], il), _layer(layers["moe"], il)
            h = norm(x, lp["attn_norm"], eps)
            q, k, v = _window_qkv(h, lp, *tables[kind])
            carry, attn = attn_layer(carry, kind, q, k, v, il, ik)
            with jax.named_scope("attn_out"):
                a = jnp.einsum("bsnh,nhd->bsd", attn, lp["wo"])
            if not config.parallel_block:
                x = x + a
                h = norm(x, mp["mlp_norm"], eps)
            with jax.named_scope("mlp"):
                y, stats = moe_ffn(
                    h, mp, config, stats, valid, il, layers["moe"], moe_step_impl)
            x = x + a + y if config.parallel_block else x + y
        return (x, carry, stats), None

    (x, carry, stats), _ = lax.scan(
        body, (x, carry, stats),
        jnp.arange(config.n_layers // len(period), dtype=jnp.int32))
    return x, carry, stats


def _window_forward(params, config, tokens, positions, kv_cache, seq_lens, insert_at,
                    stats, n_valid, chunk_attn_impl="xla"):
    """``forward`` for a window stack: a prefill or a chunk against the
    wave's scratch ``[L, B, K, P, hd]`` (every layer, every position: a
    window layer's ring is filled from it when the wave lands).

    ``chunk_attn_impl`` (static; ``InferenceEngine._resolved_chunk_attn_impl``
    chose it) names what computes a layer's attention: :func:`blocked_attention`
    ("xla", the reference) or the Pallas kernel that is the same flash law
    with the scores kept in VMEM (``pallas_attention.chunk_attention_pallas``),
    which takes a row's positions as CONSECUTIVE from its first: a prefill's
    and a chunk's are (offset + 0 .. S - 1)."""
    x = params["embed"][tokens]
    valid = None
    if n_valid is not None:
        valid = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :] < n_valid[:, None]

    def attn_layer(cache, kind, q, k, v, il, ik):
        k_all, v_all = cache
        k_page = _insert_chunk(lax.dynamic_index_in_dim(k_all, il, 0, keepdims=False), k, insert_at)
        v_page = _insert_chunk(lax.dynamic_index_in_dim(v_all, il, 0, keepdims=False), v, insert_at)
        window = config.sliding_window if kind == WINDOW else 0
        with jax.named_scope("attention"), _kind_scope(kind):
            if chunk_attn_impl.startswith("pallas"):
                from calfkit_tpu.inference.pallas_attention import chunk_attention_pallas

                attn = chunk_attention_pallas(
                    q, k_page, v_page, positions[:, 0], seq_lens, window=window,
                    interpret=chunk_attn_impl == "pallas_interpret")
            else:
                attn = blocked_attention(q, k_page, v_page, positions, seq_lens, window=window)
        return (lax.dynamic_update_index_in_dim(k_all, k_page, il, 0),
                lax.dynamic_update_index_in_dim(v_all, v_page, il, 0)), attn

    x, cache, stats = _window_stack(
        config, params["layers"], x, tuple(kv_cache), attn_layer, positions, stats, valid)
    logits = lm_logits(x, params, config.norm_eps, norm=config.norm)
    if stats is None:
        return logits, cache
    return logits, cache, stats


def _window_decode_step(params, config, tokens, ring, t, base_lens, attn_source, stats, active,
                        moe_step_impl="xla"):
    """One decode step of a window stack: the fresh K and V go to the
    dispatch's ring of fresh tokens (all layers, stack order), and
    ``attn_source(kind, ik, q, ring_k_i, ring_v_i)`` reads (that kind's pages
    + the fresh tokens)."""
    positions = (base_lens + t)[:, None]
    x = params["embed"][tokens]
    valid = None if active is None else active[:, None]

    def attn_layer(ring, kind, q, k, v, il, ik):
        ring_k, ring_v = ring
        ring_k = lax.dynamic_update_slice(
            ring_k, k[:, 0].astype(ring_k.dtype)[None, None], (il, t, 0, 0, 0))
        ring_v = lax.dynamic_update_slice(
            ring_v, v[:, 0].astype(ring_v.dtype)[None, None], (il, t, 0, 0, 0))
        attn = attn_source(
            kind, ik, q,
            lax.dynamic_index_in_dim(ring_k, il, 0, keepdims=False),
            lax.dynamic_index_in_dim(ring_v, il, 0, keepdims=False))
        return (ring_k, ring_v), attn

    x, ring, stats = _window_stack(
        config, params["layers"], x, tuple(ring), attn_layer, positions, stats, valid,
        moe_step_impl)
    logits = lm_logits(x, params, config.norm_eps, norm=config.norm)
    if stats is None:
        return logits, ring
    return logits, ring, stats


def _kind_decode_attention(kind, q, main_source, ring_k, ring_v, t):
    """(a kind's pages + the fresh tokens) merged, under ``attention/<kind>``."""
    B, _, H, hd = q.shape
    K = ring_k.shape[2]
    with jax.named_scope("attention"), _kind_scope(kind):
        qg = q.reshape(B, K, H // K, hd)
        out = logsumexp_merge(main_source(qg), ring_attention_source(qg, ring_k, ring_v, t))
        return out.reshape(B, 1, H, hd).astype(q.dtype)


def _window_decode_step_paged(params, config, tokens, pool, tables, ring, t, base_lens,
                              wpages, attn_impl, active, moe, moe_step_impl="xla"):
    """:func:`decode_step_ring_paged` for a window stack: the pool's sides
    and the tables are pairs by kind.  A global layer reads its row's pages
    ``0 .. ceil(len / page)`` as every model does; a window layer reads its
    row's RING: every entry that holds a position inside ``(q - W, q]``."""
    (kg, kw), (vg, vw) = pool
    tg, tw = tables
    W, hd = config.sliding_window, config.head_dim
    pallas, interpret = attn_impl.startswith("pallas"), attn_impl == "pallas_interpret"
    read_lens = base_lens if active is None else jnp.where(active, base_lens, 0)
    q_pos = base_lens + t

    def attn_source(kind, ik, q, rk, rv):
        if pallas:
            from calfkit_tpu.inference.pallas_attention import paged_decode_attention_pallas

            def main(qg):
                if kind == WINDOW:
                    o, m, z = paged_decode_attention_pallas(
                        qg, kw, vw, ik, tw, read_lens, wpages=tw.shape[1], interpret=interpret,
                        window_starts=jnp.maximum(q_pos - W + 1, 0))
                else:
                    o, m, z = paged_decode_attention_pallas(
                        qg, kg, vg, ik, tg, read_lens, wpages=wpages, interpret=interpret)
                return o, m[..., None], z[..., None]
        elif kind == WINDOW:
            def main(qg):
                k_ring = gather_window_paged(
                    lax.dynamic_index_in_dim(kw, ik, 0, keepdims=False), tw, tw.shape[1], hd)
                v_ring = gather_window_paged(
                    lax.dynamic_index_in_dim(vw, ik, 0, keepdims=False), tw, tw.shape[1], hd)
                return masked_attention_source(
                    qg, k_ring, v_ring, _window_ring_valid(k_ring.shape[2], base_lens, q_pos, W))
        else:
            def main(qg):
                k_win = gather_window_paged(
                    lax.dynamic_index_in_dim(kg, ik, 0, keepdims=False), tg, wpages, hd)
                v_win = gather_window_paged(
                    lax.dynamic_index_in_dim(vg, ik, 0, keepdims=False), tg, wpages, hd)
                return masked_attention_source(
                    qg, k_win, v_win, jnp.arange(k_win.shape[2])[None, :] < base_lens[:, None])
        return _kind_decode_attention(kind, q, main, rk, rv, t)

    return _window_decode_step(
        params, config, tokens, ring, t, base_lens, attn_source, moe, active, moe_step_impl)



# --------------------------------------------------------------------------- #
# the transformer
# --------------------------------------------------------------------------- #


def forward(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, S] int32
    positions: jax.Array,  # [B, S] absolute positions
    kv_cache: tuple[jax.Array, jax.Array] | None,  # ([L,B,K,Smax,hd], ...)
    seq_lens: jax.Array,  # [B] kv length AFTER inserting this chunk
    attn_window: int | None = None,  # static: attend only cache[..., :W, :]
    unroll: bool = False,  # static: python layer loop (the decode hot path)
    insert_at: jax.Array | None = None,  # [B] explicit per-row write offset
    state: tuple[jax.Array, jax.Array] | None = None,  # hybrid: (ssm, conv) of the rows
    n_valid: jax.Array | None = None,  # hybrid: [B] positions of the chunk that are the row's own
    moe: tuple[jax.Array, jax.Array] | None = None,  # routed experts: their counters (moe.py)
    chunk_attn_impl: str = "xla",  # static; a window stack's chunk attention (_window_forward)
) -> Any:
    """Run the decoder over a token chunk, updating the cache functionally.

    Works for prefill (S = prompt chunk) and decode (S = 1) alike; the
    engine jits specializations per shape/window.  ``attn_window`` bounds
    the attention scan to the first W cache positions — the engine picks the
    smallest bucket covering every live sequence, so short conversations
    never pay full-``max_seq`` HBM reads.

    ``unroll=True`` trades compile time for the decode-critical memory
    pattern: layers indexed statically, so the chunk's K/V writes land
    in-place in the donated cache (bytes ∝ chunk) instead of round-tripping
    a full 2×[B,K,S,hd] page per layer through a scan carry (measured ~2x
    end-to-end decode slowdown).  Returns (logits, new_cache).

    A hybrid stack also takes the rows' recurrent ``state`` as the chunk
    before left it and how many of the chunk's positions are each row's
    own (``n_valid``; the rest is padding, which moves no state), and
    returns (logits, new_cache, new_state).  A stack with routed experts
    takes its counters (``moe``: ``moe.moe_stats_init``) beside that, counts
    the positions that are the rows' own (``n_valid``) and returns them last.
    """
    eps = config.norm_eps
    if insert_at is None:
        # default: the chunk is fully valid and ends at seq_lens.  An
        # explicit insert_at serves RAGGED chunks (speculative draft
        # catch-up: per-row valid lengths shorter than the padded width)
        insert_at = seq_lens - tokens.shape[1]  # where this chunk lands
    if config.eva:  # one window against the wave's scratch of summaries (eva.py)
        from calfkit_tpu.inference.eva import eva_forward

        return eva_forward(params, config, tokens, positions, kv_cache, chunk_attn_impl)
    k_pages, v_pages = kv_cache  # [L, B, K, Smax, hd]
    W = attn_window or k_pages.shape[3]
    if config.latent and not config.layer_types:
        return _latent_forward(params, config, tokens, positions, kv_cache, seq_lens, W,
                               insert_at, moe, n_valid)
    if config.windowed:
        return _window_forward(params, config, tokens, positions, kv_cache, seq_lens,
                               insert_at, moe, n_valid, chunk_attn_impl)
    if config.layer_types:
        x = _embed(params, config, tokens)
        cos, sin = (_rope_dim_tables if config.latent else _positions_tables)(config, positions)
        if n_valid is None:
            n_valid = jnp.full(tokens.shape[:1], tokens.shape[1], jnp.int32)

        def latent_layer(carry, x, lp, ia):  # as _latent_forward's, the state riding along
            *cache, st = carry
            cache, attn = _mla_chunk_mixer(
                cache, x, lp, ia, cos, sin, config, positions, seq_lens, W, insert_at)
            return (*cache, st), mla_head_gate(attn, x, lp, config)

        def attn_layer(carry, x, lp, ia):
            k_all, v_all, st = carry
            q, k, v, gate = _hybrid_qkv(x, lp, cos, sin, config)
            k_page = _insert_chunk(
                lax.dynamic_index_in_dim(k_all, ia, 0, keepdims=False), k, insert_at)
            v_page = _insert_chunk(
                lax.dynamic_index_in_dim(v_all, ia, 0, keepdims=False), v, insert_at)
            attn = prefill_attention(
                q, k_page[:, :, :W], v_page[:, :, :W], positions, seq_lens
            )
            k_all = lax.dynamic_update_index_in_dim(k_all, k_page, ia, 0)
            v_all = lax.dynamic_update_index_in_dim(v_all, v_page, ia, 0)
            return (k_all, v_all, st), attn_out_gate(attn, gate)

        def mamba_layer(carry, h, lp, im):
            k_all, v_all, st = carry
            chunk = (shortconv_chunk if config.shortconv else
                     gdn_chunk if config.gdn else mamba_chunk)
            y, st = chunk(h, lp, st, im, n_valid, config)
            return (k_all, v_all, st), y

        valid = None
        if config.moe:
            valid = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :] < n_valid[:, None]
        x, (new_k, new_v, state), moe = _hybrid_stack(
            config, params["layers"], x, (k_pages, v_pages, state),
            latent_layer if config.latent else attn_layer, mamba_layer, moe, valid,
        )
        logits = lm_logits(x, params, eps, config.logits_scaling, config.norm_plus_one)
        return (logits, (new_k, new_v), state, *(() if moe is None else (moe,)))

    x = params["embed"][tokens]  # [B, S, D] gather
    cos, sin = rope_tables(positions, *rope_frequencies(config.head_dim, config.rope_theta))
    layer_params = params["layers"]

    def layer_math(x, lp, k_page, v_page):
        """One block given this layer's cache page; returns (x, k, v chunk).

        The caller owns how pages are read/written (scan carry vs static).
        """
        q, k, v = attn_qkv(x, lp, cos, sin, eps)
        k_page = _insert_chunk(k_page, k, insert_at)
        v_page = _insert_chunk(v_page, v, insert_at)
        attn = prefill_attention(
            q, k_page[:, :, :W], v_page[:, :, :W], positions, seq_lens
        )
        return attn_out_mlp(x, attn, lp, eps), k_page, v_page

    if unroll:
        new_k, new_v = k_pages, v_pages
        for i in range(config.n_layers):
            lp = jax.tree.map(lambda a: a[i], layer_params)
            x, k_page, v_page = layer_math(x, lp, new_k[i], new_v[i])
            new_k = new_k.at[i].set(k_page)
            new_v = new_v.at[i].set(v_page)
    else:
        def layer_body(carry, lp):
            x, k_all, v_all, i = carry
            k_page = lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
            v_page = lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
            x, k_page, v_page = layer_math(x, lp, k_page, v_page)
            k_all = lax.dynamic_update_index_in_dim(k_all, k_page, i, 0)
            v_all = lax.dynamic_update_index_in_dim(v_all, v_page, i, 0)
            return (x, k_all, v_all, i + 1), None

        (x, new_k, new_v, _), _ = lax.scan(
            layer_body, (x, k_pages, v_pages, jnp.int32(0)), layer_params
        )
    logits = lm_logits(x, params, eps)
    return logits, (new_k, new_v)


def _decode_step_with_ring(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, 1]
    ring: tuple[jax.Array, jax.Array],  # [L, T, B, K, hd] fresh-token ring
    t: jax.Array,  # scalar: this dispatch's step index (ring write slot)
    base_lens: jax.Array,  # [B]
    attn_source: Any,  # (i, q, ring_k_i, ring_v_i) -> attn [B, 1, H, hd]
    scan_xs: Any,  # extra per-layer scan inputs threaded to attn_source
    state: tuple[jax.Array, jax.Array] | None = None,  # hybrid: (ssm, conv)
    active: jax.Array | None = None,  # hybrid: rows whose state advances
    ssm_impl: str = "xla",  # hybrid: the state's pass (mamba.mamba_step, gdn.gdn_step)
    moe: tuple[jax.Array, jax.Array] | None = None,  # routed experts: their counters
    moe_step_impl: str = "xla",  # routed experts: the step's products (moe.moe_ffn)
) -> Any:
    """The shared decode-step transformer body (ring-buffer scheme).

    Why a ring: per-token scatters into the main cache cost ~10ms/step on
    TPU (measured, TinyLlama bs=64) — scatter with per-row offsets is the
    single most expensive op in naive decode.  Here every step writes its
    K/V *densely* at ring slot ``t`` (same index for all rows: one cheap
    dynamic_update_index), attention merges (main cache ⊕ ring) with a
    flash-style logsumexp combine, and the consolidate function writes the
    whole dispatch's tokens back in one amortized pass.

    The main-cache read is the ONLY thing the dense and paged layouts do
    differently, so it arrives as ``attn_source`` (with its per-layer scan
    inputs in ``scan_xs``); everything else lives once, here.

    Layers run via scan: main-cache buffers are read-only scan inputs or
    closed-over invariants (no carry round-trip), only the small ring
    travels in the carry.  An unrolled python loop has the same memory
    pattern but compiles ~10x slower for deep models.

    A hybrid stack threads the slots' recurrent ``state`` through the same
    scan (each Mamba layer reads and rewrites its own slice in place; rows
    that are not ``active`` keep theirs) and returns it third; a stack with
    routed experts counts its ``active`` rows' choices into ``moe`` and
    returns that last.
    """
    eps = config.norm_eps
    positions = (base_lens + t)[:, None]  # [B, 1] absolute position
    ring_k, ring_v = ring
    if config.latent and not config.layer_types:
        return _latent_decode_step(
            params, config, tokens, ring, t, base_lens, attn_source, moe, active, moe_step_impl)
    if config.layer_types:
        x = _embed(params, config, tokens)
        cos, sin = (_rope_dim_tables if config.latent else _positions_tables)(config, positions)

        def latent_layer(carry, x, lp, ia):  # as _latent_decode_step's, the state riding along
            *ring, st = carry
            ring, out = _mla_step_mixer(ring, x, lp, ia, t, cos, sin, config, attn_source)
            return (*ring, st), mla_head_gate(out, x, lp, config)

        def attn_layer(carry, x, lp, ia):
            ring_k, ring_v, st = carry
            q, k, v, gate = _hybrid_qkv(x, lp, cos, sin, config)
            slab = k[:, 0].astype(ring_k.dtype)[None, None]
            ring_k = lax.dynamic_update_slice(ring_k, slab, (ia, t, 0, 0, 0))
            slab = v[:, 0].astype(ring_v.dtype)[None, None]
            ring_v = lax.dynamic_update_slice(ring_v, slab, (ia, t, 0, 0, 0))
            extra = None if scan_xs is None else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, ia, 0, keepdims=False), scan_xs
            )
            attn = attn_source(
                ia, q,
                lax.dynamic_index_in_dim(ring_k, ia, 0, keepdims=False),
                lax.dynamic_index_in_dim(ring_v, ia, 0, keepdims=False),
                extra,
            )
            return (ring_k, ring_v, st), attn_out_gate(attn, gate)

        def mamba_layer(carry, h, lp, im):
            ring_k, ring_v, st = carry
            if config.shortconv:
                y, st = shortconv_step(h, lp, st, im, active)
            elif config.gdn:
                y, st = gdn_step(h, lp, st, im, active, config, ssm_impl)
            else:
                y, st = mamba_step(h, lp, st, im, active, config, ssm_impl)
            return (ring_k, ring_v, st), y

        valid = active[:, None] if config.moe and active is not None else None
        x, (ring_k, ring_v, state), moe = _hybrid_stack(
            config, params["layers"], x, (ring_k, ring_v, state),
            latent_layer if config.latent else attn_layer, mamba_layer, moe, valid,
            moe_step_impl,
        )
        logits = lm_logits(x, params, eps, config.logits_scaling, config.norm_plus_one)
        return (logits, (ring_k, ring_v), state, *(() if moe is None else (moe,)))

    x = params["embed"][tokens]
    cos, sin = rope_tables(positions, *rope_frequencies(config.head_dim, config.rope_theta))

    def layer_body(carry, inputs):
        x, ring_k, ring_v, i = carry
        lp, extra = inputs
        q, k, v = attn_qkv(x, lp, cos, sin, eps)
        # dense ring write at (layer i, slot t) — no scatter anywhere
        slab = k[:, 0].astype(ring_k.dtype)[None, None]
        ring_k = lax.dynamic_update_slice(ring_k, slab, (i, t, 0, 0, 0))
        slab = v[:, 0].astype(ring_v.dtype)[None, None]
        ring_v = lax.dynamic_update_slice(ring_v, slab, (i, t, 0, 0, 0))
        attn = attn_source(
            i,
            q,
            lax.dynamic_index_in_dim(ring_k, i, 0, keepdims=False),
            lax.dynamic_index_in_dim(ring_v, i, 0, keepdims=False),
            extra,
        )
        return (attn_out_mlp(x, attn, lp, eps), ring_k, ring_v, i + 1), None

    (x, ring_k, ring_v, _), _ = lax.scan(
        layer_body,
        (x, ring_k, ring_v, jnp.int32(0)),
        (params["layers"], scan_xs),
    )
    logits = lm_logits(x, params, eps)
    return logits, (ring_k, ring_v)


def decode_step_ring(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, 1]
    kv_cache: tuple[jax.Array, jax.Array],  # main pages, READ-ONLY here
    ring: tuple[jax.Array, jax.Array],  # [L, T, B, K, hd] fresh-token ring
    t: jax.Array,  # scalar: this dispatch's step index (ring write slot)
    base_lens: jax.Array,  # [B] kv length at dispatch start (main cache)
    attn_window: int | None = None,
    state: tuple[jax.Array, jax.Array] | None = None,  # hybrid: (ssm, conv)
    active: jax.Array | None = None,
    ssm_impl: str = "xla",
    moe: tuple[jax.Array, jax.Array] | None = None,
) -> Any:
    """One decode step over the dense [L, B, K, S, hd] cache layout."""
    k_pages, v_pages = kv_cache
    W = attn_window or k_pages.shape[3]

    def attn_source(i, q, rk, rv, extra):
        k_page, v_page = extra
        return _merged_decode_attention(
            q, k_page[:, :, :W], v_page[:, :, :W], rk, rv, base_lens, t
        )

    return _decode_step_with_ring(
        params, config, tokens, ring, t, base_lens, attn_source,
        (k_pages, v_pages), state, active, ssm_impl, moe,
    )


@jax.named_scope("attention")
def _merged_decode_attention(
    q: jax.Array,  # [B, 1, H, hd]
    k_cache: jax.Array,  # [B, K, W, hd] main pages (stale within dispatch)
    v_cache: jax.Array,
    ring_k: jax.Array,  # [T, B, K, hd] this layer's ring
    ring_v: jax.Array,
    base_lens: jax.Array,  # [B]
    t: jax.Array,  # current step (ring slots 0..t valid)
) -> jax.Array:
    """Softmax over (main cache ⊕ ring) via a two-source logsumexp merge."""
    B, _, H, hd = q.shape
    K = k_cache.shape[1]
    G = H // K
    qg = q.reshape(B, K, G, hd)

    # source 1: the main cache
    valid1 = jnp.arange(k_cache.shape[2])[None, :] < base_lens[:, None]
    o1, m1, z1 = masked_attention_source(qg, k_cache, v_cache, valid1)

    o2, m2, z2 = ring_attention_source(qg, ring_k, ring_v, t)
    out = logsumexp_merge((o1, m1, z1), (o2, m2, z2))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def masked_attention_source(
    qg: jax.Array,  # [B, K, G, hd] (unscaled)
    k_cache: jax.Array,  # [B, K, S, hd]
    v_cache: jax.Array,
    valid: jax.Array,  # [B, S] bool — attendable positions
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One masked flash-stats attention source → (o unnormalized, m, z).

    The numerically delicate idiom (-1e30 mask → running max → -1e29
    finite-floor clamp → exp/z) lives HERE once; the dense decode merge and
    the context-parallel shard source both call it.
    """
    scale = 1.0 / math.sqrt(qg.shape[-1])
    s = _einsum_f32("bkgh,bksh->bkgs", qg, k_cache) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e29)  # fully-masked rows stay finite
    p = jnp.exp(s - m).astype(k_cache.dtype)
    z = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    o = _einsum_f32("bkgs,bksh->bkgh", p, v_cache)
    return o, m, z


def ring_attention_source(
    qg: jax.Array,  # [B, K, G, hd]
    ring_k: jax.Array,  # [T, B, K, hd]
    ring_v: jax.Array,
    t: jax.Array,  # ring slots 0..t valid
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fresh-token attention source (tiny: T ≤ steps-per-dispatch) →
    (o unnormalized, m, z) — shared by the XLA and Pallas merged paths."""
    T = ring_k.shape[0]
    scale = 1.0 / math.sqrt(qg.shape[-1])
    s2 = _einsum_f32("bkgh,tbkh->bkgt", qg, ring_k) * scale  # [B,K,G,T]
    valid2 = (jnp.arange(T) <= t).reshape(1, 1, 1, T)
    s2 = jnp.where(valid2, s2, -1e30)
    m2 = jnp.max(s2, axis=-1, keepdims=True)
    p2 = jnp.exp(s2 - m2).astype(ring_k.dtype)
    z2 = jnp.sum(p2.astype(jnp.float32), axis=-1, keepdims=True)
    o2 = _einsum_f32("bkgt,tbkh->bkgh", p2, ring_v)
    return o2, m2, z2


def logsumexp_merge(
    a: tuple[jax.Array, jax.Array, jax.Array],
    b: tuple[jax.Array, jax.Array, jax.Array],
) -> jax.Array:
    """Combine two (o unnormalized, m, z) attention sources."""
    o1, m1, z1 = a
    o2, m2, z2 = b
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    return (o1 * w1 + o2 * w2) / (z1 * w1 + z2 * w2)


def _verify_step_with_ring(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, S] fed tokens: [last, d_0, .., d_{S-2}]
    base_lens: jax.Array,  # [B] kv length at dispatch start
    ring_dtype: Any,
    attn_source: Any,  # (i, q [B,S,H,hd], rk, rv, extra) -> [B, S, H, hd]
    scan_xs: Any,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """The shared speculative-VERIFY transformer body.

    Structurally :func:`_decode_step_with_ring` generalized from one query
    to S = k+1 queries per row: the whole drafted chunk runs as ONE forward
    (this is the point — the full weight read is amortized over every
    accepted token), its K/V lands densely in a chunk ring (slot j = the
    token at position ``base_lens + j``), attention merges (main cache ⊕
    causal chunk), and the caller consolidates the ring exactly like a
    decode dispatch — so ragged acceptance needs NO physical rollback:
    rejected slots simply sit beyond the advanced ``lens`` and the next
    wave overwrites them.
    """
    eps = config.norm_eps
    B, S = tokens.shape
    positions = base_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x = params["embed"][tokens]
    cos, sin = rope_tables(positions, *rope_frequencies(config.head_dim, config.rope_theta))
    ring_shape = (config.n_layers, S, B, config.n_kv_heads, config.head_dim)
    ring_k = jnp.zeros(ring_shape, ring_dtype)
    ring_v = jnp.zeros(ring_shape, ring_dtype)

    def layer_body(carry, inputs):
        x, ring_k, ring_v, i = carry
        lp, extra = inputs
        q, k, v = attn_qkv(x, lp, cos, sin, eps)
        # [B, S, K, hd] -> ring layout [S, B, K, hd], written densely at
        # layer i — same no-scatter scheme as the decode ring
        slab = jnp.swapaxes(k, 0, 1).astype(ring_k.dtype)[None]
        ring_k = lax.dynamic_update_slice(ring_k, slab, (i, 0, 0, 0, 0))
        slab = jnp.swapaxes(v, 0, 1).astype(ring_v.dtype)[None]
        ring_v = lax.dynamic_update_slice(ring_v, slab, (i, 0, 0, 0, 0))
        attn = attn_source(
            i,
            q,
            lax.dynamic_index_in_dim(ring_k, i, 0, keepdims=False),
            lax.dynamic_index_in_dim(ring_v, i, 0, keepdims=False),
            extra,
        )
        return (attn_out_mlp(x, attn, lp, eps), ring_k, ring_v, i + 1), None

    (x, ring_k, ring_v, _), _ = lax.scan(
        layer_body,
        (x, ring_k, ring_v, jnp.int32(0)),
        (params["layers"], scan_xs),
    )
    logits = lm_logits(x, params, eps)
    return logits, (ring_k, ring_v)  # logits [B, S, V]


def ragged_attention_source(
    qg: jax.Array,  # [B, S, K, G, hd] multi-query, kv-grouped (unscaled)
    k_cache: jax.Array,  # [B, K, W, hd]
    v_cache: jax.Array,
    q_starts: jax.Array,  # [B] absolute position of each row's query 0
    kv_lens: jax.Array,  # [B] valid kv length each row may attend
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """THE ragged multi-query attention source (XLA reference path for the
    unified prefill+decode wave, ISSUE 6) → (o unnormalized [B,K,G,S,hd],
    m [B,K,G,S,1], z [B,K,G,S,1]).

    One masking law serves every row kind of a ragged wave (see
    :mod:`calfkit_tpu.inference.ragged` for the descriptor vocabulary):
    query ``j`` of row ``b`` attends kv positions
    ``< min(kv_lens[b], q_starts[b] + j + 1)`` — causal within the row's
    own fresh span, bounded by its valid cache length.  Decode rows
    (S=1, start=kv_len=lens) and spec-verify rows (start=kv_len=base_lens)
    reduce to the plain length mask; prefill-chunk rows (start=offset,
    kv_len=offset+chunk against a scratch holding the chunk itself) get
    the within-chunk causal triangle.  One batched einsum pair reads the
    window ONCE for all S queries — the multi-query amortization both
    speculation and chunk absorption rely on.
    """
    W = k_cache.shape[2]
    S = qg.shape[1]
    scale = 1.0 / math.sqrt(qg.shape[-1])
    s1 = _einsum_f32("bskgh,bkwh->bkgsw", qg, k_cache) * scale
    kv_pos = jnp.arange(W, dtype=jnp.int32)[None, None, :]  # [1, 1, W]
    limit = jnp.minimum(
        kv_lens[:, None], q_starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :] + 1
    )  # [B, S]
    valid = kv_pos < limit[:, :, None]  # [B, S, W]
    s1 = jnp.where(valid[:, None, None, :, :], s1, -1e30)
    m1 = jnp.max(s1, axis=-1, keepdims=True)
    m1 = jnp.maximum(m1, -1e29)  # fresh/padding rows stay finite
    p1 = jnp.exp(s1 - m1).astype(k_cache.dtype)
    z1 = jnp.sum(p1.astype(jnp.float32), axis=-1, keepdims=True)
    o1 = _einsum_f32("bkgsw,bkwh->bkgsh", p1, v_cache)
    return o1, m1, z1


@jax.named_scope("attention")
def ragged_attention_xla(
    q: jax.Array,  # [B, S, H, hd] ragged queries (padded to the wave max)
    k_cache: jax.Array,  # [B, K, W, hd]
    v_cache: jax.Array,
    q_starts: jax.Array,  # [B]
    kv_lens: jax.Array,  # [B]
) -> jax.Array:
    """Normalized ragged attention → [B, S, H, hd]: the single-source
    closure of :func:`ragged_attention_source` (rows with no second
    source — plain cache reads).  Queries past a row's true q_len are
    padding; their output is garbage the caller must ignore (the same
    beyond-valid-length law the decode ring relies on)."""
    B, S, H, hd = q.shape
    K = k_cache.shape[1]
    qg = q.reshape(B, S, K, H // K, hd)
    o, m, z = ragged_attention_source(qg, k_cache, v_cache, q_starts, kv_lens)
    out = o / jnp.maximum(z, 1e-30)  # [B, K, G, S, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def ragged_attention_paged_xla(
    q: jax.Array,  # [B, S, H, hd]
    pool_layer_k: jax.Array,  # [N, K, page / f, f * hd] one layer's pages, as stored
    pool_layer_v: jax.Array,
    tables: jax.Array,  # [B, Pmax]
    q_starts: jax.Array,  # [B]
    kv_lens: jax.Array,  # [B]
    *,
    wpages: int,
) -> jax.Array:
    """Ragged attention through the block tables (XLA reference): gather
    each row's window, then the shared ragged mask law — mixed decode /
    prefill-chunk / verify rows served against the paged KV cache in one
    call."""
    return ragged_attention_xla(
        q,
        gather_window_paged(pool_layer_k, tables, wpages, q.shape[-1]),
        gather_window_paged(pool_layer_v, tables, wpages, q.shape[-1]),
        q_starts, kv_lens,
    )


def verify_chunk_source(
    qg: jax.Array,  # [B, S, K, G, hd]
    ring_k: jax.Array,  # [S, B, K, hd] this layer's chunk K (ring layout)
    ring_v: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The verify chunk's self-attention source → (o, m, z): query j
    attends chunk slots 0..j (slot j IS its own token)."""
    S = qg.shape[1]
    scale = 1.0 / math.sqrt(qg.shape[-1])
    s2 = _einsum_f32("bskgh,tbkh->bkgst", qg, ring_k) * scale
    causal = (
        jnp.arange(S, dtype=jnp.int32)[None, :]
        <= jnp.arange(S, dtype=jnp.int32)[:, None]
    )  # [S(query), S(chunk slot)]
    s2 = jnp.where(causal[None, None, None, :, :], s2, -1e30)
    m2 = jnp.max(s2, axis=-1, keepdims=True)
    m2 = jnp.maximum(m2, -1e29)
    p2 = jnp.exp(s2 - m2).astype(ring_k.dtype)
    z2 = jnp.sum(p2.astype(jnp.float32), axis=-1, keepdims=True)
    o2 = _einsum_f32("bkgst,tbkh->bkgsh", p2, ring_v)
    return o2, m2, z2


@jax.named_scope("attention")
def _verify_merged_attention(
    q: jax.Array,  # [B, S, H, hd] the chunk's queries
    k_cache: jax.Array,  # [B, K, W, hd] main cache window (read-only)
    v_cache: jax.Array,
    ring_k: jax.Array,  # [S, B, K, hd] this layer's chunk K
    ring_v: jax.Array,
    base_lens: jax.Array,  # [B]
) -> jax.Array:
    """Multi-query merged attention for the verify step (XLA path).

    Source 1 is the main cache read through the shared ragged law
    (:func:`ragged_attention_source` with start = kv_len = base_lens —
    everything in the cache precedes every query, so the ragged mask
    reduces to the plain length mask).  Source 2 is the chunk itself with
    a causal within-chunk mask (:func:`verify_chunk_source`).  Merged
    with the shared logsumexp law; one batched einsum pair reads the
    window ONCE for all S queries (the per-token window read is what
    speculation amortizes).
    """
    B, S, H, hd = q.shape
    K = k_cache.shape[1]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)

    o1, m1, z1 = ragged_attention_source(
        qg, k_cache, v_cache, base_lens, base_lens
    )
    o2, m2, z2 = verify_chunk_source(qg, ring_k, ring_v)
    out = logsumexp_merge((o1, m1, z1), (o2, m2, z2))  # [B, K, G, S, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def verify_step_ring(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, S] fed tokens
    kv_cache: tuple[jax.Array, jax.Array],  # window-sliced, READ-ONLY here
    base_lens: jax.Array,  # [B]
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Speculative verify over the dense cache layout → (logits [B, S, V],
    chunk ring [L, S, B, K, hd] ×2 for :func:`consolidate_ring`)."""
    k_pages, v_pages = kv_cache
    S = tokens.shape[1]

    def attn_source(i, q, rk, rv, extra):
        k_page, v_page = extra
        return _verify_merged_attention(q, k_page, v_page, rk, rv, base_lens)

    return _verify_step_with_ring(
        params, config, tokens, base_lens, k_pages.dtype, attn_source,
        (k_pages, v_pages),
    )


def verify_step_ring_paged(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, S]
    pool: tuple[jax.Array, jax.Array],  # as stored (make_page_pool); READ-ONLY here
    tables: jax.Array,  # [B, Pmax]
    base_lens: jax.Array,  # [B]
    wpages: int,  # static: window bucket in pages
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Speculative verify reading KV through the block tables → (logits,
    chunk ring for :func:`consolidate_ring_paged`)."""
    pool_k, pool_v = pool

    def attn_source(i, q, rk, rv, extra):
        kl = lax.dynamic_index_in_dim(pool_k, i, 0, keepdims=False)
        vl = lax.dynamic_index_in_dim(pool_v, i, 0, keepdims=False)
        return _verify_merged_attention(
            q,
            gather_window_paged(kl, tables, wpages, config.head_dim),
            gather_window_paged(vl, tables, wpages, config.head_dim),
            rk, rv, base_lens,
        )

    return _verify_step_with_ring(
        params, config, tokens, base_lens, pool_k.dtype, attn_source, None
    )


@jax.named_scope("kv_write")
def consolidate_ring(
    kv_cache: tuple[jax.Array, jax.Array],  # [L, B, K, S, hd] (donated)
    ring: tuple[jax.Array, jax.Array],  # [L, T, B, K, hd]
    base_lens: jax.Array,  # [B] where each row's ring tokens begin
) -> tuple[jax.Array, jax.Array]:
    """Write the dispatch's ring tokens into the main cache — per-row dense
    contiguous chunks, once per dispatch (amortizing what a per-step scatter
    would pay 'steps' times).  Rows whose requests already retired write
    garbage BEYOND their valid length — harmless, masked by seq_lens and
    overwritten by the next prefill on that slot.  Under overlapped
    execution a row that retired in the still-in-flight previous dispatch
    arrives here FROZEN (the engine's done-mask chain stops its ``lens``
    advancing), so its garbage writes repeat at one fixed in-row offset —
    the same beyond-valid-length law, never another row's data."""
    k_pages, v_pages = kv_cache
    ring_k, ring_v = ring

    def write(pages: jax.Array, r: jax.Array) -> jax.Array:
        # r: [L, T, B, K, hd] -> [B, L, K, T, hd]
        chunk = jnp.transpose(r, (2, 0, 3, 1, 4)).astype(pages.dtype)
        # pages: [L, B, K, S, hd] -> vmap rows on axis 1
        def one(row_pages, row_chunk, off):
            return lax.dynamic_update_slice(
                row_pages, row_chunk, (0, 0, off, 0)
            )

        return jax.vmap(one, in_axes=(1, 0, 0), out_axes=1)(
            pages, chunk, base_lens
        )

    return write(k_pages, ring_k), write(v_pages, ring_v)


@jax.named_scope("kv_write")
def _insert_chunk(
    cache: jax.Array,  # [B, K, Smax, hd]
    chunk: jax.Array,  # [B, S, K, hd]
    offsets: jax.Array,  # [B]
) -> jax.Array:
    """Per-row dynamic_update_slice at each sequence's write offset."""
    chunk = jnp.swapaxes(chunk, 1, 2)  # -> [B, K, S, hd]

    def one(row_cache, row_chunk, off):
        return lax.dynamic_update_slice(
            row_cache, row_chunk.astype(row_cache.dtype), (0, off, 0)
        )

    return jax.vmap(one)(cache, chunk, offsets)


def cache_sides(
    config: ModelConfig, lead: tuple, dtype: Any, per_row: int = 1
) -> tuple[jax.Array, jax.Array]:
    """A zeroed cache ``[*lead, width]``: the pair (K, V), or the latent's
    two parts (c, k_rope) for a model whose token leaves one latent behind.
    ``per_row`` positions share a row (a page pool's stored form,
    :func:`positions_per_row`): ``[*lead[:-1], lead[-1] / per_row, per_row *
    width]``, the same numbers in the same order."""
    *lead, n = lead
    return tuple(jnp.zeros((*lead, n // per_row, per_row * w), dtype) for w in config.cache_dims)


def make_empty_cache(
    config: ModelConfig, batch: int, max_seq: int, dtype: Any = None
) -> tuple[jax.Array, jax.Array]:
    dtype = dtype or jnp.dtype(config.dtype)
    return cache_sides(
        config, (config.n_kv_layers, batch, config.cache_heads, max_seq), dtype)


# --------------------------------------------------------------------------- #
# paged KV cache (block-table indirection; see inference/paged.py)
# --------------------------------------------------------------------------- #


LANES = 128  # a TPU lane tile: the minor dimension of every tiled array


def lane_pack(width: int) -> int:
    """How many vectors of ``width`` numbers fill one 128-lane row: ``128 /
    width`` where that is whole, else 1 (whole lane tiles, or a width no
    packing helps)."""
    return LANES // width if LANES % width == 0 else 1


def sublane_tile(dtype: Any) -> int:
    """Rows of one (sublane, lane) tile of ``dtype``: 8 of 32 bits, 16 of
    bfloat16, 32 of an 8-bit type."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def positions_per_row(width: int, page: int, dtype: Any) -> int:
    """``f``: how many positions of one kv head share a stored row of a K/V
    page pool.  THE rule of the pool's stored form, for every reader and
    writer on every platform.

    A head that divides a lane tile (64, 32) is stored ``f = 128 / width``
    positions a row, row ``r`` of a page holding positions ``f * r .. f * r +
    f - 1`` side by side, where that leaves a page whole sublane tiles of the
    cache's dtype (``page / f`` rows: 16 a tile in bfloat16); every other
    head, and every page the packing would leave a partial tile, is stored as
    declared, ``f = 1``.  Why: a ``[.., page, 64]`` array does not lie
    row-major and lane-dense in a TPU's HBM (compiled for the v5e it is held
    with the page INDEX minor-most), so the paged decode kernel, which copies
    a page slab whole, and the write's loop of window updates each made their
    own copy of the whole side, six a dispatch (PERF.md section 6, PRs 28, 46
    and 49).  ``[.., page / f, 128]`` is held as it is declared and both take
    it as it lies."""
    f = lane_pack(width)
    return f if page % (f * sublane_tile(dtype)) == 0 else 1


def make_page_pool(
    config: ModelConfig, num_pages: int, page_size: int, dtype: Any = None,
    window_pages: int = 0,
) -> tuple[Any, Any]:
    """The page pools, BY CACHE KIND, as a pair of sides; page 0 of every
    pool is its trash page.  ``f`` is :func:`positions_per_row` of the head,
    the page and the dtype (1 for a head of 128 or wider: the pool as
    declared):

    - K and V of every token (a dense or hybrid model's attention layers):
      ``[L, N, K, page / f, f * hd]`` x 2.
    - one latent a token (MLA): ``[L, N, 1, page, r]`` and ``[L, N, 1, page,
      dr]``, the two parts of the latent, and no K or V per head at all;
      stored as declared (the decode kernel reads the rope side through
      ``pallas_attention.latent_rope_view``, another arrangement).
    - a model with window layers (``config.windowed``): each side is a pair
      ``(global [Lg, N, K, page / f, f * hd], window [Lw, window_pages, K,
      page / f, f * hd])``: ``num_pages`` pages for the layers that keep every
      token, ``window_pages`` for the layers that keep a ring of pages a row.
      An EVA stack's layers are of BOTH kinds: the first pool holds every
      layer's summaries, the second every layer's ring (eva.py).

    A reader cannot tell ``hd`` from the array: it takes the head's width
    from ``config.cache_dims`` (or from the queries, the ring or the scratch
    it holds beside the pool)."""
    dtype = dtype or jnp.dtype(config.dtype)
    f = 1 if config.latent else positions_per_row(config.head_dim, page_size, dtype)
    if config.windowed:
        kg, vg = cache_sides(
            config, (config.n_global_layers, num_pages, config.cache_heads, page_size), dtype, f)
        kw, vw = cache_sides(
            config, (config.n_window_layers, window_pages, config.cache_heads, page_size), dtype, f)
        return (kg, kw), (vg, vw)
    return cache_sides(
        config, (config.n_kv_layers, num_pages, config.cache_heads, page_size), dtype, f)


@jax.named_scope("gather_window")
def gather_window_paged(
    pool_layer: jax.Array,  # [N, K, page / f, f * width] one layer's pages, as stored
    tables: jax.Array,  # [B, Pmax] int32 block tables
    wpages: int,  # static: pages per attention window
    width: int,  # static: the head's width (``config.cache_dims`` of this side)
) -> jax.Array:
    """Materialize each row's window from its pages → [B, K, wp·page, width].

    The XLA read path: one gather per (layer, step) of EVERY row's whole
    window bucket, used or not, occupied slot or not — read, written and
    read again by the attention.  Correct everywhere, for K and V pairs
    and for the two sides of a latent pool alike: the CPU path, the
    ``tp > 1`` path, the verify and ragged S > 1 programs, and the parity
    reference of the two Pallas decode kernels, which read each row's live
    pages in place instead.  The pool's stored rows (``f`` positions side by
    side, :func:`positions_per_row`) come apart in the GATHERED rows, a
    row-major reshape of the result: nothing of the pool is copied for it.
    No decode step of a benchmark cell runs it on a chip since PR 32 (on the
    v5e the gather was 48% of device time in the Mistral cell, a quarter in
    granite's, and with the layer slice a third in Kimi's: PERF.md section
    6, PRs 25, 28 and 32).
    """
    B = tables.shape[0]
    positions = wpages * pool_layer.shape[2] * (pool_layer.shape[3] // width)
    gathered = pool_layer[tables[:, :wpages]]  # [B, wp, K, page / f, f * width]
    gathered = jnp.transpose(gathered, (0, 2, 1, 3, 4))
    return gathered.reshape(B, pool_layer.shape[1], positions, -1)


def decode_step_ring_paged(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [B, 1]
    pool: tuple[jax.Array, jax.Array],  # as stored (make_page_pool); READ-ONLY here
    tables: jax.Array,  # [B, Pmax] block tables
    ring: tuple[jax.Array, jax.Array],  # [L, T, B, K, hd]
    t: jax.Array,  # scalar step index
    base_lens: jax.Array,  # [B]
    wpages: int,  # static: window bucket in pages
    attn_impl: str = "xla",
    active: jax.Array | None = None,  # [B] bool; None: every row reads
    state: tuple[jax.Array, jax.Array] | None = None,  # hybrid: (ssm, conv)
    ssm_impl: str = "xla",  # hybrid: the state's pass (mamba.mamba_step, gdn.gdn_step)
    moe: tuple[jax.Array, jax.Array] | None = None,  # routed experts: their counters
    moe_step_impl: str = "xla",  # routed experts: the step's products (moe.moe_ffn)
) -> Any:
    """One decode step reading KV through the block tables.

    Shares the transformer body with :func:`decode_step_ring`; only the
    main-cache read differs.  The pool is a scan *invariant* (closed over,
    indexed per layer), never a carry — its bytes move once per read, not
    per scan round-trip.

    A Pallas read (K and V pairs, or a latent pool's absorbed read: two
    kernels, one rule) follows each row's length and takes the layer as an
    INDEX into the pool, so a row that is not ``active`` (its token is
    discarded by the caller) is given length 0 there and costs no page, and
    no layer is sliced out of the pool; the XLA read slices the layer,
    gathers every row's window whatever it holds and takes no notice of
    ``active``.  Both take the pool of K and V pairs AS IT IS STORED
    (:func:`make_page_pool`: ``f`` positions a row for a head narrower than
    a lane tile; the kernel copies a page's stored rows whole, the XLA read
    takes its gathered rows apart).  For a Pallas read of a LATENT pool the
    rope side may be the kernel's view of it
    (:func:`pallas_attention.latent_rope_view`), which a caller that loops
    over steps makes once, outside its loop.
    """
    if config.eva:  # an aligned window ring beside summary pages (eva.py)
        from calfkit_tpu.inference.eva import eva_decode_step_paged

        return eva_decode_step_paged(
            params, config, tokens, pool, tables, ring, t, base_lens, wpages, attn_impl, active)
    if config.windowed:
        return _window_decode_step_paged(
            params, config, tokens, pool, tables, ring, t, base_lens, wpages, attn_impl,
            active, moe, moe_step_impl)
    pool_k, pool_v = pool

    def read_lens():  # what a Pallas read walks: nothing of a row not active
        return base_lens if active is None else jnp.where(active, base_lens, 0)

    def latent_source(i, q, ring_c, ring_r, extra):
        scale = 1.0 / math.sqrt(config.head_dim)
        if attn_impl.startswith("pallas"):
            from calfkit_tpu.inference.pallas_attention import (
                merged_latent_decode_attention_pallas,
            )

            return merged_latent_decode_attention_pallas(
                *q, pool_k, pool_v, i, tables, (ring_c, ring_r), read_lens(), t,
                scale=scale, wpages=wpages, interpret=attn_impl == "pallas_interpret",
            )
        window = tuple(
            gather_window_paged(
                lax.dynamic_index_in_dim(side, i, 0, keepdims=False), tables, wpages, width)
            for side, width in zip(pool, config.cache_dims))
        return mla_merged_decode_attention(*q, window, (ring_c, ring_r), base_lens, t, scale)

    if config.latent:
        return _decode_step_with_ring(
            params, config, tokens, ring, t, base_lens, latent_source, None,
            state, active, ssm_impl, moe, moe_step_impl,
        )

    def attn_source(i, q, rk, rv, extra):
        if attn_impl.startswith("pallas"):
            from calfkit_tpu.inference.pallas_attention import (
                merged_paged_decode_attention_pallas,
            )

            return merged_paged_decode_attention_pallas(
                q, pool_k, pool_v, i, tables, rk, rv, read_lens(), t,
                wpages=wpages, interpret=attn_impl == "pallas_interpret",
            )
        kl = lax.dynamic_index_in_dim(pool_k, i, 0, keepdims=False)
        vl = lax.dynamic_index_in_dim(pool_v, i, 0, keepdims=False)
        return _merged_decode_attention(
            q,
            gather_window_paged(kl, tables, wpages, config.head_dim),
            gather_window_paged(vl, tables, wpages, config.head_dim),
            rk, rv, base_lens, t,
        )

    return _decode_step_with_ring(
        params, config, tokens, ring, t, base_lens, attn_source, None,
        state, active, ssm_impl, moe, moe_step_impl,
    )


@jax.named_scope("kv_write")
def consolidate_ring_paged(
    pool: tuple[jax.Array, jax.Array],  # as stored (make_page_pool); donated
    ring: tuple[jax.Array, jax.Array],  # [L, T, B, K, hd]
    tables: jax.Array,  # [B, Pmax]
    base_lens: jax.Array,  # [B]
    active: jax.Array,  # [B] bool — inactive rows write to the trash page
    layer_kinds: Any = None,  # pages by kind: (global_layer_ids, window_layer_ids)
) -> tuple[jax.Array, jax.Array]:
    """Write the dispatch's ring tokens through the block tables.

    Window updates in place, two a row (:func:`_write_windows`): a row's
    ``T`` new positions lie in the page it is in and the page it runs on
    into, in whole stored rows of each.  Inactive rows are redirected to
    page 0 (the trash page): a
    retired slot's pages may already belong to a NEW request, so letting
    its stale row write through its old table entries would corrupt a
    neighbor — the dense layout tolerated garbage-beyond-length, the paged
    layout must not.  Overlapped execution leans on the same redirect: a
    row that retired inside the previous, still-in-flight dispatch reaches
    this one masked inactive (device-side done chain), so its writes land
    in the trash page even though the host hasn't freed its pages yet
    (one-dispatch-late retirement frees them only after this dispatch
    lands).  So do the positions past a row's table (a dispatch can
    overshoot a retiring row's cap), and the second window of a row that
    stays inside one page.
    """
    if isinstance(tables, tuple):  # pages by cache kind: (global, window)
        return _consolidate_by_kind(pool, ring, tables, base_lens, active, layer_kinds)
    return _write_windows(pool, ring, tables, base_lens, active)


def _write_windows(pool, ring, tables, base_lens, active, wraps=False):
    """``ring`` [L, T, B, K, w] x 2 into ``pool`` [L, N, K, page / f, f * w] x 2
    (its stored form, :func:`positions_per_row`) at each row's positions
    ``base_len .. base_len + T - 1``: a loop over the rows on the donated
    pool, two turns a row, each a read-modify-write of a window of stored
    ROWS of ONE page, ``[L, 1, K, rows, f * w]``.  ``rows`` is what ``T``
    positions can touch at any offset, ``ceil((T + f - 1) / f)`` (``T`` at
    ``f = 1``; ``T / f + 1`` otherwise, taken up to whole groups of 8 rows),
    a page at most.  The first window starts at row ``min(offset // f, page
    / f - rows)`` of the row's page so that it never leaves it; the second
    at 0 of the next table entry (the table a ring of pages where it
    ``wraps``), or of the trash page where the row does not straddle, is not
    ``active`` or has run past its table.
    Positions of a window that take no token keep the bits they had (a first
    window that starts before the row's offset covers live tokens; at ``f >
    1`` so does its tail past the row's last token): the mask is taken over
    (row, lane block).  Not one scatter over (page, offset): those are not the
    pool's major dimensions, and the TPU compiler copied each pool side into a
    layout with the offset above the KV heads and back around it, every
    dispatch (PERF.md section 6, PR 46).  A ring longer than a page goes in as
    several of at most a page."""
    T, B = ring[0].shape[1:3]
    f = pool[0].shape[4] // ring[0].shape[4]
    page = pool[0].shape[3] * f
    if T > page:
        for at in range(0, T, page):
            pool = _write_windows(
                pool, tuple(r[:, at:at + page] for r in ring), tables, base_lens + at, active,
                wraps)
        return pool
    entries = tables.shape[1]
    rows = -(-(T + f - 1) // f)  # what T positions can touch at any offset: T at f = 1
    if f > 1:
        # whole groups of 8 rows: for such a window the v5e's compiler keeps the
        # side in the loop as it is stored, and for one of 5 rows it relays the
        # whole side into a layout of its own and back, every dispatch
        # (compiled for the described v5e and on the chip, PERF.md section 6, PR 49)
        rows = -(-rows // 8) * 8
    rows = min(rows, page // f)
    slack = rows * f - T  # positions of a window that no token of T can take: 0 at f = 1

    def page_of(entry, live):  # [B] table entry -> [B] page id, the trash page if not live
        entry = entry % entries if wraps else entry
        ids = jnp.take_along_axis(tables, jnp.minimum(entry, entries - 1)[:, None], axis=1)[:, 0]
        return jnp.where(live & (entry < entries), ids, 0)

    entry, offset = base_lens // page, base_lens % page
    # the first window's first row, and how many of its first positions hold older tokens
    # (f = 1 is the same arithmetic spelled without its divisions: a pool stored as
    # declared traces to the program it was, operation for operation)
    if f == 1:
        at = jnp.minimum(offset, page - T)
        shift = offset - at
    else:
        at = jnp.minimum(offset // f, page // f - rows)
        shift = offset - at * f
    over = shift - slack if slack else shift  # tokens that run over the window's end
    first = page_of(entry, active)
    second = page_of(entry + 1, active & (over > 0))
    where = jnp.arange(T + slack)[:, None]  # a window's positions, against [.., T + slack, w]

    def head(b):  # the first window's positions that take a token of row b
        takes = where >= shift[b]
        # at f > 1 the window's tail past the row's last token keeps its bits too
        return takes & (where < shift[b] + T) if slack else takes

    def write(side, r):
        size = (side.shape[0], 1, side.shape[2], rows, side.shape[4])

        def stored(x):  # [.., rows * f, w or 1] -> the window's stored rows [.., rows, f * w]
            return jnp.broadcast_to(x, (*x.shape[:-1], r.shape[4])).reshape(
                *x.shape[:-2], *size[3:]) if f > 1 else x

        def row(b, side):
            # [L, T, 1, K, w] -> [L, 1, K, T + slack, w], token j at window
            # position (j + shift) % (T + slack): the head of the ring at
            # `shift` of the first window, what ran over the page's end at 0
            # of the second
            vals = jnp.transpose(lax.dynamic_slice_in_dim(r, b, 1, axis=2), (0, 2, 3, 1, 4))
            vals = vals.astype(side.dtype)
            if slack:
                vals = jnp.pad(vals, ((0, 0),) * 3 + ((0, slack), (0, 0)))
            vals = stored(jnp.roll(vals, shift[b], axis=3))
            for page_id, row0, takes in ((first[b], at[b], head(b)),
                                         (second[b], 0, where < over[b])):
                corner = (0, page_id, 0, row0, 0)
                old = lax.dynamic_slice(side, corner, size)
                side = lax.dynamic_update_slice(
                    side, jnp.where(stored(takes), vals, old), corner)
            return side

        return lax.fori_loop(0, B, row, side)

    # a loop a side: one loop over both sides held both in the loop's layout
    # at once where that was not the stored one (PERF.md section 6, PR 46)
    return write(pool[0], ring[0]), write(pool[1], ring[1])


def _consolidate_by_kind(pool, ring, tables, base_lens, active, layer_kinds):
    """:func:`consolidate_ring_paged` for pools by kind: the global layers'
    tokens go through the global table as ever; the window layers' land IN
    THE RING, table entry ``(position // page) % R``, over what the window
    has left behind."""
    (kg, kw), (vg, vw) = pool
    tg, tw = tables
    gl, wl = (jnp.asarray(ids, jnp.int32) for ids in layer_kinds)
    rk, rv = ring
    kg, vg = consolidate_ring_paged((kg, vg), (rk[gl], rv[gl]), tg, base_lens, active)
    kw, vw = _write_windows((kw, vw), (rk[wl], rv[wl]), tw, base_lens, active, wraps=True)
    return (kg, kw), (vg, vw)


@jax.named_scope("kv_write")
def write_prefill_pages(
    pool: tuple[jax.Array, jax.Array],  # as stored (make_page_pool); donated
    scratch: tuple[jax.Array, jax.Array],  # [L, R, K, P, hd] prefill K/V
    page_ids: jax.Array,  # [R, P // page] int32 destination pages
    layer_kinds: Any = None,  # pages by kind: (global_layer_ids, window_layer_ids)
) -> tuple[jax.Array, jax.Array]:
    """Scatter whole prefill pages into the pool (page-granular writes).
    Each page block of the SCRATCH is packed into the pool's stored rows
    first (:func:`make_page_pool`: ``[.., page, hd]`` -> ``[.., page / f, f *
    hd]``, a row-major reshape of the wave's rows), so the set lands on the
    donated pool as it lies and nothing of the pool is relaid.
    Pools by kind take ``page_ids`` as a pair too: the global layers' pages
    of the scratch go to the row's global pages, the window layers' to its
    ring, where the caller has named the trash page for every page of the
    scratch but the last ``R`` that hold the row's own tokens."""
    if isinstance(scratch[0], tuple):  # an EVA stack's scratch of summaries (eva.py)
        from calfkit_tpu.inference.eva import write_prefill_pages as write_eva_pages

        return write_eva_pages(pool, scratch, page_ids)
    if isinstance(page_ids, tuple):
        (kg, kw), (vg, vw) = pool
        gl, wl = (jnp.asarray(ids, jnp.int32) for ids in layer_kinds)
        kg, vg = write_prefill_pages((kg, vg), (scratch[0][gl], scratch[1][gl]), page_ids[0])
        kw, vw = write_prefill_pages((kw, vw), (scratch[0][wl], scratch[1][wl]), page_ids[1])
        return (kg, kw), (vg, vw)
    L, R, K, P, _ = scratch[0].shape

    def write(pool_side: jax.Array, s: jax.Array) -> jax.Array:
        # [L, R, K, np*page, hd] -> [L, R, np, K, page, hd] -> [L, R*np, ...]
        # and each page block into the pool's stored rows, f positions side by
        # side: a row-major reshape of the wave's scratch, never of the pool
        hd = s.shape[-1]
        rows, lanes = pool_side.shape[3:]
        page = rows * (lanes // hd)
        npg = P // page
        blocks = s.reshape(L, R, K, npg, page, hd).transpose(0, 1, 3, 2, 4, 5)
        blocks = blocks.reshape(L, R * npg, K, rows, lanes).astype(pool_side.dtype)
        return pool_side.at[:, page_ids.reshape(-1)].set(blocks)

    return write(pool[0], scratch[0]), write(pool[1], scratch[1])
