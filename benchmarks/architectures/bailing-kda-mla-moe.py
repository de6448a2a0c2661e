"""Architecture ``bailing-kda-mla-moe``: Kimi Delta Attention beside latent
attention in ONE stack, leading dense layers, experts chosen by GROUP and
held by SHARE.

``model_type`` ``bailing_hybrid`` as ``inclusionAI/Ling-3.0-flash-VL``
publishes its language decoder (text only: the tower is no part of it).
Behind the interface ``manifest.load_architecture`` checks: the program's
model description from a configuration file, the seeded parameter tree, the
plain float32 reference, and the operations and bytes the mathematics
requires.

Architecture, by the keys of the model's ``config.json`` (D = hidden_size;
every RMSNorm multiplies by ``w``, ``rms_norm_eps``; ``x = x + mixer(norm_1(
x))``, ``x = x + ffn(norm_2(x))``; final norm, an UNTIED head):

- stack: published layer ``i`` is latent attention where ``(i + 1) %
  layer_group_size == 0``, Kimi Delta Attention otherwise; its FFN a SwiGLU
  of ``intermediate_size`` where ``i < first_k_dense_replace``, the expert
  block otherwise.  ``published_layers`` of the configuration file names the
  published layers THIS file keeps, in order.
- Kimi Delta Attention (H = ``num_attention_heads`` heads of ``head_dim`` dk
  = dv; ``num_kv_heads_for_linear_attn`` 0 = as many key heads):
  ``[q | k | v | z | b] = h W_in`` (z, b one a head), ``a = h W_alpha`` [H, dk]
  (``no_kda_lora``: one full matrix); ``(q, k, v) = silu(causal depthwise conv
  of short_conv_kernel_size taps, no bias, over the channels q | k | v)``
  (``linear_silu``); ``q, k`` L2-normalised per head (eps 1e-6), ``q /
  sqrt(dk)``; ``beta = sigmoid(b)``; the log-decay a key CHANNEL ``g =
  kda_lower_bound sigmoid(exp(A_log[head]) (a + dt_bias))`` (``kda_safe_gate``).
  Per head, ``S`` of dk x dv zero for a new sequence: ``S = Diag(exp(g_t))
  S``; ``u = S^T k_t``; ``S = S + k_t (x) (beta_t (v_t - u))``; ``o_t = S^T
  q_t``; ``y = rmsnorm_w(o_t) sigmoid(z_t[head])``
  (``gated_attention_proj_granularity_type`` head_wise); ``out = y W_o``.
- latent attention (``q_lora_rank`` null): ``q = h W_q`` -> H x
  (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c | k_r] = h W_kva`` ->
  ``kv_lora_rank`` | rope, ``c = rmsnorm(c)``; ``[k_nope | v] = c W_kvb``;
  rotary (``rope_theta``) on the rope parts; causal softmax of the scores
  over nope + rope at ``1 / sqrt(nope + rope)``; ``out = (o_head
  sigmoid(h W_z)[head]) W_o``.  The tree holds the rope dimensions in HALVES
  (the loader permutes a checkpoint's interleaved pairs once:
  ``rope_interleave``), which changes no product.
- expert block: ``s = sigmoid(float32(h) W_g)`` over ALL ``num_experts`` the
  model has (``published.num_experts``); the choice over ``s + bias``
  (``moe_router_enable_expert_bias``): ``n_group`` equal groups, a group's
  score the sum of its two largest, the ``topk_group`` best groups kept, the
  ``num_experts_per_tok`` largest of their experts; ``w = s[chosen] / sum
  s[chosen]`` (``norm_topk_prob``) times ``routed_scaling_factor``; ``y =
  sum_e w_e E_e(h) + Shared(h)``.  The swiglu limit lists are 0 in every
  layer this file may keep (``model`` refuses a nonzero one by name: the
  clamp's form is not published).

**The share.**  ``num_experts`` of the configuration is how many experts
THIS chip holds, ``[expert_first, expert_first + num_experts)`` of the
published count: one routing group.  The gate keeps its published width,
groups and experts a token; the sum runs over the held experts alone with
the weights normalised over the token's whole top k.  What an absent expert
would add is left out, here as in the program.  The vocabulary's slice is a
smaller vocabulary.

The reference runs the delta rule token by token, exactly the recurrence
above (a plain ``lax.scan`` over positions: no chunking, no cache), latent
attention expanded over the whole sequence, and the expert block as "every
HELD expert on every token, times a weight that is zero outside the chosen",
one expert at a time.  It imports nothing of the program but the model
description it is handed.

**What the served rows leave behind** (``_left_behind``, as
``qwen3-next-gdn-moe.py`` reads it): the FIRST delta-rule layer's state the
finished rows left in their slots and the first expert layer's count of
tokens to each held expert, each against a limit of the configuration file:
what tells a state stored, or a gate taken, in bfloat16.

**Near-ties of the gate.**  A top-k is not continuous: where the last expert
chosen leads the first one left out by less than the rounding of a bfloat16
stream, the program may rightly choose the other one.  With an
``agreement.routing_tie`` (in the scores the choice is made on: a group's
two largest ``s + bias`` summed, then ``s + bias`` of the kept groups' experts)
the reference follows, for each position it decides, every choice of groups
and then of experts within the tie through all later layers (a position's
own stream alone: the earlier positions' latents, delta-rule states and conv
inputs are the reference's) and accepts the served token under any of them:
the rule, its bounds and its verdict are ``benchmarks/routing_tie.py``'s, its
two applications a layer ``_choices``, the walk through this architecture's
layers ``_admitted``.  A tie among experts that are ALL held elsewhere, and a
choice of groups that names the same held experts, opens no branch.
"""

from __future__ import annotations

import functools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES
from benchmarks.routing_tie import bounded, decide, padded, room, routings

ATTENTION, KDA = "attention", "kda"
_ROWS_AT_ONCE = 2  # rows the reference carries through a layer together
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_VOCAB_BLOCK = 16384  # columns of the head upcast at once
_L2_EPS = 1e-6
# the seeded tree (params): W_g at this gain on 1/sqrt(fan_in); the bias on the
# choice uniform in +-this; every norm's w uniform in 1 +-_NORM_RANGE
_ROUTER_GAIN = 2.0
_ROUTER_BIAS_RANGE = 0.05
_NORM_RANGE = 0.1
# the bounded gate's argument exp(A_log) (a + dt_bias): a bias a CHANNEL
# uniform in this range beside a's unit spread and a rate a head in 0.5-2, so
# that the channels of one head keep from a few tokens (g near -4) to a
# hundred (g near -0.01): a decay taken by head must disagree
_DT_BIAS_RANGE = (-6.0, 1.0)
_RATE_RANGE = (0.5, 2.0)

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok", "moe_intermediate_size": "moe_d_ff",
    "n_group": "n_group", "topk_group": "topk_group",
    "short_conv_kernel_size": "gdn_d_conv",
}
_AS_READ = (  # keys that select a variant: the one reading this file describes
    ("q_lora_rank", None), ("score_function", "sigmoid"),
    ("moe_router_enable_expert_bias", True), ("use_mla_nope", False), ("use_nGPT", False),
    ("scale_router_input", False), ("value_norm", False), ("up_proj_norm", False),
    ("group_norm_size", 1), ("linear_silu", True), ("no_kda_lora", True),
    ("use_kda_lora", False), ("kda_safe_gate", True), ("use_qk_norm", True),
    ("gated_attention_proj_granularity_type", "head_wise"),
    ("num_kv_heads_for_linear_attn", 0), ("norm_topk_prob", True),
)


# ------------------------------------------------- the program's description
@functools.lru_cache(maxsize=None)
def _described():
    """The program's description with, beside it, what ``forward_top2``
    reads of the file's ``agreement``: it is handed the description and
    nothing else of the file."""
    import dataclasses

    from calfkit_tpu.inference.config import ModelConfig

    return dataclasses.make_dataclass("Described", [
        ("agreement_margin", float, 0.0),
        ("agreement_new_tokens", int, 0),  # a row's last positions are the served ones
        ("routing_tie", float, 0.0),
        ("state_error_limit", float, 0.0),  # 0: the reading is logged, nothing is held to it
        ("gate_mismatch_limit", float, 0.0),
    ], bases=(ModelConfig,), frozen=True)


def kept_layers(config: dict) -> list[int]:
    """The published layers this configuration keeps, in order."""
    kept = [int(i) for i in config.get(
        "published_layers", range(config["num_hidden_layers"]))]
    if len(kept) != config["num_hidden_layers"] or kept != sorted(set(kept)):
        raise ValueError("bailing-kda-mla-moe: published_layers names num_hidden_layers "
                         "distinct layers in order")
    return kept


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RuntimeConfig

    for key, want in _AS_READ:
        if config.get(key, want) != want:
            raise ValueError(f"bailing-kda-mla-moe: {key} other than {want!r} is not described")
    if config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("bailing-kda-mla-moe: rotary_dim is the latent layers' rope part")
    if config["moe_shared_expert_intermediate_size"] % config["moe_intermediate_size"]:
        raise ValueError("bailing-kda-mla-moe: the shared expert is whole expert widths")
    kept = kept_layers(config)
    every, dense = int(config["layer_group_size"]), int(config["first_k_dense_replace"])
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    sizes.update(
        gdn_n_k_heads=config["num_attention_heads"], gdn_n_v_heads=config["num_attention_heads"],
        gdn_d_k=config["head_dim"], gdn_d_v=config["head_dim"],
        n_experts_total=config["published"].get("num_experts", config["num_experts"]),
        expert_first=int(config.get("expert_first", 0)),
        n_shared_experts=(config["moe_shared_expert_intermediate_size"]
                          // config["moe_intermediate_size"]),
        first_k_dense=sum(i < dense for i in kept),
    )
    agree = config["agreement"]
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    experts = [i for i in kept if i >= dense]
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), kv_norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        state_dtype=config["precision"]["state"],
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        layer_types=tuple(ATTENTION if (i + 1) % every == 0 else KDA for i in kept),
        attn_output_gate=True, kda_lower_bound=float(config["kda_lower_bound"]),
        scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        # the kept expert layers' entries: a nonzero one is refused by ModelConfig, by name
        expert_swiglu_limits=tuple(
            float(config["expert_swiglu_limit_list"][i]) for i in experts),
        shared_expert_swiglu_limits=tuple(
            float(config["share_expert_swiglu_limit_list"][i]) for i in experts),
        agreement_margin=float(agree["margin"]),
        agreement_new_tokens=int(agree["new_tokens"]),
        routing_tie=float(agree.get("routing_tie", 0.0)),
        # read on the chip at the published widths: at toy widths logged, not held
        state_error_limit=0.0 if rehearse else float(agree.get("state_error_limit", 0.0)),
        gate_mismatch_limit=0.0 if rehearse else float(agree.get("gate_mismatch_limit", 0.0)),
        **sizes,
    )
    return described, RuntimeConfig(**runtime)


# ------------------------------------------------------------------ weights
def params(model_config, runtime, mesh, seed: int):
    """The seeded tree the engine is started with, made on the device from
    the seed in the type it is served in (``assumed`` in the configuration
    file).  The program's own initialiser draws every matrix at
    1/sqrt(fan_in) and every norm at 1; seeded HERE, for what the cell is to
    show:

    - the embedding at UNIT scale (a lookup's fan-in is the one row it reads);
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in) and its bias uniform
      in +-``_ROUTER_BIAS_RANGE`` and NOT zero, so that a program that puts the
      bias into the weights, or leaves it out of the choice, disagrees;
    - every norm's ``w`` uniform in 1 +-``_NORM_RANGE``;
    - ``A_log`` and ``dt_bias`` so that the log-decay spans its range channel
      by channel within every head (``_RATE_RANGE``, ``_DT_BIAS_RANGE``): some
      channels of a head keep a few tokens and others a hundred, and a decay
      taken by head (the channels' mean) disagrees with the reference."""
    if runtime.quantization is not None:
        raise ValueError(f"no initialiser for quantization {runtime.quantization!r}")
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.model import init_params
    from calfkit_tpu.inference.sharding import param_shardings

    c = model_config

    def seeded(key):
        tree = init_params(c, key)
        tree["embed"] = (tree["embed"].astype(jnp.float32) * math.sqrt(c.d_model)).astype(
            tree["embed"].dtype)
        layers = tree["layers"]
        moe, gdn = layers["moe"], layers["gdn"]
        moe["router"] = moe["router"] * _ROUTER_GAIN
        moe["router_bias"] = jax.random.uniform(
            jax.random.fold_in(key, 1), moe["router_bias"].shape, jnp.float32,
            -_ROUTER_BIAS_RANGE, _ROUTER_BIAS_RANGE)
        gdn["A_log"] = jax.random.uniform(
            jax.random.fold_in(key, 2), gdn["A_log"].shape, jnp.float32,
            *(math.log(v) for v in _RATE_RANGE))
        gdn["dt_bias"] = jax.random.uniform(
            jax.random.fold_in(key, 3), gdn["dt_bias"].shape, jnp.float32, *_DT_BIAS_RANGE)
        norms = [(tree, "final_norm"), (moe, "mlp_norm"), (layers["dense"], "mlp_norm"),
                 (gdn, "mixer_norm"), (gdn, "norm"),
                 *((layers["attn"], n) for n in ("attn_norm", "kv_norm"))]
        for n, (group, name) in enumerate(norms):
            leaf = group[name]
            group[name] = (leaf.astype(jnp.float32) + jax.random.uniform(
                jax.random.fold_in(key, 100 + n), leaf.shape, jnp.float32,
                -_NORM_RANGE, _NORM_RANGE)).astype(leaf.dtype)
        return tree

    return jax.jit(seeded, out_shardings=param_shardings(c, mesh))(jax.random.key(seed))


# ---------------------------------------------------------- plain reference
def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def _f32(tree, i):
    """Layer ``i`` of a stacked group, upcast to float32."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32), tree)


def _index(a, i):
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32)


def _rotate(x, positions, theta):
    """Rotary embedding over the last axis of ``x`` [T, .., d], the two
    HALVES paired; ``positions`` [T] run along axis 0."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs  # [T, d/2]
    angles = angles.reshape(angles.shape[:1] + (1,) * (x.ndim - 2) + angles.shape[1:])
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
         x2 * jnp.cos(angles) + x1 * jnp.sin(angles)], axis=-1)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)


# ---- latent attention
@functools.lru_cache(maxsize=None)
def _attention(r: int, dn: int, theta: float, eps: float, kv_eps: float):
    import jax
    import jax.numpy as jnp

    def project(x, w, positions):
        """Tokens ``x`` [T, D] at ``positions`` [T], expanded -> (q_nope, q_rope
        [T, H, .], k_nope [T, H, dn], k_rope [T, dr], v [T, H, dv], gate [T, H])."""
        h = _rms(x, w["attn_norm"], eps)
        q = jnp.einsum("td,dnh->tnh", h, w["wq"])
        kva = h @ w["w_kva"]
        c = _rms(kva[:, :r], w["kv_norm"], kv_eps)
        return (q[..., :dn], _rotate(q[..., dn:], positions, theta),
                jnp.einsum("tc,cnh->tnh", c, w["w_uk"]), _rotate(kva[:, r:], positions, theta),
                jnp.einsum("tc,cnh->tnh", c, w["w_uv"]), jax.nn.sigmoid(h @ w["w_z"]))

    @jax.jit
    def layer(x, attn, ia, lens):  # x [B, S, D] float32 -> x + attention
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, ia)
            t = jnp.arange(x.shape[1])

            def row(x, n):  # one row, whole and expanded: causal over its own n tokens
                q_nope, q_rope, k_nope, k_rope, v, gate = project(x, w, t)
                scores = (jnp.einsum("snh,tnh->nst", q_nope, k_nope)
                          + jnp.einsum("snh,th->nst", q_rope, k_rope)
                          ) / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
                mask = (t[None, :] <= t[:, None]) & (t[None, :] < n)
                probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
                o = jnp.einsum("nst,tnh->snh", probs, v) * gate[..., None]  # one gate a head
                return jnp.einsum("snh,nhd->sd", o, w["wo"])

            return x + jax.vmap(row)(x, lens)

    @jax.jit
    def nodes(xn, at, x, attn, ia):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N] of ONE row whose stream is ``x`` [S, D]: each attends the
        row's EARLIER positions as the reference has them, and itself."""
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, ia)
            t = jnp.arange(x.shape[0])
            _, _, k_nope, k_rope, v, _ = project(x, w, t)
            q_nope, q_rope, own_nope, own_rope, own_v, gate = project(xn, w, at)
            scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
            earlier = (jnp.einsum("pnh,tnh->pnt", q_nope, k_nope)
                       + jnp.einsum("pnh,th->pnt", q_rope, k_rope)) * scale
            earlier = jnp.where(t[None, None, :] < at[:, None, None], earlier, -1e30)
            own = (jnp.sum(q_nope * own_nope, axis=-1)
                   + jnp.einsum("pnh,ph->pn", q_rope, own_rope)) * scale
            probs = jax.nn.softmax(jnp.concatenate([earlier, own[..., None]], axis=-1), axis=-1)
            o = jnp.einsum("pnt,tnh->pnh", probs[..., :-1], v) + probs[..., -1:] * own_v
            return xn + jnp.einsum("pnh,nhd->pd", o * gate[..., None], w["wo"])

    return layer, nodes


# ---- Kimi Delta Attention
@functools.lru_cache(maxsize=None)
def _kda(H: int, dk: int, dv: int, taps: int, lower: float, eps: float):
    import jax
    import jax.numpy as jnp

    C = 2 * H * dk + H * dv

    def inputs(x, w):
        """Tokens ``x`` [T, D] -> (qkv [T, C] BEFORE the conv, z [T, H], beta
        [T, H], g [T, H, dk])."""
        h = _rms(x, w["mixer_norm"], eps)
        out = jnp.einsum("td,ed->te", h, w["w_in"])
        a = jnp.einsum("td,ed->te", h, w["w_alpha"]).reshape(-1, H, dk)
        g = lower * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * (a + w["dt_bias"]))
        return out[:, :C], out[:, C:C + H], jax.nn.sigmoid(out[:, C + H:]), g

    def heads(conv):  # silu(conv) [T, C] -> q, k [T, H, dk] normalised, v [T, H, dv]
        return (_l2(conv[:, :H * dk].reshape(-1, H, dk)) / math.sqrt(dk),
                _l2(conv[:, H * dk:2 * H * dk].reshape(-1, H, dk)),
                conv[:, 2 * H * dk:].reshape(-1, H, dv))

    def conv_of(qkv, w):  # causal depthwise: tap j sees the input taps - 1 - j back
        T = qkv.shape[0]
        padded_ = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded_[j:j + T] * w["conv_w"][j] for j in range(taps)))

    def delta(S, q, k, v, beta, g):  # one position, all heads: S [H, dk, dv]
        S = S * jnp.exp(g)[:, :, None]  # a decay a key ROW of S
        u = jnp.einsum("hkv,hk->hv", S, k)
        S = S + k[:, :, None] * ((v - u) * beta[:, None])[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    def out_of(o, z, w):  # o [.., H, dv], z [.., H] -> [.., D]
        y = _rms(o, w["norm"], eps) * jax.nn.sigmoid(z)[..., None]
        return jnp.einsum("...e,ed->...d", y.reshape(*y.shape[:-2], -1), w["w_out"])

    @functools.partial(jax.jit, static_argnames="span")
    def nodes(xn, at, x, gdn, im, first, span: int):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N], all within ``[first, first + span)``, of ONE row whose
        stream is ``x`` [S, D]: each takes the state and the conv's inputs the
        row's EARLIER positions left, as the reference has them."""
        with jax.default_matmul_precision("highest"):
            w = _f32(gdn, im)
            qkv, _, beta, g = inputs(x, w)
            q, k, v = heads(conv_of(qkv, w))

            def position(carry, step):  # keep the state BEFORE each position of the span
                S, kept = carry
                t, *rest = step
                at_span = jnp.clip(t - first, 0, span - 1)
                inside = (t >= first) & (t < first + span)
                kept = jax.lax.dynamic_update_index_in_dim(
                    kept, jnp.where(inside, S, kept[at_span]), at_span, 0)
                S, _ = delta(S, *rest)
                return (S, kept), None

            (_, before), _ = jax.lax.scan(
                position,
                (jnp.zeros((H, dk, dv), jnp.float32), jnp.zeros((span, H, dk, dv), jnp.float32)),
                (jnp.arange(x.shape[0]), q, k, v, beta, g))
            own_qkv, z, own_beta, own_g = inputs(xn, w)
            # the conv at a node: its own input under the last tap, the row's before it
            padded_ = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
            pre = own_qkv * w["conv_w"][taps - 1] + sum(
                padded_[at + j] * w["conv_w"][j] for j in range(taps - 1))
            own_q, own_k, own_v = heads(jax.nn.silu(pre))
            _, o = jax.vmap(delta)(before[at - first], own_q, own_k, own_v, own_beta, own_g)
            return xn + out_of(o, z, w)

    @jax.jit
    def layer(x, gdn, im, lens):
        """x [B, S, D] float32 -> (x + mixer, S [B, 2, H, dk, dv] as each
        row's first ``lens - 1`` and ``lens`` tokens left it: what an engine
        that served the row's last token holds, whether or not its last
        dispatch went on to feed that token)."""
        with jax.default_matmul_precision("highest"):
            w = _f32(gdn, im)

            def row(x, n):  # token by token, from S = 0
                qkv, z, beta, g = inputs(x, w)
                q, k, v = heads(conv_of(qkv, w))

                def position(carry, step):
                    S, kept = carry
                    t, *rest = step
                    S, o = delta(S, *rest)
                    kept = jnp.where((t == n - 2 + jnp.arange(2))[:, None, None, None], S, kept)
                    return (S, kept), o

                zero = jnp.zeros((H, dk, dv), jnp.float32)
                (_, kept), o = jax.lax.scan(
                    position, (zero, jnp.stack([zero, zero])),
                    (jnp.arange(x.shape[0]), q, k, v, beta, g))
                return out_of(o, z, w), kept

            out, kept = jax.vmap(row)(x, lens)
            return x + out, kept

    return layer, nodes


# ---- the FFNs
@functools.lru_cache(maxsize=None)
def _dense_ffn(eps: float):
    import jax

    @jax.jit
    def layer(x, dense, i):
        with jax.default_matmul_precision("highest"):
            w = _f32(dense, i)
            return x + _swiglu(_rms(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])

    return layer


def _pick(scores, bias, n_group: int, topk_group: int):
    """What the top k is taken of: ``scores + bias`` [.., E] of the KEPT
    groups' experts (the groups' two largest summed, the best groups kept),
    minus infinity outside them."""
    import jax
    import jax.numpy as jnp

    pick = scores + bias
    E = pick.shape[-1]
    if n_group > 1:
        grouped = pick.reshape(*pick.shape[:-1], n_group, E // n_group)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, groups = jax.lax.top_k(best, topk_group)
        kept = jnp.sum(jax.nn.one_hot(groups, n_group, dtype=jnp.float32), axis=-2) > 0
        pick = jnp.where(jnp.repeat(kept, E // n_group, axis=-1), pick, -jnp.inf)
    return pick


def _chosen(scores, bias, k: int, n_group: int, topk_group: int):
    """The group-limited choice -> [.., E] of 0 and 1: the k largest of
    :func:`_pick`."""
    import jax
    import jax.numpy as jnp

    _, top = jax.lax.top_k(_pick(scores, bias, n_group, topk_group), k)
    return jnp.sum(jax.nn.one_hot(top, scores.shape[-1], dtype=jnp.float32), axis=-2)


@functools.lru_cache(maxsize=None)
def _gate(eps: float):
    import jax

    @jax.jit
    def scored(x, moe, m):
        """Expert layer ``m``'s ``s + bias`` [.., E scored]: what its choice,
        of groups and then of experts, is made on."""
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(_rms(x, _index(moe["mlp_norm"], m), eps) @ _index(moe["router"], m))
        return s + _index(moe["router_bias"], m)

    return scored


def _choices(scored, k: int, tie: float, n_group: int, topk_group: int, held: tuple[int, int]):
    """``routing_tie.routings`` for a gate that chooses by group: ``scored``
    [N, E] -> (parent, chosen, first, crowded) as ``routings`` gives them.
    The rule is applied twice: to the GROUPS' scores (the two largest summed,
    ``topk_group`` kept; every choice of groups within the tie), then to the
    experts of each choice of groups.  A choice that names the same HELD
    experts as an earlier one of its token is that one again (what differs
    lies on other devices) and is dropped."""
    import numpy as np

    N, E = scored.shape
    if n_group <= 1:
        return routings(scored, k, tie, held)
    best = np.sort(scored.reshape(N, n_group, E // n_group), axis=-1)[..., -2:].sum(-1)
    token, kept, own_groups, crowded = routings(best, topk_group, tie)
    pick = np.where(np.repeat(kept > 0, E // n_group, axis=-1), scored[token], -np.inf)
    with np.errstate(invalid="ignore"):  # -inf beside -inf: no doubt there
        row, chosen, own_experts, many = routings(pick, k, tie, held)
    parent, first = token[row], own_groups[row] & own_experts
    crowded = crowded | (np.bincount(token, weights=many, minlength=N) > 0)
    seen, keep = set(), np.zeros(len(parent), bool)
    for m, n in enumerate(parent):  # a token's own choice comes first of its rows
        mine = (int(n), chosen[m, held[0]:held[0] + held[1]].tobytes())
        keep[m] = mine not in seen
        seen.add(mine)
    return parent[keep], chosen[keep], first[keep], crowded


@functools.lru_cache(maxsize=None)
def _gate_counts(k: int, n_group: int, topk_group: int, first: int, eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def counts(x, moe, m, lens):
        """Tokens expert layer ``m``'s gate sends to each HELD expert, row by
        row [B, 2, held], over the row's first ``lens - 1`` and ``lens``
        tokens (as the delta-rule layer keeps its state)."""
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(_rms(x, _index(moe["mlp_norm"], m), eps) @ _index(moe["router"], m))
        chosen = _chosen(s, _index(moe["router_bias"], m), k, n_group, topk_group)
        fed = jnp.arange(x.shape[1])[None, None, :] < (
            lens[:, None] - 1 + jnp.arange(2)[None, :])[..., None]  # [B, 2, S]
        held = moe["w_gate"].shape[1]
        return jnp.einsum("bfs,bse->bfe", fed.astype(jnp.int32),
                          chosen[..., first:first + held].astype(jnp.int32))

    return counts


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, n_group: int, topk_group: int, norm: bool, scale: float, first: int,
                eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, moe, m, chosen=None):
        """Every HELD expert on every token, times a weight that is zero
        outside the chosen; ONE expert's float32 copy at a time.  ``chosen``
        [.., E scored] of 0 and 1 names each token's experts; without it they
        are the gate's own choice."""
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _index(moe["mlp_norm"], m), eps)
            s = jax.nn.sigmoid(h @ _index(moe["router"], m))  # [.., E scored]
            if chosen is None:
                chosen = _chosen(s, _index(moe["router_bias"], m), k, n_group, topk_group)
            w = s * chosen
            if norm:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            held = moe["w_gate"].shape[1]
            w = w[..., first:first + held] * scale

            def one(a, e):  # held expert e of layer m, float32
                return jax.lax.dynamic_slice(
                    a, (m, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)

            def expert(acc, e):
                out = _swiglu(h, one(moe["w_gate"], e), one(moe["w_up"], e),
                              one(moe["w_down"], e))
                return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
            return x + y + _swiglu(h, _index(moe["s_gate"], m), _index(moe["s_up"], m),
                                   _index(moe["s_down"], m))

    return layer


def _layers(c):
    eps = float(c.norm_eps)
    attention, attn_nodes = _attention(
        c.kv_lora_rank, c.qk_nope_head_dim, float(c.rope_theta), eps, float(c.kv_norm_eps))
    kda, kda_nodes = _kda(c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v, c.gdn_d_conv,
                          float(c.kda_lower_bound), eps)
    gate = (c.n_experts_per_tok, c.n_group, c.topk_group)
    experts = _expert_ffn(*gate, bool(c.norm_topk_prob), float(c.routed_scaling_factor),
                          c.expert_first, eps)
    return (attention, attn_nodes, kda, kda_nodes, experts, _dense_ffn(eps),
            _gate_counts(*gate, c.expert_first, eps))


def _walk(params, c, tokens, lens, keep=False, left=False):
    """The stream after the last layer, float32, for a few rows [B, S]; with
    ``keep`` also every layer's input; with ``left`` also what the rows'
    tokens leave behind, without the last and with it: every delta-rule
    layer's state [Lg, B, 2, H, dk, dv] and every expert layer's tokens to
    each held expert [Lm, B, 2, held]."""
    import jax.numpy as jnp

    attention, _, kda, _, experts, dense, counts = _layers(c)
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    row_lens = jnp.asarray(lens)
    inputs, states, sent = [], [], []
    ia = im = 0
    for il, kind in enumerate(c.layer_types):  # one layer's float32 copy at a time
        if keep:
            inputs.append(x)
        if kind == ATTENTION:
            x = attention(x, layers["attn"], jnp.int32(ia), row_lens)
            ia += 1
        else:
            x, state = kda(x, layers["gdn"], jnp.int32(im), row_lens)
            states.append(state)
            im += 1
        if il < c.first_k_dense:
            x = dense(x, layers["dense"], jnp.int32(il))
            continue
        m = jnp.int32(il - c.first_k_dense)
        if left:
            sent.append(counts(x, layers["moe"], m, row_lens))
        x = experts(x, layers["moe"], m)
    return x, inputs, ((jnp.stack(states), jnp.stack(sent)) if left else None)


def forward_logits(params, model_config, tokens, lens):
    """Full forward -> float32 logits [B, S, V], held whole: for the small
    sizes of the tests, which compare logits and never tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = model_config
    x, _, _ = _walk(params, c, np.asarray(tokens), np.asarray(lens))
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), float(c.norm_eps))
        return np.asarray(jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(jnp.float32)))


def left_behind(params, model_config, tokens, lens):
    """(states [Lg, B, 2, H, dk, dv], sent [Lm, B, 2, held]) of ``_walk``:
    for the tests that hold an engine's state and counts to the reference's."""
    import numpy as np

    _, _, left = _walk(params, model_config, np.asarray(tokens), np.asarray(lens), left=True)
    return tuple(np.asarray(a) for a in left)


@functools.lru_cache(maxsize=None)
def _head(eps: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, lm_head, v0):  # top 2 of one block of the vocabulary
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), eps)
            w = jax.lax.dynamic_slice_in_dim(lm_head, v0, block, axis=1).astype(jnp.float32)
            top, idx = jax.lax.top_k(jnp.einsum("bsd,dv->bsv", h, w), 2)
            return top, idx + v0

    return head


def _top2(x, params, eps):
    """(argmax, top-1 margin) of the logits of ``x`` [B, S, D]: the head a
    block of the vocabulary at a time, the blocks' top 2 merged."""
    import numpy as np

    lm_head = params["lm_head"]
    V = lm_head.shape[1]
    block = min(_VOCAB_BLOCK, V)
    head = _head(eps, block)
    tops, idxs = [], []
    for v0 in sorted({min(v, V - block) for v in range(0, V, block)}):
        top, idx = head(x, params["final_norm"], lm_head, np.int32(v0))
        tops.append(np.asarray(top))
        idxs.append(np.asarray(idx))
    top, idx = np.concatenate(tops, axis=-1), np.concatenate(idxs, axis=-1)
    first = np.argmax(top, axis=-1)
    arg = np.take_along_axis(idx, first[..., None], axis=-1)[..., 0]
    best = np.take_along_axis(top, first[..., None], axis=-1)[..., 0]
    # the runner-up: the best candidate that is another token (overlapping
    # blocks name the same token twice)
    rest = np.where(idx == arg[..., None], -np.inf, top)
    return arg, best - rest.max(axis=-1)


def _top2_blocks(x, params, eps):
    """``_top2`` over [B, S, D], a block of positions at a time."""
    import numpy as np

    parts = [_top2(x[:, s0:s0 + _HEAD_BLOCK], params, eps)
             for s0 in range(0, x.shape[1], _HEAD_BLOCK)]
    return (np.concatenate([a for a, _ in parts], axis=1),
            np.concatenate([g for _, g in parts], axis=1))


def _admitted(params, c, inputs, row: int, at, tie: float):
    """Every routing within the tie for positions ``at`` of one row, followed
    through the later layers (``benchmarks/routing_tie.py`` has the rule) ->
    (position [M] index into ``at``, stream [M, D] after the last layer,
    first [M] bool: the reference's own routing, given_up [len(at)] bool)."""
    import jax.numpy as jnp
    import numpy as np

    _, attn_nodes, _, kda_nodes, experts, dense, _ = _layers(c)
    scored = _gate(float(c.norm_eps))
    layers = params["layers"]
    moe, k = layers["moe"], c.n_experts_per_tok
    at = np.asarray(at)
    span = 1 << max(int(at.max() - at.min()), 1).bit_length()  # one shape for the span
    position = np.arange(len(at))
    first = np.ones(len(at), bool)
    given_up = np.zeros(len(at), bool)
    x = np.asarray(inputs[0][row])[at]
    ia = im = 0
    for il, kind in enumerate(c.layer_types):
        n, size = len(x), room(len(x))
        if kind == ATTENTION:
            x = attn_nodes(padded(x, size), padded(at[position], size), inputs[il][row],
                           layers["attn"], jnp.int32(ia))
            ia += 1
        else:
            x = kda_nodes(padded(x, size), padded(at[position], size), inputs[il][row],
                          layers["gdn"], jnp.int32(im), jnp.int32(at.min()), span=span)
            im += 1
        if il < c.first_k_dense:
            x = np.asarray(dense(x, layers["dense"], jnp.int32(il)))[:n]
            continue
        m = jnp.int32(il - c.first_k_dense)
        parent, chosen, position, first = bounded(
            position, first, given_up,
            *_choices(np.asarray(scored(x, moe, m))[:n], k, tie, c.n_group, c.topk_group,
                      (c.expert_first, c.n_routed_experts)))
        x = np.asarray(x)[parent]
        n, size = len(x), room(len(x))
        x = np.asarray(experts(padded(x, size), moe, m, padded(chosen, size)))[:n]
    return position, x, first, given_up


def _decided(params, c, inputs, row: int, at, served, margin: float, tie: float):
    """The rule for positions ``at`` of one row, whose served tokens are
    ``served`` -> (argmax [len(at)], margin [len(at)], what was seen, counted)."""
    position, x, first, given_up = _admitted(params, c, inputs, row, at, tie)
    whole = -(-len(x) // _HEAD_BLOCK) * _HEAD_BLOCK  # whole blocks: one shape for the head
    arg, gap = _top2_blocks(padded(x, whole)[None], params, float(c.norm_eps))
    return decide(position, first, given_up, arg[0, :len(x)], gap[0, :len(x)], served, margin)


def _engine_of(params):
    """The engine that serves ``params``, or None: the harness hands
    ``forward_top2`` the tree and nothing else of the engine, so the check
    of what the served rows LEFT BEHIND finds the engine by the tree it
    holds (``qwen3-next-gdn-moe.py`` has the reason)."""
    import gc

    from calfkit_tpu.inference.engine import InferenceEngine

    return next((e for e in gc.get_objects()
                 if isinstance(e, InferenceEngine) and e.params is params), None)


@functools.lru_cache(maxsize=None)
def _state_errors():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errors(held, ref):
        """``held`` [Lg, slots, H, dk, dv], the engine's; ``ref`` [Lg, H, dk,
        dv], one row's -> (the slot whose FIRST layer's state is nearest the
        row's, that slot's distance over the row's norm, layer by layer)."""
        first = held[0].astype(jnp.float32)
        slot = jnp.argmin(jnp.sum(jnp.square(first - ref[0]), axis=(1, 2, 3)))
        mine = jax.lax.dynamic_index_in_dim(held, slot, 1, keepdims=False).astype(jnp.float32)
        far = jnp.sqrt(jnp.sum(jnp.square(mine - ref), axis=(1, 2, 3)))
        return slot, far / jnp.sqrt(jnp.sum(jnp.square(ref), axis=(1, 2, 3)))

    return errors


def _left_behind(engine, states, sent) -> dict:
    """What the engine still holds of the rows it served, against what the
    reference says they leave (``_walk(left=True)``):

    - ``state_error``: a finished row's slot keeps its delta-rule state until
      a wave lands in it; its distance from the reference's over the
      reference's norm, in the FIRST delta-rule layer (whose input has had
      the least rounding), the rows' mean;
    - ``gate_mismatch``: the share of the FIRST expert layer's counts of
      tokens to each held expert that differ from the reference's routing of
      the same tokens.

    A row's last served token is fed to the model only if the engine's last
    dispatch for the row ran past it, so the reference keeps both states and
    both counts and each row is held to the nearer state.  Both readings need
    the engine to have served these rows and nothing else since it started,
    which is how the harness runs the agreement check."""
    import numpy as np

    out = {}
    held = engine.recurrent_state()
    fed_last = np.ones(states.shape[1], np.int64)
    if held is not None:
        errors = _state_errors()
        slots, e = [], []
        for r in range(states.shape[1]):
            both = [errors(held[0], states[:, r, f]) for f in range(2)]
            fed_last[r] = int(float(both[1][1][0]) <= float(both[0][1][0]))
            slots.append(int(both[fed_last[r]][0]))
            e.append(np.asarray(both[fed_last[r]][1]))
        e = np.asarray(e)  # [rows, Lg]
        out.update(state_slots=slots, rows_fed_their_last_token=int(fed_last.sum()),
                   state_error_by_layer=[round(float(v), 6) for v in e.mean(0)],
                   state_error_worst_row=float(e[:, 0].max()),
                   state_error=float(e[:, 0].mean()))
    counts = engine.moe_expert_counts()
    if counts is not None:
        ref = sent[:, np.arange(sent.shape[1]), fed_last].sum(1)  # [Lm, held]
        miss = np.abs(np.asarray(counts, np.int64) - ref).sum(1) / np.maximum(ref.sum(1), 1)
        out.update(gate_mismatch_by_layer=[round(float(v), 6) for v in miss],
                   gate_mismatch=float(miss[0]))
    return out


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits.  Where an engine serves ``params``,
    also what the rows left behind in it (``_left_behind``), each reading
    beside its limit on stderr; a reading over its limit is returned as ONE
    decided position that no token satisfies, so that the harness's own
    comparison reads it.  At the positions whose next token was SERVED (a
    row's last ``agreement.new_tokens``), and with an ``agreement.routing_tie``,
    by the rule of ``benchmarks/routing_tie.py``."""
    import collections
    import sys

    import numpy as np

    c = model_config
    margin, tie, new = (getattr(c, "agreement_margin", 0.0), getattr(c, "routing_tie", 0.0),
                        getattr(c, "agreement_new_tokens", 0))
    follow = bool(tie)
    engine = _engine_of(params)
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    args, gaps, seen, served_all, behind = [], [], collections.Counter(), [], []
    for r0 in range(0, tokens.shape[0], _ROWS_AT_ONCE):
        rows = slice(r0, r0 + _ROWS_AT_ONCE)
        x, inputs, left = _walk(params, c, tokens[rows], lens[rows], keep=follow,
                                left=engine is not None)
        behind.append(left)
        arg, gap = _top2_blocks(x, params, float(c.norm_eps))
        for b, (row, n) in enumerate(zip(tokens[rows], lens[rows])):
            at = np.arange(max(n - 1 - new, 0) if new else 0, n - 1)
            served_all.append(row[at + 1])
            if follow and len(at):
                arg[b, at], gap[b, at], counted = _decided(
                    params, c, inputs, b, at, row[at + 1], margin, tie)
                seen.update(counted)
        args.append(arg)
        gaps.append(gap)
    arg, gap = np.concatenate(args), np.concatenate(gaps)
    readings = {}
    if engine is not None:
        readings = _left_behind(
            engine, np.concatenate([np.asarray(s) for s, _ in behind], axis=1),
            np.concatenate([np.asarray(n, np.int64) for _, n in behind], axis=1))
    over = []
    for name, what in (("state_error", "the first delta-rule layer's state the served rows "
                        "left, distance from the reference's over its norm"),
                       ("gate_mismatch", "the first expert layer's tokens to each held expert, "
                        "share that differs from the reference's")):
        limit = getattr(c, f"{name}_limit", 0.0)
        if name in readings and limit:
            passes = readings[name] <= limit
            over += [] if passes else [name]
            print(f"benchmarks/architectures/bailing-kda-mla-moe.py: "
                  f"{'ok  ' if passes else 'FAIL'} {what}: {readings[name]:.6f} "
                  f"(limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    print(json.dumps({
        "phase": "reference", "architecture": "bailing-kda-mla-moe",
        "positions": int(lens.sum()), "routing_tie": tie, **seen, **readings,
        "over_their_limit": over,
        "served_tokens": int(sum(len(s) for s in served_all)),
        "distinct_served_tokens": len({int(t) for s in served_all for t in s}),
        "served_token_repeats_the_one_before": int(
            sum((s[1:] == s[:-1]).sum() for s in served_all)),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, H = config["hidden_size"], config["num_attention_heads"]
    kept = kept_layers(config)
    L = len(kept)
    La = sum((i + 1) % config["layer_group_size"] == 0 for i in kept)
    Ld = sum(i < config["first_k_dense_replace"] for i in kept)
    r, dn, dr, dvh = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    dk = dv = config["head_dim"]
    taps = config["short_conv_kernel_size"]
    F, Fe, Fs, V = (config["intermediate_size"], config["moe_intermediate_size"],
                    config["moe_shared_expert_intermediate_size"], config["vocab_size"])
    E = config["num_experts"]  # held here
    scored = config.get("published", {}).get("num_experts", E)
    conv_dim = 2 * H * dk + H * dv
    return dict(
        D=D, L=L, La=La, Lg=L - La, Ld=Ld, Lm=L - Ld, H=H, dk=dk, dv=dv, V=V, E=E,
        scored=scored, k=config["num_experts_per_tok"], latent=r + dr, r=r, dn=dn, dr=dr,
        dvh=dvh,
        mla=D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dvh) + H * dvh * D + D * H,
        kda=D * (conv_dim + 2 * H) + D * H * dk + H * dv * D,
        dense=3 * D * F, expert=3 * D * Fe, shared=3 * D * Fs, gate=D * scored,
        small=(La * (D + r) + (L - La) * (D + conv_dim * taps + H + H * dk + dv) + L * D
               + (L - Ld) * scored + D),  # the norms, the conv, A_log, dt_bias, the gates' biases
        S_numbers=(L - La) * H * dk * dv, conv_numbers=(L - La) * conv_dim * (taps - 1),
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: both mixers, the
    dense layers, the gates, the shared experts, the head's slice."""
    return (s["La"] * s["mla"] + s["Lg"] * s["kda"] + s["Ld"] * s["dense"]
            + s["Lm"] * (s["gate"] + s["shared"]) + s["D"] * s["V"])


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: every matrix of every kept layer,
    the routed experts held here, the embedding's and the untied head's slice."""
    s = _sizes(config)
    numbers = (_outside_experts(s) + s["Lm"] * s["E"] * s["expert"] + s["V"] * s["D"]
               + s["small"])
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS: ONE latent ``[c | k_rope]`` a
    latent-attention layer (the recurrent state does not grow with length)."""
    s = _sizes(config)
    return float(s["La"] * s["latent"]) * WEIGHT_BYTES[config["precision"]["kv"]]


def recurrent_state_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of recurrent state ``rows`` sequences hold: every delta-rule
    layer's ``S`` at ``precision.state`` and its conv tail at the activations'."""
    s = _sizes(config)
    return float(rows) * (
        s["S_numbers"] * WEIGHT_BYTES[config["precision"]["state"]]
        + s["conv_numbers"] * WEIGHT_BYTES[config["precision"]["activations"]]
    )


def recurrent_state_step(config: dict, rows: float, chips: int = 1) -> dict:
    """What one decode step must do to the recurrent state of ``rows`` rows:
    read it and write it (bytes), and the delta rule on ``S`` (4 multiply-adds
    a number: the decay by key row, ``S^T k``, the rank-one update, ``S^T q``)."""
    s = _sizes(config)
    return {"flops": 8.0 * s["S_numbers"] * rows / chips,
            "bytes": 2.0 * recurrent_state_bytes(config, rows) / chips}


def recurrent_chunk(config: dict, tokens: float, chips: int = 1, block: int = 64) -> dict:
    """What the chunkwise delta rule must do for ``tokens`` prompt tokens in
    every delta-rule layer, in blocks of ``block`` positions: a head's
    token needs, of multiply-adds, the in-block products with the decay
    inside them (``k . k`` below the diagonal and ``q . k`` to it: dk block / 2
    each), the unit triangular solve for ``v | k`` (block / 2 (dv + dk)), the
    in-block output (dv block / 2) and three products with ``S`` (``S^T k``,
    ``S^T q``, the update: dk dv each).  FLOPs alone: the pass's bytes are the
    activations', which the projections' scopes carry."""
    s = _sizes(config)
    dk, dv = s["dk"], s["dv"]
    madds = block / 2.0 * (2 * dk + (dv + dk) + dv) + 3.0 * dk * dv
    return {"flops": 2.0 * madds * s["H"] * s["Lg"] * float(tokens) / chips, "bytes": 0.0}


def experts_hit(config: dict, rows: float) -> float:
    """Distinct HELD experts a layer reads for ``rows`` tokens under EVEN
    routing over all the experts scored: held (1 - (1 - k / scored)^rows).
    55 of 64 at 128 rows of 8 among 512."""
    s = _sizes(config)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["scored"]) ** float(rows))


def expert_layer_step(config: dict, rows: float, hit: float, chips: int = 1) -> dict:
    """What ONE expert block must do on THIS chip in a decode step over
    ``rows`` rows that hit ``hit`` distinct held experts: read those, the
    shared expert and the gate; the products of a row's share of its chosen
    (k x held / scored of them lie here) and of the shared expert."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    numbers = hit * s["expert"] + s["shared"] + s["gate"]
    here = s["k"] * s["E"] / s["scored"]
    flops = 2.0 * rows * (here * s["expert"] + s["shared"] + s["gate"])
    return {"flops": flops / chips, "bytes": numbers * wb / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each, on
    THIS chip: everything outside the experts once, the held experts the
    step must read under EVEN routing, each row's recurrent state read AND
    written, the latents of the rows' contexts once a latent-attention layer
    (H heads' scores over r + dr and values over r)."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    ctx = float(rows) * float(mean_context)
    moe = expert_layer_step(config, rows, experts_hit(config, rows))
    state = recurrent_state_step(config, rows)
    mixers = s["La"] * s["mla"] + s["Lg"] * s["kda"] + s["Ld"] * s["dense"] + s["D"] * s["V"]
    flops = (2.0 * mixers * rows + s["Lm"] * moe["flops"] + state["flops"]
             + 2.0 * s["La"] * s["H"] * (s["latent"] + s["r"]) * ctx)
    bytes_ = ((mixers + s["small"]) * wb + s["Lm"] * moe["bytes"] + state["bytes"]
              + state_bytes_per_token(config) * ctx)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context, on THIS chip: the matmul FLOPs of both mixers, the dense
    layers, the gates, the shared experts and the tokens' share of their
    chosen experts, the chunkwise delta rule's, causal attention expanded in
    the latent layers; the weights outside the embedding once with the held
    experts the chunk hits, the rows' recurrent state in and out, the latents
    written and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    here = s["k"] * s["E"] / s["scored"]
    per_token = (s["La"] * s["mla"] + s["Lg"] * s["kda"] + s["Ld"] * s["dense"]
                 + s["Lm"] * (s["gate"] + s["shared"] + here * s["expert"]) + s["D"] * s["V"])
    flops = (2.0 * per_token * tokens
             + 2.0 * s["La"] * s["H"] * (s["dn"] + s["dr"] + s["dvh"]) * attended
             + recurrent_chunk(config, tokens)["flops"])
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["Lm"] * experts_hit(config, tokens) * s["expert"] * wb
              + 2.0 * recurrent_state_bytes(config, rows)
              + state_bytes_per_token(config) * rows * (offset + chunk))
    return {"flops": flops / chips, "bytes": bytes_ / chips}
