"""Architecture ``deepseek-mla-moe``: latent attention and routed experts.

HF ``DeepseekV3ForCausalLM`` as ``moonshotai/Kimi-VL-A3B-Instruct`` publishes
it for its language decoder (``text_config``: DeepSeek-V3's architecture at
Moonlight's widths).  Behind the interface ``manifest.load_architecture``
checks: the program's model description from a configuration file, the
seeded parameter tree, the plain float32 reference, and the operations and
bytes the mathematics requires.

Architecture, by the keys of the model's ``config.json`` (D = hidden_size,
H = num_attention_heads, r = kv_lora_rank, dn | dr = qk_nope | qk_rope
_head_dim, dv = v_head_dim):

- stack: pre-norm residual, ``x = x + attn(rmsnorm_1(x))``, ``x = x +
  ffn(rmsnorm_2(x))`` (``rms_norm_eps``); the first ``first_k_dense_replace``
  layers' ``ffn`` is one SwiGLU of ``intermediate_size``, every later layer's
  is the expert layer (``moe_layer_freq`` 1); final rmsnorm; an UNTIED head.
- latent attention (``q_lora_rank`` null: the query is not compressed):
  ``q = h W_q`` -> H heads of ``q_nope | q_rope``; ``[c | k_rope] = h W_kva``
  -> r | dr, ONE of each a token, shared by all heads; ``c = rmsnorm(c)``
  (``kv_a_layernorm``, HF's class default epsilon 1e-6); ``[k_nope | v] = c
  W_kvb`` -> H heads of dn | dv; rotary embedding (``rope_theta``, no
  scaling) on ``q_rope`` and ``k_rope`` only; ``k = [k_nope | k_rope]``,
  scores ``q . k / sqrt(dn + dr)``, causal softmax, ``o = P v``, ``o W_o``.
- expert layer: ``s = sigmoid(float32(h) W_g)`` (``n_routed_experts``
  scores); the experts are the top ``num_experts_per_tok`` of ``s + b``
  (``e_score_correction_bias``; ``n_group`` = ``topk_group`` = 1, so the
  group limit is the identity); their weights are the UNBIASED ``s`` of the
  chosen, ``w = s / (sum s + 1e-20) * routed_scaling_factor``
  (``norm_topk_prob``); ``y = sum_e w_e E_e(h) + Shared(h)``, every ``E_e``
  a SwiGLU of ``moe_intermediate_size``, ``Shared`` ONE SwiGLU of
  ``n_shared_experts`` times that.  No capacity, no dropped token.

The reference computes attention in the EXPANDED form above over the whole
sequence (no cache, no absorbed form, no kernel) and the expert layer as
"every expert on every token, times a weight that is zero outside the
chosen", one expert at a time: plain, and independent of any grouping.

**Near-ties of the gate.**  A top-k is not continuous: where the last expert
chosen leads the first one left out by less than the rounding of a bfloat16
stream (``agreement.routing_tie``, in the biased scores the top-k sees), the
program may rightly choose the other one, and then that expert's whole
weighted output moves the position's logits and its later layers' choices.
Every matrix stays at 1/sqrt(fan_in); the comparison deals with it instead:
for each position it decides, the reference follows EVERY choice of experts
that is within the tie, through all later layers (a position's own stream
alone: the earlier positions' keys and values are the reference's), and

- accepts the served token if it is the argmax, by more than the margin, of
  ONE of those routings (``forward_top2`` then returns it with that margin);
- fails it if every routing decides by more than the margin and none gives it;
- leaves the position undecided (margin 0) otherwise.

So a position with a tie is still compared, and a program whose choice of
experts is off by more than the tie (a bias left out of the choice, a gate
in a lower precision whose error is larger) serves tokens that none of the
admitted routings gives.

Departures from the published description: none in the mathematics.  The
tree holds ``kv_b_proj`` as its two halves (``w_uk``, ``w_uv``), which
changes no product.  HF's rotary embedding for this family pairs ADJACENT
columns of the rope part (``rope_interleave``); the tree's rope columns are
in the order that pairs the two HALVES (the loader permutes them once), so
the reference rotates halves.  Text only: the vision tower of the published
model is no part of this architecture.

The reference's weights are the tree the engine serves, upcast to float32
ONE LAYER, and within an expert layer ONE EXPERT, at a time, a few rows at a
time and the head a block of the vocabulary at a time, so that it fits
beside the engine on the chip (an expert layer is 2.3 GB in float32).  It
imports nothing of the program but the model description it is handed.
Counts are what the mathematics requires: in a decode step everything
outside the experts once, the experts the rows' choices hit, the latent
cache of the rows' contexts once a layer.
"""

from __future__ import annotations

import functools
import itertools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES

_ROWS_AT_ONCE = 2  # rows the reference carries through a layer together
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_VOCAB_BLOCK = 16384  # columns of the head upcast at once
_NODES_AT_LEAST = 128  # a position's routings are padded to a power of two from here
_ROUTINGS_A_LAYER = 6  # more choices than this within the tie at one layer, or
_ROUTINGS_A_POSITION = 48  # than this in all: the position is left undecided
# the seeded gate (params): W_g at this gain on 1/sqrt(fan_in), so its logits
# have a standard deviation of 2 and the sigmoid scores spread over 0.1-0.9;
# and the range e_score_correction_bias is drawn from, uniform
_ROUTER_GAIN = 2.0
_ROUTER_BIAS_RANGE = 0.05

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "n_routed_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok", "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_d_ff", "first_k_dense_replace": "first_k_dense",
    "n_group": "n_group", "topk_group": "topk_group",
}


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
    ], bases=(ModelConfig,), frozen=True)


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RuntimeConfig

    if config.get("q_lora_rank") is not None:
        raise ValueError("deepseek-mla-moe: a compressed query (q_lora_rank) is not described")
    if config.get("rope_scaling") is not None:
        raise ValueError("deepseek-mla-moe: rope_scaling is not described")
    if config.get("moe_layer_freq", 1) != 1:
        raise ValueError("deepseek-mla-moe: moe_layer_freq other than 1")
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    agree = config["agreement"]
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        kv_norm_eps=float(config["kv_a_layernorm_eps"]),
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_topk_prob=bool(config["norm_topk_prob"]), scoring_func=config["scoring_func"],
        topk_method=config["topk_method"], agreement_margin=float(agree["margin"]),
        agreement_new_tokens=int(agree["new_tokens"]),
        routing_tie=float(agree.get("routing_tie", 0.0)), **sizes,
    )
    return described, RuntimeConfig(**runtime)


# ------------------------------------------------------------------ weights
def params(model_config, runtime, mesh, seed: int):
    """The seeded tree the engine is started with, made on the device from
    the seed in the type it is served in (``assumed`` in the configuration
    file).  The program's own initialiser draws every matrix at
    1/sqrt(fan_in), the norms at 1 and ``e_score_correction_bias`` zero;
    three leaves are seeded HERE, for what the cell is to show:

    - the embedding at UNIT scale (a lookup's fan-in is the one row it
      reads).  At 1/sqrt(hidden_size) a token's own row is a tenth of what
      the first attention layer adds, which over random keys is the context's
      mean: every position of a sequence then carries the same direction,
      the gate sends most of them to the same few experts (the busiest took
      7.4 times the mean and 36 of 64 were hit by 56 rows, my chip runs, PR
      31) and no routing a trained model sends was measured;
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in): a gate that scored
      every expert 0.5 +- 0.01 would make every top-k a coin toss between
      the program's bfloat16 stream and the reference's float32 one;
    - ``e_score_correction_bias`` uniform in +-``_ROUTER_BIAS_RANGE`` and
      NOT zero: it moves 1.2 of a token's 6 experts, so a program that puts
      the bias into the weights, or leaves it out of the choice, disagrees
      with the reference.  (+-0.1 at this gain, where the leading scores lie
      within 0.07 of each other, starves some experts: the busiest 2.3
      times the mean, the idlest 0.04; +-0.05: 1.8 and 0.4.)"""
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
        if c.n_routed_experts:
            moe = tree["layers"]["moe"]
            moe["router"] = moe["router"] * _ROUTER_GAIN
            moe["router_bias"] = jax.random.uniform(
                jax.random.fold_in(key, 1), moe["router_bias"].shape, jnp.float32,
                -_ROUTER_BIAS_RANGE, _ROUTER_BIAS_RANGE)
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


def _rotate(x, positions, theta, axis: int):
    """Rotary embedding over the last axis of ``x``, the two HALVES paired;
    ``positions`` run along ``axis`` of ``x``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[:, None] * freqs  # [T, d/2]
    shape = [1] * x.ndim
    shape[axis], shape[-1] = x.shape[axis], d // 2
    angles = angles.reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
         x2 * jnp.cos(angles) + x1 * jnp.sin(angles)], axis=-1)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _project(x, w, positions, r, dn, theta, eps, kv_eps):
    """The mixer's projections of tokens ``x`` [T, D] at ``positions`` [T],
    expanded: (q_nope [T,H,dn], q_rope [T,H,dr], k_nope [T,H,dn], k_rope
    [T,dr], v [T,H,dv]); ONE ``c`` and ONE ``k_rope`` a token."""
    import jax.numpy as jnp

    h = _rms(x, w["attn_norm"], eps)
    q = jnp.einsum("td,dnh->tnh", h, w["wq"])
    kva = h @ w["w_kva"]
    c = _rms(kva[..., :r], w["kv_norm"], kv_eps)
    return (q[..., :dn], _rotate(q[..., dn:], positions, theta, 0),
            jnp.einsum("tc,cnh->tnh", c, w["w_uk"]), _rotate(kva[..., r:], positions, theta, 0),
            jnp.einsum("tc,cnh->tnh", c, w["w_uv"]))


@functools.lru_cache(maxsize=None)
def _attention(r: int, dn: int, theta: float, eps: float, kv_eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, attn, i, lens):  # x [B, S, D] float32 -> x + attention
        with jax.default_matmul_precision("highest"):
            S = x.shape[1]
            w = _f32(attn, i)
            t = jnp.arange(S)

            def row(x, n):  # one row, whole: causal over its own n tokens
                q_nope, q_rope, k_nope, k_rope, v = _project(x, w, t, r, dn, theta, eps, kv_eps)
                scores = (jnp.einsum("snh,tnh->nst", q_nope, k_nope)
                          + jnp.einsum("snh,th->nst", q_rope, k_rope)
                          ) / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
                mask = (t[None, :] <= t[:, None]) & (t[None, :] < n)
                probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
                return jnp.einsum("snh,nhd->sd", jnp.einsum("nst,tnh->snh", probs, v), w["wo"])

            return x + jax.vmap(row)(x, lens)

    @jax.jit
    def nodes(xn, at, x, attn, i):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N] of ONE row whose stream is ``x`` [S, D]: each attends the
        row's EARLIER positions as the reference has them, and itself."""
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, i)
            t = jnp.arange(x.shape[0])
            _, _, k_nope, k_rope, v = _project(x, w, t, r, dn, theta, eps, kv_eps)
            q_nope, q_rope, own_nope, own_rope, own_v = _project(
                xn, w, at, r, dn, theta, eps, kv_eps)
            scale = 1.0 / math.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
            earlier = (jnp.einsum("pnh,tnh->pnt", q_nope, k_nope)
                       + jnp.einsum("pnh,th->pnt", q_rope, k_rope)) * scale
            earlier = jnp.where(t[None, None, :] < at[:, None, None], earlier, -1e30)
            own = (jnp.sum(q_nope * own_nope, axis=-1)
                   + jnp.einsum("pnh,ph->pn", q_rope, own_rope)) * scale
            probs = jax.nn.softmax(jnp.concatenate([earlier, own[..., None]], axis=-1), axis=-1)
            out = jnp.einsum("pnt,tnh->pnh", probs[..., :-1], v) + probs[..., -1:] * own_v
            return xn + jnp.einsum("pnh,nhd->pd", out, w["wo"])

    return layer, nodes


@functools.lru_cache(maxsize=None)
def _dense_ffn(eps: float):
    import jax

    @jax.jit
    def layer(x, dense, i):
        with jax.default_matmul_precision("highest"):
            w = _f32(dense, i)
            return x + _swiglu(_rms(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"])

    return layer


def _index(a, i):
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _gate(eps: float):
    import jax

    @jax.jit
    def scores(x, moe, m):
        """The gate's UNBIASED sigmoid scores [.., E] of expert layer ``m``."""
        with jax.default_matmul_precision("highest"):
            return jax.nn.sigmoid(_rms(x, _index(moe["mlp_norm"], m), eps) @ _index(moe["router"], m))

    return scores


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, norm: bool, scale: float, eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, moe, m, chosen=None):
        """Every expert on every token, times a weight that is zero
        outside the chosen; ONE expert's float32 copy at a time.  ``chosen``
        [.., E] of 0 and 1 names each token's experts; without it they are
        the top k of the biased scores."""
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _index(moe["mlp_norm"], m), eps)
            s = jax.nn.sigmoid(h @ _index(moe["router"], m))  # [.., E]
            E = s.shape[-1]
            if chosen is None:
                _, top = jax.lax.top_k(s + _index(moe["router_bias"], m), k)
                chosen = jnp.sum(jax.nn.one_hot(top, E, dtype=jnp.float32), axis=-2)
            w = s * chosen
            if norm:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            w = w * scale

            def one(a, e):  # expert e of layer m, float32
                return jax.lax.dynamic_slice(
                    a, (m, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)

            def expert(acc, e):
                out = _swiglu(h, one(moe["w_gate"], e), one(moe["w_up"], e),
                              one(moe["w_down"], e))
                return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
            if "s_gate" in moe:
                y = y + _swiglu(h, _index(moe["s_gate"], m), _index(moe["s_up"], m),
                                _index(moe["s_down"], m))
            return x + y

    return layer


def _layers(c):
    eps = float(c.norm_eps)
    attention, nodes = _attention(c.kv_lora_rank, c.qk_nope_head_dim, float(c.rope_theta), eps,
                                  float(c.kv_norm_eps))
    experts = _expert_ffn(c.n_experts_per_tok, bool(c.norm_topk_prob),
                          float(c.routed_scaling_factor), eps) if c.n_routed_experts else None
    return attention, nodes, _dense_ffn(eps), experts


def _walk(params, c, tokens, lens, keep=False):
    """The stream after the last layer, float32, for a few rows [B, S]; with
    ``keep`` also every layer's input."""
    import jax.numpy as jnp

    attention, _, dense, experts = _layers(c)
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    row_lens = jnp.asarray(lens)
    n_dense = c.first_k_dense if c.n_routed_experts else c.n_layers
    inputs = []
    for i in range(c.n_layers):  # one layer's float32 copy at a time
        if keep:
            inputs.append(x)
        x = attention(x, layers["attn"], jnp.int32(i), row_lens)
        if i < n_dense:
            x = dense(x, layers["dense"], jnp.int32(i))
        else:
            x = experts(x, layers["moe"], jnp.int32(i - n_dense))
    return x, inputs


def forward_logits(params, model_config, tokens, lens):
    """Full forward -> float32 logits [B, S, V], held whole: for the small
    sizes of the tests, which compare logits and never tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = model_config
    x, _ = _walk(params, c, np.asarray(tokens), np.asarray(lens))
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), float(c.norm_eps))
        return np.asarray(jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(jnp.float32)))


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


def _routings(biased, k: int, tie: float):
    """Every choice of k experts within ``tie`` of the top k of ``biased``
    [N, E] -> (parent [M] the token of each choice, chosen [M, E] of 0 and 1,
    first [M] bool: the top k itself, crowded [N] bool: a token with more
    choices than ``_ROUTINGS_A_LAYER``, which keeps its top k alone).  An
    expert inside the top k is in doubt if it leads the first one outside by
    less than the tie, one outside if the last one inside leads it by less;
    the experts in doubt take the places of those inside in every way."""
    import numpy as np

    N, E = biased.shape
    order = np.argsort(-biased, axis=-1, kind="stable")
    ranked = np.take_along_axis(biased, order, axis=-1)
    inside = ranked[:, :k] - ranked[:, k:k + 1] < tie  # [N, k]
    outside = ranked[:, k - 1:k] - ranked[:, k:] < tie  # [N, E - k]
    parent, chosen, first = [], [], []
    crowded = np.zeros(N, bool)
    top = np.zeros((N, E), np.float32)
    np.put_along_axis(top, order[:, :k], 1.0, axis=-1)
    for n in range(N):
        parent.append(n)
        chosen.append(top[n])
        first.append(True)
        if not outside[n].any():
            continue
        doubt = [*order[n, :k][inside[n]], *order[n, k:][outside[n]]]
        places = int(inside[n].sum())
        if math.comb(len(doubt), places) > _ROUTINGS_A_LAYER:
            crowded[n] = True
            continue
        sure = top[n].copy()
        sure[doubt] = 0.0
        for take in itertools.combinations(doubt, places):
            if set(take) == set(order[n, :k][inside[n]]):
                continue  # the top k itself
            other = sure.copy()
            other[list(take)] = 1.0
            parent.append(n)
            chosen.append(other)
            first.append(False)
    return np.asarray(parent), np.stack(chosen), np.asarray(first), crowded


def _padded(a, n: int):
    import numpy as np

    a = np.asarray(a)
    return np.concatenate([a, np.repeat(a[:1], n - len(a), axis=0)]) if n > len(a) else a


def _admitted(params, c, inputs, row: int, at, tie: float):
    """Every routing within the tie for positions ``at`` of one row, followed
    through the later layers -> (position [M] index into ``at``, stream [M, D]
    after the last layer, first [M] bool: the reference's own routing,
    given_up [len(at)] bool)."""
    import jax.numpy as jnp
    import numpy as np

    _, nodes, _, experts = _layers(c)
    scores = _gate(float(c.norm_eps))
    layers = params["layers"]
    moe, k = layers["moe"], c.n_experts_per_tok
    bias = np.asarray(moe["router_bias"], np.float32)
    at = np.asarray(at)
    position = np.arange(len(at))
    first = np.ones(len(at), bool)
    given_up = np.zeros(len(at), bool)
    x = np.asarray(inputs[c.first_k_dense][row])[at]
    for i in range(c.first_k_dense, c.n_layers):
        m = i - c.first_k_dense
        n = len(x)
        size = max(_NODES_AT_LEAST, 1 << (n - 1).bit_length())
        x = nodes(_padded(x, size), _padded(at[position], size), inputs[i][row],
                  layers["attn"], jnp.int32(i))
        biased = np.asarray(scores(x, moe, jnp.int32(m)))[:n] + bias[m]
        parent, chosen, top, crowded = _routings(biased, k, tie)
        given_up[position[crowded]] = True
        many = np.bincount(position[parent], minlength=len(at)) > _ROUTINGS_A_POSITION
        if many.any():  # keep the reference's own routing of such a position alone
            given_up |= many
            keep = ~many[position[parent]] | (top & first[parent])
            parent, chosen, top = parent[keep], chosen[keep], top[keep]
        x = np.asarray(x)[parent]
        position, first = position[parent], first[parent] & top
        n = len(x)
        size = max(_NODES_AT_LEAST, 1 << (n - 1).bit_length())
        x = np.asarray(experts(_padded(x, size), moe, jnp.int32(m), _padded(chosen, size)))[:n]
    return position, x, first, given_up


def _decided(params, c, inputs, row: int, at, served, margin: float, tie: float):
    """The rule of the module's text for positions ``at`` of one row, whose
    served tokens are ``served`` -> (argmax [len(at)], margin [len(at)],
    what was seen, counted)."""
    import numpy as np

    position, x, first, given_up = _admitted(params, c, inputs, row, at, tie)
    whole = -(-len(x) // _HEAD_BLOCK) * _HEAD_BLOCK  # whole blocks: one shape for the head
    arg, gap = _top2_blocks(_padded(x, whole)[None], params, float(c.norm_eps))
    arg, gap = arg[0, :len(x)], gap[0, :len(x)]
    out_arg, out_gap = np.zeros(len(at), arg.dtype), np.zeros(len(at), gap.dtype)
    seen = {"routings": len(position), "positions_with_a_routing_tie": 0,
            "positions_given_up_for_their_many_routings": int(given_up.sum()),
            "accepted_under_another_routing_than_the_reference's": 0,
            "served_token_under_no_admitted_routing": 0}
    for p in range(len(at)):
        mine = position == p
        own = int(np.flatnonzero(mine & first)[0])  # the reference's own routing
        seen["positions_with_a_routing_tie"] += int(mine.sum() > 1)
        hits = mine & (arg == served[p]) & (gap > margin)
        out_arg[p] = arg[own]
        if hits.any():
            out_arg[p], out_gap[p] = served[p], gap[hits].max()
            seen["accepted_under_another_routing_than_the_reference's"] += int(not hits[own])
        elif not given_up[p] and (gap[mine] > margin).all():
            out_gap[p] = gap[own]  # decided, and the served token is none of them
            seen["served_token_under_no_admitted_routing"] += 1
    return out_arg, out_gap, seen


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits.  At the positions whose next token
    was SERVED (a row's last ``agreement.new_tokens``), and with an
    ``agreement.routing_tie``, by the rule of the module's text: the served
    token and the margin of a routing within the tie that gives it; the
    reference's own argmax and margin where every such routing decides and
    none gives it; the margin 0, undecided, otherwise.  Also logs what the
    margin rule cannot show by itself: how many distinct tokens the engine
    served, how many positions had a tie, how many were accepted under
    another routing than the reference's own."""
    import collections

    import numpy as np

    c = model_config
    margin, tie, new = (getattr(c, "agreement_margin", 0.0), getattr(c, "routing_tie", 0.0),
                        getattr(c, "agreement_new_tokens", 0))
    follow = bool(tie and c.n_routed_experts)
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    args, gaps, seen, served_all = [], [], collections.Counter(), []
    for r0 in range(0, tokens.shape[0], _ROWS_AT_ONCE):
        rows = slice(r0, r0 + _ROWS_AT_ONCE)
        x, inputs = _walk(params, c, tokens[rows], lens[rows], keep=follow)
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
    print(json.dumps({
        "phase": "reference", "architecture": "deepseek-mla-moe",
        "positions": int(lens.sum()), "routing_tie": tie, **seen,
        "served_tokens": int(sum(len(s) for s in served_all)),
        "distinct_served_tokens": len({int(t) for s in served_all for t in s}),
        "served_token_repeats_the_one_before": int(
            sum((s[1:] == s[:-1]).sum() for s in served_all)),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, L, H = config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"]
    r, dn, dr, dv = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                     config["qk_rope_head_dim"], config["v_head_dim"])
    F, Fe, V = config["intermediate_size"], config["moe_intermediate_size"], config["vocab_size"]
    E, k, ns = (config["n_routed_experts"], config["num_experts_per_tok"],
                config["n_shared_experts"])
    Ld = config["first_k_dense_replace"] if E else L
    Lm = L - Ld
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    expert = 3 * D * Fe
    return dict(
        D=D, L=L, Ld=Ld, Lm=Lm, H=H, r=r, dn=dn, dr=dr, dv=dv, V=V, E=E, k=k,
        latent=r + dr, attn=attn, dense=3 * D * F, expert=expert,
        gate=D * E, shared=ns * expert,
        small=L * (2 * D + r) + Lm * E + D,  # the norms, the gates' biases
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: attention, the
    dense layers, the gates, the shared experts, the head."""
    return (s["L"] * s["attn"] + s["Ld"] * s["dense"] + s["Lm"] * (s["gate"] + s["shared"])
            + s["D"] * s["V"])


def weight_bytes(config: dict) -> float:
    """Bytes of weights the device holds: every matrix of every layer, all
    the routed experts, the embedding and the untied head, at the stated
    weight precision."""
    s = _sizes(config)
    numbers = (_outside_experts(s) + s["Lm"] * s["E"] * s["expert"] + s["V"] * s["D"]
               + s["small"])
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS: ONE latent ``[c | k_rope]`` a
    layer, not K and V per head."""
    s = _sizes(config)
    return float(s["L"] * s["latent"]) * WEIGHT_BYTES[config["precision"]["kv"]]


def experts_hit(config: dict, rows: float) -> float:
    """Distinct experts a layer reads for ``rows`` tokens under EVEN routing
    (each token's experts drawn alike over all of them): E (1 - (1 - k/E)^rows).
    63.9 of 64 at 64 rows of 6; about 35 at 8 rows."""
    s = _sizes(config)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** float(rows)) if s["E"] else 0.0


def expert_layer_step(config: dict, rows: float, hit: float, chips: int = 1) -> dict:
    """What ONE expert layer's FFN must do in a decode step over ``rows``
    rows that hit ``hit`` distinct experts: read those experts, the shared
    expert and the gate; the products of the chosen and the shared experts a
    row."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    numbers = hit * s["expert"] + s["shared"] + s["gate"]
    flops = 2.0 * rows * (s["k"] * s["expert"] + s["shared"] + s["gate"])
    return {"flops": flops / chips, "bytes": numbers * wb / chips}


def latent_read_step(config: dict, context_tokens: float, chips: int = 1) -> dict:
    """What ONE layer's absorbed read must do in a decode step whose rows
    hold ``context_tokens`` cached tokens in all: read each token's latent
    once; H heads' scores over r + dr and values over r."""
    s = _sizes(config)
    flops = 2.0 * s["H"] * (s["latent"] + s["r"]) * context_tokens
    return {"flops": flops / chips,
            "bytes": s["latent"] * WEIGHT_BYTES[config["precision"]["kv"]] * context_tokens / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each:
    everything outside the experts once, the experts the step must read
    under EVEN routing (:func:`experts_hit`; a skewed gate reads fewer), the
    latent cache of the rows' contexts once a layer; the FLOPs of the chosen
    and shared experts a row and of the absorbed scores."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    ctx = float(rows) * float(mean_context)
    moe = expert_layer_step(config, rows, experts_hit(config, rows))
    read = latent_read_step(config, ctx)
    dense_numbers = s["L"] * s["attn"] + s["Ld"] * s["dense"] + s["D"] * s["V"]
    flops = 2.0 * dense_numbers * rows + s["Lm"] * moe["flops"] + s["L"] * read["flops"]
    bytes_ = ((dense_numbers + s["small"]) * wb + s["Lm"] * moe["bytes"]
              + s["L"] * read["bytes"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context: the matmul FLOPs of the tokens ROUTED (the chosen and
    the shared experts a token, never every expert), causal attention in the
    expanded form in every layer; the weights outside the embedding once,
    the latents written and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    per_token = (s["L"] * s["attn"] + s["Ld"] * s["dense"]
                 + s["Lm"] * (s["gate"] + s["shared"] + s["k"] * s["expert"]) + s["D"] * s["V"])
    flops = (2.0 * per_token * tokens
             + 2.0 * s["L"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * attended)
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["Lm"] * experts_hit(config, tokens) * s["expert"] * wb
              + state_bytes_per_token(config) * rows * (offset + chunk))
    return {"flops": flops / chips, "bytes": bytes_ / chips}
