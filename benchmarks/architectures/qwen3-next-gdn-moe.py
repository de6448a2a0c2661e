"""Architecture ``qwen3-next-gdn-moe``: Gated DeltaNet beside gated attention,
the expert block in every layer, the experts held by SHARE.

HF ``Qwen3NextForCausalLM`` (``model_type`` ``qwen3_next``) as
``Qwen/Qwen3-Next-80B-A3B-Instruct`` publishes it.  Behind the interface
``manifest.load_architecture`` checks: the program's model description from
a configuration file, the seeded parameter tree, the plain float32
reference, and the operations and bytes the mathematics requires.

Architecture, by the keys of the model's ``config.json`` (D = hidden_size;
every RMSNorm of the stack multiplies by ``1 + w``, ``rms_norm_eps``, except
the gated norm inside DeltaNet, which multiplies by ``w``):

- stack: layer ``i`` is full attention where ``(i + 1) %
  full_attention_interval == 0``, Gated DeltaNet otherwise; ``x = x +
  mixer(norm_1(x))``, ``x = x + moe(norm_2(x))`` with the expert block in
  EVERY layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` []); final norm,
  an UNTIED head.
- Gated DeltaNet (``linear_num_key_heads`` Hk key heads and
  ``linear_num_value_heads`` Hv value heads of ``linear_key_head_dim`` dk and
  ``linear_value_head_dim`` dv, ``linear_conv_kernel_dim`` taps): ``[q | k | v
  | z] = h W_qkvz``, ``[b | a] = h W_ba``; ``(q, k, v) = silu(causal depthwise
  conv without bias over the channels q | k | v)``; ``q, k`` L2-normalised
  per head (eps 1e-6), ``q / sqrt(dk)``, a key head serving Hv / Hk value
  heads; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  Per
  value head, ``S`` of dk x dv zero for a new sequence: ``S = exp(g_t) S``;
  ``u = S^T k_t``; ``S = S + k_t (x) (beta_t (v_t - u))``; ``o_t = S^T q_t``;
  ``y = rmsnorm_w(o_t) * silu(z_t)`` per head; ``out = y W_o``.
- gated attention (``num_attention_heads`` H query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``): ``[q | gate] = h W_q``
  per head, ``k = h W_k``, ``v = h W_v``; ``q, k = rmsnorm_(1 + w)`` over the
  head; rotary embedding (``rope_theta``, halves rotated) on the FIRST
  ``partial_rotary_factor`` of the head, the rest left; causal softmax of ``q
  k^T / sqrt(head_dim)``; ``out = (o * sigmoid(gate)) W_o``.
- expert block: ``p = softmax(float32(h) W_g)`` over ALL ``num_experts`` the
  model has (the configuration's ``published.num_experts``); the
  ``num_experts_per_tok`` largest; ``w = p[chosen] / sum p[chosen]``
  (``norm_topk_prob``); ``y = sum_e w_e E_e(h) + sigmoid(h . w_sg)
  Shared(h)``, every ``E_e`` a SwiGLU of ``moe_intermediate_size``, ``Shared``
  one of ``shared_expert_intermediate_size``.  No bias in the choice, no
  scaling factor, no capacity, no dropped assignment.

**The share.**  The configuration's ``num_experts`` is how many experts THIS
chip holds: ``[expert_first, expert_first + num_experts)`` of the published
count.  The gate keeps its published width and its experts a token; the sum
runs over the held experts alone, with the weights normalised over the
token's whole top k, NOT over the held.  What an absent expert would add
to a token is left out, here as in the program, and the partial sum goes on
to the next layer.  The vocabulary's slice is a smaller vocabulary.

The reference runs DeltaNet token by token, exactly the recurrence above
(a plain ``lax.scan`` over positions: no chunking, no cache), attention over
the whole sequence, and the expert block as "every HELD expert on every
token, times a weight that is zero outside the chosen", one expert at a time.

**Near-ties of the gate.**  A top-k is not continuous: where the last expert
chosen leads the first one left out by less than the rounding of a bfloat16
stream, the program may rightly choose the other one.  With an
``agreement.routing_tie`` (in the gate's LOGITS: a softmax keeps their
order, and what the stream's rounding moves is a logit, whatever the number
of experts the probabilities are shared among) the reference follows, for
each position it decides, EVERY choice of experts within the tie through all
later layers (a position's own stream alone: the earlier positions' keys,
values, DeltaNet states and conv inputs are the reference's) and accepts
the served token under any of them: the rule, its bounds and its verdict are
``benchmarks/routing_tie.py``'s, the walk through this architecture's
layers is ``_admitted``.  A tie among experts that are ALL held elsewhere
opens no branch.  The configuration file sets the tie from readings on the
chip; at 0 no branch is followed.

**What the served rows leave behind.**  A delta-rule state stored, or a gate
taken, in bfloat16 where the file states float32 serves tokens the margin
rule cannot tell from the stated program's (the bfloat16 stream's own
rounding covers both: the file's ``agreement.why``).  Each shows where it
acts, before later layers drown it: ``forward_top2`` finds the engine that
serves the tree it is handed and holds the first DeltaNet layer's state the
finished rows left in their slots, and the first layer's count of tokens to
each held expert, to the reference's (``_left_behind``), each against a
limit of the configuration file.

Departures from the published description: none in the mathematics.  The
tree holds ``in_proj_qkvz`` and ``in_proj_ba`` as ONE matrix with its rows
in the order ``q | k | v | z | b | a``, heads in order within each, where HF
interleaves them per key head (the loader undoes that once), which changes
no product.  The multi-token-prediction module of the published checkpoint
is no part of this architecture (``assumed`` in the configuration file).

The reference's weights are the tree the engine serves, upcast to float32
ONE LAYER, and within an expert block ONE EXPERT, at a time, a few rows at a
time and the head a block of the vocabulary at a time.  It imports nothing of
the program but the model description it is handed.  Counts are what the
mathematics requires of THIS chip: in a decode step everything outside the
experts once, the held experts the rows' choices hit, each row's recurrent
state read AND written, the K and V of the rows' contexts.
"""

from __future__ import annotations

import functools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES
from benchmarks.routing_tie import bounded, decide, padded, room, routings

ATTENTION, GDN = "attention", "gdn"
_ROWS_AT_ONCE = 2  # rows the reference carries through a layer together
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_VOCAB_BLOCK = 16384  # columns of the head upcast at once
_L2_EPS = 1e-6
# the seeded tree (params): W_g at this gain on 1/sqrt(fan_in), so the gate's
# logits have a standard deviation of 2 and a token's tenth expert leads its
# eleventh by about 0.08; the (1 + w) norms' w uniform in +-this
_ROUTER_GAIN = 2.0
_NORM_RANGE = 0.1

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "head_dim": "attn_head_dim",
    "linear_num_key_heads": "gdn_n_k_heads", "linear_num_value_heads": "gdn_n_v_heads",
    "linear_key_head_dim": "gdn_d_k", "linear_value_head_dim": "gdn_d_v",
    "linear_conv_kernel_dim": "gdn_d_conv", "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok", "moe_intermediate_size": "moe_d_ff",
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
        ("state_error_limit", float, 0.0),  # 0: the reading is logged, nothing is held to it
        ("gate_mismatch_limit", float, 0.0),
    ], bases=(ModelConfig,), frozen=True)


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RuntimeConfig

    for key, want in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("rope_scaling", None), ("use_sliding_window", False)):
        if config.get(key, want) != want:
            raise ValueError(f"qwen3-next-gdn-moe: {key} other than {want!r} is not described")
    if config["shared_expert_intermediate_size"] % config["moe_intermediate_size"]:
        raise ValueError("qwen3-next-gdn-moe: the shared expert is whole expert widths")
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    sizes["n_experts_total"] = config["published"].get("num_experts", config["num_experts"])
    sizes["expert_first"] = int(config.get("expert_first", 0))
    sizes["n_shared_experts"] = (
        config["shared_expert_intermediate_size"] // config["moe_intermediate_size"])
    agree = config["agreement"]
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    every = int(config["full_attention_interval"])
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        state_dtype=config["precision"]["state"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        layer_types=tuple(ATTENTION if (i + 1) % every == 0 else GDN
                          for i in range(sizes["n_layers"])),
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        qk_norm=True, attn_output_gate=True, norm_plus_one=True, shared_expert_gate=True,
        scoring_func="softmax", topk_method="greedy",
        norm_topk_prob=bool(config["norm_topk_prob"]),
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
    1/sqrt(fan_in), ``A_log`` and ``dt_bias`` from the ranges HF initialises
    them in, and every norm at its identity; seeded HERE, for what the cell
    is to show:

    - the embedding at UNIT scale (a lookup's fan-in is the one row it
      reads: PERF.md section 6, PR 31);
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in), so that its logits
      spread (standard deviation 2) and the top ten are no coin toss;
    - every norm's ``w`` uniform in +-``_NORM_RANGE`` around its identity (0
      for the ``1 + w`` norms, 1 for DeltaNet's gated norm), so that a
      program that multiplies by ``w`` where the model multiplies by ``1 +
      w`` disagrees with the reference."""
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
        layers["moe"]["router"] = layers["moe"]["router"] * _ROUTER_GAIN
        norms = [(tree, "final_norm"), (layers["moe"], "mlp_norm"),
                 (layers["gdn"], "mixer_norm"), (layers["gdn"], "norm"),
                 *((layers["attn"], n) for n in ("attn_norm", "q_norm", "k_norm"))]
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


def _rotate(x, positions, theta, rot: int, axis: int):
    """Rotary embedding on the FIRST ``rot`` of the last axis of ``x``, its
    two HALVES paired, the rest left; ``positions`` run along ``axis``."""
    import jax.numpy as jnp

    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    angles = positions.astype(jnp.float32)[:, None] * freqs  # [T, rot/2]
    shape = [1] * x.ndim
    shape[axis], shape[-1] = x.shape[axis], rot // 2
    angles = angles.reshape(shape)
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
         x2 * jnp.cos(angles) + x1 * jnp.sin(angles), x[..., rot:]], axis=-1)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + _L2_EPS)


# ---- gated attention
def _attn_project(x, w, positions, hd, rot, theta, eps):
    """Tokens ``x`` [T, D] at ``positions`` [T] -> (q [T,H,hd], k [T,K,hd], v
    [T,K,hd], gate [T,H,hd]): normed per head, rotated on the first ``rot``."""
    import jax.numpy as jnp

    h = _rms(x, 1.0 + w["attn_norm"], eps)
    qg = jnp.einsum("td,dnh->tnh", h, w["wq"])
    q, gate = qg[..., :hd], qg[..., hd:]
    k = jnp.einsum("td,dkh->tkh", h, w["wk"])
    v = jnp.einsum("td,dkh->tkh", h, w["wv"])
    q = _rotate(_rms(q, 1.0 + w["q_norm"], eps), positions, theta, rot, 0)
    k = _rotate(_rms(k, 1.0 + w["k_norm"], eps), positions, theta, rot, 0)
    return q, k, v, gate


@functools.lru_cache(maxsize=None)
def _attention(hd: int, rot: int, theta: float, eps: float):
    import jax
    import jax.numpy as jnp

    def out(o, gate, w):  # o, gate [.., H, hd]
        return jnp.einsum("...nh,nhd->...d", o * jax.nn.sigmoid(gate), w["wo"])

    @jax.jit
    def layer(x, attn, ia, lens):  # x [B, S, D] float32 -> x + attention
        with jax.default_matmul_precision("highest"):
            S = x.shape[1]
            w = _f32(attn, ia)
            t = jnp.arange(S)

            def row(x, n):  # one row, whole: causal over its own n tokens
                q, k, v, gate = _attn_project(x, w, t, hd, rot, theta, eps)
                G = q.shape[1] // k.shape[1]
                qg = q.reshape(S, k.shape[1], G, hd)
                scores = jnp.einsum("skgh,tkh->kgst", qg, k) / math.sqrt(hd)
                mask = (t[None, :] <= t[:, None]) & (t[None, :] < n)
                probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
                o = jnp.einsum("kgst,tkh->skgh", probs, v).reshape(S, -1, hd)
                return out(o, gate, w)

            return x + jax.vmap(row)(x, lens)

    @jax.jit
    def nodes(xn, at, x, attn, ia):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N] of ONE row whose stream is ``x`` [S, D]: each attends the
        row's EARLIER positions as the reference has them, and itself."""
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, ia)
            t = jnp.arange(x.shape[0])
            _, k, v, _ = _attn_project(x, w, t, hd, rot, theta, eps)
            q, own_k, own_v, gate = _attn_project(xn, w, at, hd, rot, theta, eps)
            N, K = q.shape[0], k.shape[1]
            qg = q.reshape(N, K, q.shape[1] // K, hd)
            scale = 1.0 / math.sqrt(hd)
            earlier = jnp.einsum("pkgh,tkh->pkgt", qg, k) * scale
            earlier = jnp.where(t[None, None, None, :] < at[:, None, None, None], earlier, -1e30)
            own = jnp.einsum("pkgh,pkh->pkg", qg, own_k) * scale
            probs = jax.nn.softmax(jnp.concatenate([earlier, own[..., None]], axis=-1), axis=-1)
            o = (jnp.einsum("pkgt,tkh->pkgh", probs[..., :-1], v)
                 + probs[..., -1:] * own_v[:, :, None, :])
            return xn + out(o.reshape(N, -1, hd), gate, w)

    return layer, nodes


# ---- Gated DeltaNet
def _gdn_inputs(h, w, Hk: int, Hv: int, dk: int, dv: int):
    """Normed tokens ``h`` [T, D] -> (qkv [T, C] BEFORE the conv, z [T, Hv,
    dv], beta [T, Hv], g [T, Hv])."""
    import jax
    import jax.numpy as jnp

    C = 2 * Hk * dk + Hv * dv
    out = jnp.einsum("td,ed->te", h, w["w_in"])
    z = out[:, C:C + Hv * dv].reshape(-1, Hv, dv)
    beta = jax.nn.sigmoid(out[:, C + Hv * dv:C + Hv * dv + Hv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(out[:, C + Hv * dv + Hv:] + w["dt_bias"])
    return out[:, :C], z, beta, g


def _gdn_heads(conv_out, Hk: int, Hv: int, dk: int, dv: int):
    """silu(conv) [T, C] -> q, k [T, Hv, dk] (normalised; q over sqrt(dk);
    a key head repeated for its value heads), v [T, Hv, dv]."""
    import jax.numpy as jnp

    kd = Hk * dk
    q = _l2(conv_out[:, :kd].reshape(-1, Hk, dk)) / math.sqrt(dk)
    k = _l2(conv_out[:, kd:2 * kd].reshape(-1, Hk, dk))
    q, k = jnp.repeat(q, Hv // Hk, axis=1), jnp.repeat(k, Hv // Hk, axis=1)
    return q, k, conv_out[:, 2 * kd:].reshape(-1, Hv, dv)


def _delta(S, q, k, v, beta, g):
    """One position of the recurrence, all value heads: S [Hv, dk, dv]."""
    import jax.numpy as jnp

    S = S * jnp.exp(g)[:, None, None]
    u = jnp.einsum("hkv,hk->hv", S, k)
    S = S + k[:, :, None] * ((v - u) * beta[:, None])[:, None, :]
    return S, jnp.einsum("hkv,hk->hv", S, q)


def _gdn_out(o, z, w, eps):
    """o, z [.., Hv, dv] -> [.., D]: the gated norm (times w), the projection."""
    import jax
    import jax.numpy as jnp

    y = _rms(o, w["norm"], eps) * jax.nn.silu(z)
    return jnp.einsum("...e,ed->...d", y.reshape(*y.shape[:-2], -1), w["w_out"])


@functools.lru_cache(maxsize=None)
def _gdn(Hk: int, Hv: int, dk: int, dv: int, taps: int, eps: float):
    import jax
    import jax.numpy as jnp

    def conv(qkv, w):  # causal depthwise: tap j sees the input taps - 1 - j back
        T = qkv.shape[0]
        padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[j:j + T] * w["conv_w"][j] for j in range(taps)))

    @jax.jit
    def layer(x, gdn, im, lens):
        """x [B, S, D] float32 -> (x + DeltaNet, S [B, 2, Hv, dk, dv] as each
        row's first ``lens - 1`` and ``lens`` tokens left it: what an engine
        that served the row's last token holds, whether or not its last
        dispatch went on to feed that token)."""
        with jax.default_matmul_precision("highest"):
            w = _f32(gdn, im)

            def row(x, n):  # token by token, from S = 0
                qkv, z, beta, g = _gdn_inputs(
                    _rms(x, 1.0 + w["mixer_norm"], eps), w, Hk, Hv, dk, dv)
                q, k, v = _gdn_heads(conv(qkv, w), Hk, Hv, dk, dv)

                def position(carry, inputs):
                    S, kept = carry
                    t, *step = inputs
                    S, o = _delta(S, *step)
                    kept = jnp.where((t == n - 2 + jnp.arange(2))[:, None, None, None], S, kept)
                    return (S, kept), o

                zero = jnp.zeros((Hv, dk, dv), jnp.float32)
                (_, kept), o = jax.lax.scan(
                    position, (zero, jnp.stack([zero, zero])),
                    (jnp.arange(x.shape[0]), q, k, v, beta, g))
                return _gdn_out(o, z, w, eps), kept

            out, kept = jax.vmap(row)(x, lens)
            return x + out, kept

    @functools.partial(jax.jit, static_argnames="span")
    def nodes(xn, at, x, gdn, im, first, span: int):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N], all within ``[first, first + span)``, of ONE row whose
        stream is ``x`` [S, D]: each takes the state and the conv's inputs the
        row's EARLIER positions left, as the reference has them."""
        with jax.default_matmul_precision("highest"):
            w = _f32(gdn, im)
            qkv, _, beta, g = _gdn_inputs(_rms(x, 1.0 + w["mixer_norm"], eps), w, Hk, Hv, dk, dv)
            q, k, v = _gdn_heads(conv(qkv, w), Hk, Hv, dk, dv)

            def position(carry, inputs):  # keep the state BEFORE each position of the span
                S, kept = carry
                t, *step = inputs
                at_span = jnp.clip(t - first, 0, span - 1)
                inside = (t >= first) & (t < first + span)
                kept = jax.lax.dynamic_update_index_in_dim(
                    kept, jnp.where(inside, S, kept[at_span]), at_span, 0)
                S, _ = _delta(S, *step)
                return (S, kept), None

            (_, before), _ = jax.lax.scan(
                position,
                (jnp.zeros((Hv, dk, dv), jnp.float32), jnp.zeros((span, Hv, dk, dv), jnp.float32)),
                (jnp.arange(x.shape[0]), q, k, v, beta, g))
            own_qkv, z, own_beta, own_g = _gdn_inputs(
                _rms(xn, 1.0 + w["mixer_norm"], eps), w, Hk, Hv, dk, dv)
            # the conv at a node: its own input under the last tap, the row's before it
            padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
            pre = own_qkv * w["conv_w"][taps - 1] + sum(
                padded[at + j] * w["conv_w"][j] for j in range(taps - 1))
            own_q, own_k, own_v = _gdn_heads(jax.nn.silu(pre), Hk, Hv, dk, dv)
            _, o = jax.vmap(_delta)(before[at - first], own_q, own_k, own_v, own_beta, own_g)
            return xn + _gdn_out(o, z, w, eps)

    return layer, nodes


# ---- the expert block
def _held_weights(logits, chosen, first: int, held: int, norm: bool):
    """softmax over ALL the experts scored, the chosen's weights normalised
    over the chosen -> the HELD experts' [.., held] (zero outside the chosen)."""
    import jax
    import jax.numpy as jnp

    w = jax.nn.softmax(logits, axis=-1) * chosen
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w[..., first:first + held]


@functools.lru_cache(maxsize=None)
def _gate(eps: float):
    import jax

    @jax.jit
    def logits(x, moe, m):
        """The gate's float32 logits [.., E scored] of layer ``m``."""
        with jax.default_matmul_precision("highest"):
            return _rms(x, 1.0 + _index(moe["mlp_norm"], m), eps) @ _index(moe["router"], m)

    return logits


@functools.lru_cache(maxsize=None)
def _gate_counts(k: int, first: int, eps: float):
    import jax
    import jax.numpy as jnp

    logits = _gate(eps)

    @jax.jit
    def counts(x, moe, m, lens):
        """Tokens layer ``m``'s gate sends to each HELD expert, row by row
        [B, 2, held], over the row's first ``lens - 1`` and ``lens`` tokens
        (as the DeltaNet layer keeps its state)."""
        scored = logits(x, moe, m)  # [B, S, E]
        _, top = jax.lax.top_k(scored, k)
        fed = jnp.arange(x.shape[1])[None, None, :] < (
            lens[:, None] - 1 + jnp.arange(2)[None, :])[..., None]  # [B, 2, S]
        held = moe["w_gate"].shape[1]
        chosen = jax.nn.one_hot(top, scored.shape[-1], dtype=jnp.int32).sum(-2)
        return jnp.einsum("bfs,bse->bfe", fed.astype(jnp.int32), chosen[..., first:first + held])

    return counts


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, norm: bool, first: int, eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, moe, m, chosen=None):
        """Every HELD expert on every token, times a weight that is zero
        outside the chosen; ONE expert's float32 copy at a time.  ``chosen``
        [.., E scored] of 0 and 1 names each token's experts; without it they
        are the top k of the gate."""
        with jax.default_matmul_precision("highest"):
            h = _rms(x, 1.0 + _index(moe["mlp_norm"], m), eps)
            logits = h @ _index(moe["router"], m)  # [.., E scored]
            E, held = logits.shape[-1], moe["w_gate"].shape[1]
            if chosen is None:
                _, top = jax.lax.top_k(logits, k)
                chosen = jnp.sum(jax.nn.one_hot(top, E, dtype=jnp.float32), axis=-2)
            w = _held_weights(logits, chosen, first, held, norm)

            def one(a, e):  # held expert e of layer m, float32
                return jax.lax.dynamic_slice(
                    a, (m, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)

            def expert(acc, e):
                out = _swiglu(h, one(moe["w_gate"], e), one(moe["w_up"], e),
                              one(moe["w_down"], e))
                return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
            shared = _swiglu(h, _index(moe["s_gate"], m), _index(moe["s_up"], m),
                             _index(moe["s_down"], m))
            gate = jax.nn.sigmoid(h @ _index(moe["shared_gate"], m))
            return x + y + gate[..., None] * shared

    return layer


def _layers(c):
    eps = float(c.norm_eps)
    attention, attn_nodes = _attention(c.head_dim, c.rotary_dim, float(c.rope_theta), eps)
    gdn, gdn_nodes = _gdn(c.gdn_n_k_heads, c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v,
                          c.gdn_d_conv, eps)
    experts = _expert_ffn(c.n_experts_per_tok, bool(c.norm_topk_prob), c.expert_first, eps)
    return attention, attn_nodes, gdn, gdn_nodes, experts


def _kinds(c):
    """(kind, index among its kind) of every layer."""
    seen = {ATTENTION: 0, GDN: 0}
    out = []
    for kind in c.layer_types:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def _walk(params, c, tokens, lens, keep=False, left=False):
    """The stream after the last layer, float32, for a few rows [B, S]; with
    ``keep`` also every layer's input; with ``left`` also what the rows'
    tokens leave behind, without the last and with it: every DeltaNet
    layer's state [Lg, B, 2, Hv, dk, dv] and every layer's tokens to each
    held expert [L, B, 2, held]."""
    import jax.numpy as jnp

    attention, _, gdn, _, experts = _layers(c)
    counts = _gate_counts(c.n_experts_per_tok, c.expert_first, float(c.norm_eps))
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    row_lens = jnp.asarray(lens)
    inputs, states, sent = [], [], []
    for il, (kind, i) in enumerate(_kinds(c)):  # one layer's float32 copy at a time
        if keep:
            inputs.append(x)
        if kind == ATTENTION:
            x = attention(x, layers["attn"], jnp.int32(i), row_lens)
        else:
            x, state = gdn(x, layers["gdn"], jnp.int32(i), row_lens)
            states.append(state)
        if left:
            sent.append(counts(x, layers["moe"], jnp.int32(il), row_lens))
        x = experts(x, layers["moe"], jnp.int32(il))
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
        h = _rms(x, 1.0 + params["final_norm"].astype(jnp.float32), float(c.norm_eps))
        return np.asarray(jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(jnp.float32)))


@functools.lru_cache(maxsize=None)
def _head(eps: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, lm_head, v0):  # top 2 of one block of the vocabulary
        with jax.default_matmul_precision("highest"):
            h = _rms(x, 1.0 + final_norm.astype(jnp.float32), eps)
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

    _, attn_nodes, _, gdn_nodes, experts = _layers(c)
    gate = _gate(float(c.norm_eps))
    layers = params["layers"]
    moe, k = layers["moe"], c.n_experts_per_tok
    at = np.asarray(at)
    span = 1 << max(int(at.max() - at.min()), 1).bit_length()  # one shape for the span
    position = np.arange(len(at))
    first = np.ones(len(at), bool)
    given_up = np.zeros(len(at), bool)
    x = np.asarray(inputs[0][row])[at]
    for il, (kind, i) in enumerate(_kinds(c)):
        n, size = len(x), room(len(x))
        if kind == ATTENTION:
            x = attn_nodes(padded(x, size), padded(at[position], size), inputs[il][row],
                           layers["attn"], jnp.int32(i))
        else:
            x = gdn_nodes(padded(x, size), padded(at[position], size), inputs[il][row],
                          layers["gdn"], jnp.int32(i), jnp.int32(at.min()), span=span)
        logits = np.asarray(gate(x, moe, jnp.int32(il)))[:n]
        parent, chosen, position, first = bounded(
            position, first, given_up,
            *routings(logits, k, tie, (c.expert_first, c.n_routed_experts)))
        x = np.asarray(x)[parent]
        n, size = len(x), room(len(x))
        x = np.asarray(experts(padded(x, size), moe, jnp.int32(il), padded(chosen, size)))[:n]
    return position, x, first, given_up


def _decided(params, c, inputs, row: int, at, served, margin: float, tie: float):
    """The rule for positions ``at`` of one row, whose served tokens are
    ``served`` -> (argmax [len(at)], margin [len(at)], what was seen, counted)."""
    position, x, first, given_up = _admitted(params, c, inputs, row, at, tie)
    whole = -(-len(x) // _HEAD_BLOCK) * _HEAD_BLOCK  # whole blocks: one shape for the head
    arg, gap = _top2_blocks(padded(x, whole)[None], params, float(c.norm_eps))
    return decide(position, first, given_up, arg[0, :len(x)], gap[0, :len(x)], served, margin)


def _engine_of(params):
    """The engine that serves ``params``, or None.  The harness hands
    ``forward_top2`` the tree and nothing else of the engine
    (``benchmarks/harness.py``), and what a delta-rule state kept, or a gate
    taken, in lower precision changes does not show in the served tokens
    (PERF.md section 6, PR 33): so the check of what the served rows LEFT
    BEHIND finds the engine by the tree it holds."""
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
        """``held`` [Lg, slots, Hv, dk, dv], the engine's; ``ref`` [Lg, Hv,
        dk, dv], one row's -> (the slot whose FIRST layer's state is nearest
        the row's, that slot's distance over the row's norm, layer by layer)."""
        first = held[0].astype(jnp.float32)
        slot = jnp.argmin(jnp.sum(jnp.square(first - ref[0]), axis=(1, 2, 3)))
        mine = jax.lax.dynamic_index_in_dim(held, slot, 1, keepdims=False).astype(jnp.float32)
        far = jnp.sqrt(jnp.sum(jnp.square(mine - ref), axis=(1, 2, 3)))
        return slot, far / jnp.sqrt(jnp.sum(jnp.square(ref), axis=(1, 2, 3)))

    return errors


def _left_behind(engine, states, sent) -> dict:
    """What the engine still holds of the rows it served, against what the
    reference says they leave (``_walk(left=True)``):

    - ``state_error``: a finished row's slot keeps its delta-rule state
      until a wave lands in it; its distance from the reference's, over the
      reference's norm, in the FIRST DeltaNet layer (whose input has had the
      least rounding), the rows' mean.  A float32 state fed by a bfloat16
      stream reads what the stream's rounding of k, v, beta and g leaves; a
      state STORED in bfloat16 adds a rounding a decode step.
    - ``gate_mismatch``: the engine counts the tokens each held expert of
      each layer was sent; the share of the FIRST layer's counts that differ
      from the reference's (sum over held experts of |engine - reference|,
      over the reference's sum).  A float32 gate on a bfloat16 stream flips
      the choices whose tenth and eleventh logits the stream's rounding
      cannot tell; a gate taken in bfloat16 flips those its own cannot.

    A row's last served token is fed to the model only if the engine's last
    dispatch for the row ran past it (its steps come in fours and eights),
    so the reference keeps both states and both counts and each row is held
    to the nearer state.  Both readings need the engine to have served these
    rows and nothing else since it started, which is how the harness runs
    the agreement check."""
    import numpy as np

    out = {}
    held = engine.recurrent_state()
    fed_last = np.ones(states.shape[1], np.int64)  # without a state to tell: as 64 tokens run
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
        ref = sent[:, np.arange(sent.shape[1]), fed_last].sum(1)  # [L, held]
        miss = np.abs(np.asarray(counts, np.int64) - ref).sum(1) / np.maximum(ref.sum(1), 1)
        out.update(gate_mismatch_by_layer=[round(float(v), 6) for v in miss],
                   gate_mismatch=float(miss[0]))
    return out


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits.  At the positions whose next token
    was SERVED (a row's last ``agreement.new_tokens``), and with an
    ``agreement.routing_tie``, by the rule of ``benchmarks/routing_tie.py``.
    Where an engine serves ``params``, also what the rows left behind in it
    (``_left_behind``), each reading beside its limit on stderr; a reading
    over its limit is returned as ONE decided position that no token
    satisfies, so that the harness's own comparison reads it.  Also logs
    what the margin rule cannot show by itself."""
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
    for name, what in (("state_error", "the first DeltaNet layer's state the served rows left, "
                        "distance from the reference's over its norm"),
                       ("gate_mismatch", "the first layer's tokens to each held expert, share "
                        "that differs from the reference's")):
        limit = getattr(c, f"{name}_limit", 0.0)
        if name in readings and limit:
            passes = readings[name] <= limit
            over += [] if passes else [name]
            print(f"benchmarks/architectures/qwen3-next-gdn-moe.py: "
                  f"{'ok  ' if passes else 'FAIL'} {what}: {readings[name]:.6f} "
                  f"(limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    print(json.dumps({
        "phase": "reference", "architecture": "qwen3-next-gdn-moe",
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
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, K, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv, taps = (config["linear_key_head_dim"], config["linear_value_head_dim"],
                    config["linear_conv_kernel_dim"])
    Fe, Fs, V = (config["moe_intermediate_size"], config["shared_expert_intermediate_size"],
                 config["vocab_size"])
    E = config["num_experts"]  # held here
    scored = config.get("published", {}).get("num_experts", E)
    La = L // config["full_attention_interval"]
    Lg = L - La
    conv_dim = 2 * Hk * dk + Hv * dv
    return dict(
        D=D, L=L, La=La, Lg=Lg, H=H, K=K, hd=hd, Hv=Hv, dk=dk, dv=dv, V=V, E=E, scored=scored,
        k=config["num_experts_per_tok"],
        attn=D * H * 2 * hd + 2 * D * K * hd + H * hd * D,
        gdn=D * (conv_dim + Hv * dv + 2 * Hv) + Hv * dv * D,
        expert=3 * D * Fe, shared=3 * D * Fs, gate=D * scored + D,  # W_g and w_sg
        small=(La * (D + 2 * hd) + Lg * (D + conv_dim * taps + 2 * Hv + dv) + L * D + D),
        S_numbers=Lg * Hv * dk * dv, conv_numbers=Lg * conv_dim * (taps - 1),
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: both mixers, the
    gates, the shared experts, the head's slice."""
    return (s["La"] * s["attn"] + s["Lg"] * s["gdn"] + s["L"] * (s["gate"] + s["shared"])
            + s["D"] * s["V"])


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: every matrix of every layer, the
    routed experts held here, the embedding's and the untied head's slice."""
    s = _sizes(config)
    numbers = (_outside_experts(s) + s["L"] * s["E"] * s["expert"] + s["V"] * s["D"]
               + s["small"])
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS: K and V of the attention
    layers alone (the recurrent state does not grow with length)."""
    s = _sizes(config)
    return 2.0 * s["La"] * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def recurrent_state_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of recurrent state ``rows`` sequences hold: every DeltaNet
    layer's ``S`` at ``precision.state`` and its conv tail at the
    activations' precision."""
    s = _sizes(config)
    return float(rows) * (
        s["S_numbers"] * WEIGHT_BYTES[config["precision"]["state"]]
        + s["conv_numbers"] * WEIGHT_BYTES[config["precision"]["activations"]]
    )


def recurrent_state_step(config: dict, rows: float, chips: int = 1) -> dict:
    """What one decode step must do to the recurrent state of ``rows`` rows:
    read it and write it (bytes), and the delta rule on ``S`` (4 multiply-adds
    a number: the decay, ``S^T k``, the rank-one update, ``S^T q``)."""
    s = _sizes(config)
    return {"flops": 8.0 * s["S_numbers"] * rows / chips,
            "bytes": 2.0 * recurrent_state_bytes(config, rows) / chips}


def experts_hit(config: dict, rows: float) -> float:
    """Distinct HELD experts a layer reads for ``rows`` tokens under EVEN
    routing over all the experts scored: held (1 - (1 - k / scored)^rows).
    92 of 128 at 64 rows of 10 among 512."""
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
    written, the K and V of the attention layers over the context."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    ctx = float(rows) * float(mean_context)
    moe = expert_layer_step(config, rows, experts_hit(config, rows))
    state = recurrent_state_step(config, rows)
    mixers = s["La"] * s["attn"] + s["Lg"] * s["gdn"] + s["D"] * s["V"]
    flops = (2.0 * mixers * rows + s["L"] * moe["flops"] + state["flops"]
             + 4.0 * s["La"] * s["H"] * s["hd"] * ctx)
    bytes_ = ((mixers + s["small"]) * wb + s["L"] * moe["bytes"] + state["bytes"]
              + state_bytes_per_token(config) * ctx)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context, on THIS chip: the matmul FLOPs of both mixers, the
    gates, the shared experts and the tokens' share of their chosen experts,
    the recurrence's (as the recurrence counts them), causal attention in the
    attention layers; the weights outside the embedding once with the held
    experts the chunk hits, the rows' recurrent state in and out, the K and
    V written and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    here = s["k"] * s["E"] / s["scored"]
    per_token = (s["La"] * s["attn"] + s["Lg"] * s["gdn"]
                 + s["L"] * (s["gate"] + s["shared"] + here * s["expert"]) + s["D"] * s["V"])
    flops = (2.0 * per_token * tokens + 4.0 * s["La"] * s["H"] * s["hd"] * attended
             + 8.0 * s["S_numbers"] * tokens)
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["L"] * experts_hit(config, tokens) * s["expert"] * wb
              + 2.0 * recurrent_state_bytes(config, rows)
              + state_bytes_per_token(config) * rows * (offset + chunk))
    return {"flops": flops / chips, "bytes": bytes_ / chips}
