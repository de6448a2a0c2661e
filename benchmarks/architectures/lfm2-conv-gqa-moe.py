"""Architecture ``lfm2-conv-gqa-moe``: a gated short convolution beside rotary
GQA attention with normed heads, leading dense layers, then bias-routed
experts with no shared one.

``model_type`` ``lfm2_moe`` as ``LiquidAI/LFM2-8B-A1B`` publishes it.  Behind
the interface ``manifest.load_architecture`` checks: the program's model
description from a configuration file, the seeded parameter tree, the plain
float32 reference, and the operations and bytes the mathematics requires.

Architecture, by the keys of the model's ``config.json`` (D = hidden_size;
every RMSNorm multiplies by ``w``, ``norm_eps``; ``x = x + mixer(norm(x;
operator_norm))``, ``x = x + ffn(norm(x; ffn_norm))``; after the last layer one
RMSNorm (``embedding_norm``), then the head = the embedding transposed):

- stack: ``layer_types[i]`` is ``conv`` or ``full_attention``; layer ``i``'s
  FFN is a SwiGLU of ``intermediate_size`` where ``i < num_dense_layers``, the
  expert block otherwise.  ``published_layers`` of the configuration file
  names the published layers THIS file keeps, in order.
- ``conv`` mixer: ``B | C | x = h W_in`` (D -> 3 D, no bias, thirds in that
  order); ``u = B * x``; ``v_t = sum_j w[:, j] * u_{t - (L - 1) + j}``
  (depthwise, causal, ``conv_L_cache`` = L taps, ``u`` zero before the
  sequence, NO activation, no bias: ``conv_bias`` false); ``y = (C * v) W_out``.
  What a sequence leaves behind is ``u`` at its last L - 1 positions.
- ``full_attention`` mixer: ``q, k, v = h W_q, h W_k, h W_v``
  (``num_attention_heads`` / ``num_key_value_heads`` heads of D / heads, no
  bias); RMSNorm over each head of ``q`` and of ``k`` (``q_layernorm``,
  ``k_layernorm``) BEFORE the rotation; rotary on the whole head
  (``rope_theta``), the two HALVES of a head paired; causal softmax at
  ``1 / sqrt(head)``; ``W_o``.
- expert block: ``s = sigmoid(float32(h) W_g)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` (``use_expert_bias``:
  the bias in the CHOICE only); ``w = s[chosen] / (sum s[chosen] + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``y = sum_e w_e
  E_e(h)``, every ``E_e`` a SwiGLU of ``moe_intermediate_size``; NO shared
  expert.

The reference runs the convolution as written (a padded sum over the taps:
no tail, no chunk), attention over whole rows, and the expert block as "every
expert on every token, times a weight that is zero outside the chosen", one
expert at a time.  It imports nothing of the program but the model
description it is handed.

**What the served rows leave behind** (``_left_behind``): the conv tails the
finished rows left in their slots against the reference's ``u`` at each
row's last L - 1 positions; the conv layers AHEAD of the first expert layer
under ``agreement.tail_error_limit`` (what tells a tail kept in a lower
precision, written from a padded position or landed in another row's slot),
every layer's logged (behind an expert layer a gate's near-tie at a row's
last positions moves its tail by an expert's whole output).

**Near-ties of the gate.**  A top-k is not continuous: where the last expert
chosen leads the first one left out by less than the rounding of a bfloat16
stream, the program may rightly choose the other one.  With an
``agreement.routing_tie`` (in ``s + expert_bias``) the reference follows, for
each position it decides, every choice within the tie through all later
layers (a position's own stream alone: the earlier positions' keys, values
and conv inputs are the reference's) and accepts the served token under any
of them: the rule, its bounds and its verdict are ``benchmarks/routing_tie.py``'s,
the walk through this architecture's layers ``_admitted``.

**What the rule does not follow, and ``agreement.refused_limit``.**  A
position's conv input is a third its own and two thirds its two NEIGHBOURS':
where the engine's bfloat16 stream took another expert at position t - 1, the
conv layers behind that expert layer hand position t another ``u`` than the
reference's, and t's logits move though t's own gates stand clear of every
tie (under attention a neighbour's flip is one key in hundreds).  Following
the neighbours' routings too multiplies the streams of a position by those
of two more: tried on the chip, it explains two thirds of the refused
positions and no more, gives up at most of them for their many joint
routings, and forgives a wrong gate as readily (the configuration file's
``agreement.why`` has the readings), so it is NOT in the rule.  The file
counts the decided positions the rule REFUSES and holds the count to
``refused_limit``, set between the stated program's readings and two wrong
programs' on three seeds each (the same ``why``); within it they are
returned undecided, over it as they are, and the harness's comparison fails
on them.
"""

from __future__ import annotations

import functools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES
from benchmarks.routing_tie import bounded, decide, padded, room, routings

ATTENTION, CONV = "attention", "conv"
_ROWS_AT_ONCE = 2  # rows the reference carries through a layer together
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_VOCAB_BLOCK = 16384  # rows of the tied head upcast at once
_TOPK_EPS = 1e-6  # what lfm2_moe adds to the sum of the chosen scores
# the seeded tree (params): W_g at this gain on 1/sqrt(fan_in); the bias on the
# choice uniform in +-this; every norm's w uniform in 1 +-_NORM_RANGE
_ROUTER_GAIN = 2.0
_ROUTER_BIAS_RANGE = 0.05
_NORM_RANGE = 0.1

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok", "moe_intermediate_size": "moe_d_ff",
    "conv_L_cache": "conv_L_cache", "conv_bias": "conv_bias",
}
_AS_READ = (  # keys that select a variant: the one reading this file describes
    ("use_expert_bias", True), ("norm_topk_prob", True), ("conv_bias", False),
)


def _the_program_describes_it() -> None:
    """A program whose ``ModelConfig`` knows no ``conv`` layer cannot run this
    architecture: said when the file is LOADED, as a fault of the manifest
    (``benchmarks/run.py`` exits 4 at once), not found out later by a
    ``TypeError`` while the engine is built."""
    from benchmarks.manifest import ManifestError

    try:
        from calfkit_tpu.inference.config import CACHE_KINDS
    except ImportError:  # no program at all: run.py says so itself (exit 3)
        return
    if CONV not in CACHE_KINDS:
        raise ManifestError(
            "architecture lfm2-conv-gqa-moe: this program describes no 'conv' layer "
            "(calfkit_tpu.inference.config.CACHE_KINDS): a gated short convolution "
            "is not among its mixers")


_the_program_describes_it()


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
        ("tail_error_limit", float, 0.0),  # 0: the reading is logged, nothing is held to it
        ("refused_limit", int, 0),  # decided positions the rule may refuse (module text)
    ], bases=(ModelConfig,), frozen=True)


def kept_layers(config: dict) -> list[int]:
    """The published layers this configuration keeps, in order."""
    kept = [int(i) for i in config.get(
        "published_layers", range(config["num_hidden_layers"]))]
    if len(kept) != config["num_hidden_layers"] or kept != sorted(set(kept)):
        raise ValueError("lfm2-conv-gqa-moe: published_layers names num_hidden_layers "
                         "distinct layers in order")
    return kept


def _kinds(config: dict) -> list[str]:
    """The kept layers' kinds, in the program's names."""
    names = {"conv": CONV, "full_attention": ATTENTION}
    return [names[config["layer_types"][i]] for i in kept_layers(config)]


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RuntimeConfig

    for key, want in _AS_READ:
        if config.get(key, want) != want:
            raise ValueError(f"lfm2-conv-gqa-moe: {key} other than {want!r} is not described")
    kept = kept_layers(config)
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    sizes["first_k_dense"] = sum(i < int(config["num_dense_layers"]) for i in kept)
    agree = config["agreement"]
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["norm_eps"]), max_seq_len=runtime["max_seq_len"],
        dtype=config["precision"]["activations"],
        tie_embeddings=bool(config.get("tie_embedding", True)),
        layer_types=tuple(_kinds(config)), qk_norm=True,
        scoring_func="sigmoid", topk_method="noaux_tc", topk_norm_eps=_TOPK_EPS,
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        agreement_margin=float(agree["margin"]),
        agreement_new_tokens=int(agree["new_tokens"]),
        routing_tie=float(agree.get("routing_tie", 0.0)),
        # read on the chip at the published widths: at toy widths logged, not held
        tail_error_limit=0.0 if rehearse else float(agree.get("tail_error_limit", 0.0)),
        refused_limit=int(agree.get("refused_limit", 0)),
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

    - the embedding stays at 1/sqrt(hidden): the head is TIED, and at unit
      scale a token's own row would outscore every other (``cohere2-moe-swa.py``
      has the reading).  The first layers are convolutions, which mix three
      positions and no more, so the stream keeps what each position is
      (under attention first, every position carried the context's mean);
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in) and ``expert_bias``
      uniform in +-``_ROUTER_BIAS_RANGE`` and NOT zero, so that a program
      that leaves the bias out of the choice disagrees (it does, at the
      cell's size: the configuration file's ``agreement.why`` has the
      readings);
    - every norm's ``w`` uniform in 1 +-``_NORM_RANGE``, the heads' norms too.

    What this seeding does NOT tell at the cell's size, in bfloat16 (the same
    ``why`` has each reading, inside the stated program's own): the bias added
    into the weights (+-0.05 on scores that are then normalised) and the heads'
    norms left out (random queries and keys attend diffusely with or without
    them).  The CPU tests hold both in float32 (``tests/test_lfm2_moe.py``)."""
    if runtime.quantization is not None:
        raise ValueError(f"no initialiser for quantization {runtime.quantization!r}")
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.model import init_params
    from calfkit_tpu.inference.sharding import param_shardings

    c = model_config

    def seeded(key):
        tree = init_params(c, key)
        layers = tree["layers"]
        moe = layers["moe"]
        moe["router"] = moe["router"] * _ROUTER_GAIN
        moe["router_bias"] = jax.random.uniform(
            jax.random.fold_in(key, 1), moe["router_bias"].shape, jnp.float32,
            -_ROUTER_BIAS_RANGE, _ROUTER_BIAS_RANGE)
        norms = [(tree, "final_norm"), (moe, "mlp_norm"), (layers["dense"], "mlp_norm"),
                 (layers["conv"], "mixer_norm"),
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


def _rotate(x, positions, theta):
    """Rotary embedding over the last axis of ``x`` [T, N, d], the two
    HALVES paired; ``positions`` [T] run along axis 0."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = (positions.astype(jnp.float32)[:, None] * freqs)[:, None, :]  # [T, 1, d/2]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
         x2 * jnp.cos(angles) + x1 * jnp.sin(angles)], axis=-1)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


# ---- rotary GQA attention with normed heads
@functools.lru_cache(maxsize=None)
def _attention(theta: float, eps: float):
    import jax
    import jax.numpy as jnp

    def project(x, w, positions):
        """Tokens ``x`` [T, D] at ``positions`` [T] -> q [T, H, hd], k, v [T, K,
        hd]: the heads' norms BEFORE the rotation."""
        h = _rms(x, w["attn_norm"], eps)
        q = _rms(jnp.einsum("td,dnh->tnh", h, w["wq"]), w["q_norm"], eps)
        k = _rms(jnp.einsum("td,dkh->tkh", h, w["wk"]), w["k_norm"], eps)
        return (_rotate(q, positions, theta), _rotate(k, positions, theta),
                jnp.einsum("td,dkh->tkh", h, w["wv"]))

    @jax.jit
    def layer(x, attn, ia, lens):  # x [B, S, D] float32 -> x + attention
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, ia)
            t = jnp.arange(x.shape[1])
            K = w["wk"].shape[1]

            def row(x, n):  # one row, whole: causal over its own n tokens
                q, k, v = project(x, w, t)
                q = q.reshape(q.shape[0], K, -1, q.shape[-1])  # query head n reads KV head n // (H / K)
                scores = jnp.einsum("skgh,tkh->kgst", q, k) / math.sqrt(q.shape[-1])
                mask = (t[None, :] <= t[:, None]) & (t[None, :] < n)
                probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
                o = jnp.einsum("kgst,tkh->skgh", probs, v)
                return jnp.einsum("snh,nhd->sd", o.reshape(o.shape[0], -1, o.shape[-1]), w["wo"])

            return x + jax.vmap(row)(x, lens)

    @jax.jit
    def nodes(xn, at, x, attn, ia):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N] of ONE row whose stream is ``x`` [S, D]: each attends the
        row's EARLIER positions as the reference has them, and itself."""
        with jax.default_matmul_precision("highest"):
            w = _f32(attn, ia)
            t = jnp.arange(x.shape[0])
            K = w["wk"].shape[1]
            _, k, v = project(x, w, t)
            q, own_k, own_v = project(xn, w, at)
            q = q.reshape(q.shape[0], K, -1, q.shape[-1])
            scale = 1.0 / math.sqrt(q.shape[-1])
            earlier = jnp.einsum("pkgh,tkh->pkgt", q, k) * scale
            earlier = jnp.where(t[None, None, None, :] < at[:, None, None, None], earlier, -1e30)
            own = jnp.einsum("pkgh,pkh->pkg", q, own_k) * scale
            probs = jax.nn.softmax(jnp.concatenate([earlier, own[..., None]], axis=-1), axis=-1)
            o = (jnp.einsum("pkgt,tkh->pkgh", probs[..., :-1], v)
                 + probs[..., -1:] * own_v[:, :, None, :])
            return xn + jnp.einsum(
                "pnh,nhd->pd", o.reshape(o.shape[0], -1, o.shape[-1]), w["wo"])

    return layer, nodes


# ---- the gated short convolution
@functools.lru_cache(maxsize=None)
def _conv(taps: int, eps: float):
    import jax
    import jax.numpy as jnp

    def gated(x, w):
        """Tokens ``x`` [T, D] -> (u = B * x [T, D], C [T, D])."""
        bcx = jnp.einsum("td,ed->te", _rms(x, w["mixer_norm"], eps), w["w_in"])
        D = x.shape[-1]
        return bcx[:, :D] * bcx[:, 2 * D:], bcx[:, D:2 * D]

    @jax.jit
    def layer(x, conv, im, lens):
        """x [B, S, D] float32 -> (x + mixer, tails [B, 2, taps - 1, D]: ``u``
        at the last taps - 1 of each row's first ``lens - 1`` and ``lens``
        tokens, zero before the sequence: what an engine that served the
        row's last token holds, whether or not its last dispatch went on to
        feed that token)."""
        with jax.default_matmul_precision("highest"):
            w = _f32(conv, im)

            def row(x, n):
                u, gate_c = gated(x, w)
                T = x.shape[0]
                before = jnp.pad(u, ((taps - 1, 0), (0, 0)))  # u zero before the sequence
                v = sum(before[j:j + T] * w["conv_w"][j] for j in range(taps))
                tails = jnp.stack([
                    jax.lax.dynamic_slice_in_dim(before, n - 1 + f, taps - 1, axis=0)
                    for f in range(2)])
                return (gate_c * v) @ w["w_out"], tails

            out, tails = jax.vmap(row)(x, lens)
            return x + out, tails

    @jax.jit
    def nodes(xn, at, x, conv, im):
        """The same layer for tokens ``xn`` [N, D] that stand at positions
        ``at`` [N] of ONE row whose stream is ``x`` [S, D]: each takes the
        conv's inputs the row's EARLIER positions gave, as the reference has
        them, and its own under the last tap."""
        with jax.default_matmul_precision("highest"):
            w = _f32(conv, im)
            u, _ = gated(x, w)
            own_u, gate_c = gated(xn, w)
            before = jnp.pad(u, ((taps - 1, 0), (0, 0)))
            v = own_u * w["conv_w"][taps - 1] + sum(
                before[at + j] * w["conv_w"][j] for j in range(taps - 1))
            return xn + (gate_c * v) @ w["w_out"]

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


@functools.lru_cache(maxsize=None)
def _gate(eps: float):
    import jax

    @jax.jit
    def scored(x, moe, m):
        """Expert layer ``m``'s ``s + expert_bias`` [.., E]: what its choice
        is made on."""
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(_rms(x, _index(moe["mlp_norm"], m), eps) @ _index(moe["router"], m))
        return s + _index(moe["router_bias"], m)

    return scored


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, norm: bool, scale: float, eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, moe, m, chosen=None):
        """Every expert on every token, times a weight that is zero outside
        the chosen; ONE expert's float32 copy at a time.  ``chosen`` [.., E]
        of 0 and 1 names each token's experts; without it they are the
        gate's own choice: the k largest of ``s + expert_bias``."""
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _index(moe["mlp_norm"], m), eps)
            s = jax.nn.sigmoid(h @ _index(moe["router"], m))  # [.., E]
            if chosen is None:
                _, top = jax.lax.top_k(s + _index(moe["router_bias"], m), k)
                chosen = jnp.sum(jax.nn.one_hot(top, s.shape[-1], dtype=jnp.float32), axis=-2)
            w = s * chosen  # the UNBIASED scores of the chosen
            if norm:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + _TOPK_EPS)
            w = w * scale

            def one(a, e):  # expert e of layer m, float32
                return jax.lax.dynamic_slice(
                    a, (m, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)

            def expert(acc, e):
                out = _swiglu(h, one(moe["w_gate"], e), one(moe["w_up"], e),
                              one(moe["w_down"], e))
                return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

            y, _ = jax.lax.scan(
                expert, jnp.zeros_like(x), jnp.arange(moe["w_gate"].shape[1]))
            return x + y

    return layer


def _layers(c):
    eps = float(c.norm_eps)
    attention, attn_nodes = _attention(float(c.rope_theta), eps)
    conv, conv_nodes = _conv(int(c.conv_L_cache), eps)
    experts = _expert_ffn(c.n_experts_per_tok, bool(c.norm_topk_prob),
                          float(c.routed_scaling_factor), eps)
    return attention, attn_nodes, conv, conv_nodes, experts, _dense_ffn(eps)


def _walk(params, c, tokens, lens, keep=False, upto=None):
    """The stream after the last layer (after layer ``upto - 1``, with
    ``upto``), float32, for a few rows [B, S]; with ``keep`` also every
    layer's input; and the tails every conv layer's rows leave behind,
    without the last token and with it [Lc, B, 2, taps - 1, D]."""
    import jax.numpy as jnp

    attention, _, conv, _, experts, dense = _layers(c)
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    row_lens = jnp.asarray(lens)
    inputs, tails = [], []
    ia = im = 0
    for il, kind in enumerate(c.layer_types[:upto]):  # one layer's float32 copy at a time
        if keep:
            inputs.append(x)
        if kind == ATTENTION:
            x = attention(x, layers["attn"], jnp.int32(ia), row_lens)
            ia += 1
        else:
            x, tail = conv(x, layers["conv"], jnp.int32(im), row_lens)
            tails.append(tail)
            im += 1
        if il < c.first_k_dense:
            x = dense(x, layers["dense"], jnp.int32(il))
        else:
            x = experts(x, layers["moe"], jnp.int32(il - c.first_k_dense))
    return x, inputs, jnp.stack(tails)


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
        return np.asarray(jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(jnp.float32)))


def hidden_after(params, model_config, tokens, lens, layers: int):
    """The stream after the first ``layers`` layers, BEFORE the final norm
    [B, S, D]: for the test that ties a cut in depth to the whole model."""
    import numpy as np

    x, _, _ = _walk(params, model_config, np.asarray(tokens), np.asarray(lens), upto=layers)
    return np.asarray(x)


def left_behind(params, model_config, tokens, lens):
    """tails [Lc, B, 2, taps - 1, D] of ``_walk``: for the tests that hold an
    engine's conv tails to the reference's."""
    import numpy as np

    return np.asarray(_walk(params, model_config, np.asarray(tokens), np.asarray(lens))[2])


@functools.lru_cache(maxsize=None)
def _head(eps: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, embed, v0):  # top 2 of one block of the (tied) vocabulary
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), eps)
            w = jax.lax.dynamic_slice_in_dim(embed, v0, block, axis=0).astype(jnp.float32)
            top, idx = jax.lax.top_k(jnp.einsum("bsd,vd->bsv", h, w), 2)
            return top, idx + v0

    return head


def _top2(x, params, eps):
    """(argmax, top-1 margin) of the logits of ``x`` [B, S, D]: the tied head
    a block of the vocabulary at a time, the blocks' top 2 merged."""
    import numpy as np

    embed = params["embed"]
    V = embed.shape[0]
    block = min(_VOCAB_BLOCK, V)
    head = _head(eps, block)
    tops, idxs = [], []
    for v0 in sorted({min(v, V - block) for v in range(0, V, block)}):
        top, idx = head(x, params["final_norm"], embed, np.int32(v0))
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

    _, attn_nodes, _, conv_nodes, experts, dense = _layers(c)
    scored = _gate(float(c.norm_eps))
    layers = params["layers"]
    moe, k = layers["moe"], c.n_experts_per_tok
    at = np.asarray(at)
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
            x = conv_nodes(padded(x, size), padded(at[position], size), inputs[il][row],
                           layers["conv"], jnp.int32(im))
            im += 1
        if il < c.first_k_dense:
            x = np.asarray(dense(x, layers["dense"], jnp.int32(il)))[:n]
            continue
        m = jnp.int32(il - c.first_k_dense)
        parent, chosen, position, first = bounded(
            position, first, given_up,
            *routings(np.asarray(scored(x, moe, m))[:n], k, tie))
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
def _tail_errors():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errors(held, ref):
        """``held`` [Lc, taps - 1, slots, D], the engine's; ``ref`` [Lc, taps -
        1, D], one row's -> (the slot whose FIRST layer's tail is nearest the
        row's, that slot's distance over the row's norm, layer by layer)."""
        first = held[0].astype(jnp.float32)
        slot = jnp.argmin(jnp.sum(jnp.square(first - ref[0][:, None]), axis=(0, 2)))
        mine = jax.lax.dynamic_index_in_dim(held, slot, 2, keepdims=False).astype(jnp.float32)
        far = jnp.sqrt(jnp.sum(jnp.square(mine - ref), axis=(1, 2)))
        return slot, far / jnp.sqrt(jnp.sum(jnp.square(ref), axis=(1, 2)))

    return errors


def tail_errors(held, tails, ahead: int | None = None) -> dict:
    """``held`` [Lc, taps - 1, slots, D], an engine's conv tails as its
    finished rows left them, against the reference's ``tails`` [Lc, B, 2,
    taps - 1, D] (``_walk``): each row is found in the slot whose first
    layer's tail is nearest, and held to the nearer of its two tails (a
    row's last served token is fed to the model only if the engine's last
    dispatch for the row ran past it).  ``tail_error``: the distance over
    the reference's norm, the rows' mean, of the worst of the first
    ``ahead`` conv layers: those AHEAD of the first expert layer, whose
    input no near-tie of a gate can have moved (behind it a row whose last
    positions met one, in a bfloat16 stream, rightly differs by the whole
    of an expert's output: those layers' readings are logged beside it,
    ``tail_error_all_layers`` their worst).  None: every layer."""
    import numpy as np

    errors = _tail_errors()
    slots, fed_last, e = [], [], []
    for r in range(tails.shape[1]):
        both = [errors(held, tails[:, r, f]) for f in range(2)]
        fed = int(float(both[1][1][0]) <= float(both[0][1][0]))
        fed_last.append(fed)
        slots.append(int(both[fed][0]))
        e.append(np.asarray(both[fed][1]))
    e = np.asarray(e)  # [rows, Lc]
    by_layer = e.mean(0)
    return dict(tail_slots=slots, rows_fed_their_last_token=int(sum(fed_last)),
                tail_error_by_layer=[round(float(v), 6) for v in by_layer],
                tail_error_worst_row=float(e.max()),
                tail_error_all_layers=float(by_layer.max()),
                tail_error=float(by_layer[:ahead or None].max()))


def _left_behind(engine, tails, ahead: int) -> dict:
    """What the engine still holds of the rows it served, against what the
    reference says they leave.  A finished row's slot keeps its tail until a
    wave lands in it.  The reading needs the engine to have served these rows
    and nothing else since it started, which is how the harness runs the
    agreement check."""
    held = engine.recurrent_state()
    return {} if held is None else tail_errors(held[1], tails, ahead)


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits.  Where an engine serves ``params``,
    also what the rows left behind in it (``_left_behind``), the reading
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
        x, inputs, tails = _walk(params, c, tokens[rows], lens[rows], keep=follow)
        behind.append(np.asarray(tails))
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
    # the positions the rule REFUSES: decided by more than the margin under every routing
    # followed, and the served token none of theirs
    refused = [(r, int(p)) for r, n in enumerate(lens)
               for p in np.arange(max(n - 1 - new, 0) if new else 0, n - 1)
               if gap[r, p] > margin and arg[r, p] != tokens[r, p + 1]]
    allowed = getattr(c, "refused_limit", 0)
    if refused and follow:
        passes = len(refused) <= allowed
        print(f"benchmarks/architectures/lfm2-conv-gqa-moe.py: {'ok  ' if passes else 'FAIL'} "
              f"decided positions whose served token no admitted routing gives (a near-tie of "
              f"a NEIGHBOUR's gate reaches a position through the three taps, and is not "
              f"followed): {len(refused)} (limit <= {allowed})", file=sys.stderr, flush=True)
        if passes:  # left undecided: the harness counts them neither way
            for r, p in refused:
                gap[r, p] = 0.0
    # the conv layers ahead of the first expert layer (the leading dense layers' own)
    ahead = sum(kind == CONV for kind in c.layer_types[:c.first_k_dense])
    readings = {} if engine is None else _left_behind(
        engine, np.concatenate(behind, axis=1), ahead)
    over = []
    limit = getattr(c, "tail_error_limit", 0.0)
    if "tail_error" in readings and limit:
        passes = readings["tail_error"] <= limit
        over += [] if passes else ["tail_error"]
        print(f"benchmarks/architectures/lfm2-conv-gqa-moe.py: "
              f"{'ok  ' if passes else 'FAIL'} the conv tails the served rows left in the conv "
              f"layers ahead of the first expert layer, distance from the reference's over its "
              f"norm, the worst layer's mean over the rows: "
              f"{readings['tail_error']:.6f} (limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    print(json.dumps({
        "phase": "reference", "architecture": "lfm2-conv-gqa-moe",
        "positions": int(lens.sum()), "routing_tie": tie, **seen, **readings,
        "refused": len(refused), "refused_limit": allowed, "over_their_limit": over,
        "served_tokens": int(sum(len(s) for s in served_all)),
        "distinct_served_tokens": len({int(t) for s in served_all for t in s}),
        "served_token_repeats_the_one_before": int(
            sum((s[1:] == s[:-1]).sum() for s in served_all)),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, H, K = (config["hidden_size"], config["num_attention_heads"],
               config["num_key_value_heads"])
    kinds = _kinds(config)
    L, La = len(kinds), kinds.count(ATTENTION)
    Ld = sum(i < config["num_dense_layers"] for i in kept_layers(config))
    hd = D // H
    taps = config["conv_L_cache"]
    E = config["num_experts"]
    return dict(
        D=D, L=L, La=La, Lc=L - La, Ld=Ld, Lm=L - Ld, H=H, K=K, hd=hd, taps=taps, E=E,
        k=config["num_experts_per_tok"], V=config["vocab_size"],
        attn=D * H * hd + 2 * D * K * hd + H * hd * D,
        conv=4 * D * D,  # W_in (D -> 3 D) and W_out
        dense=3 * D * config["intermediate_size"],
        expert=3 * D * config["moe_intermediate_size"], gate=D * E,
        # the norms, the heads' norms, the taps, the gates' biases
        small=2 * L * D + D + La * 2 * hd + (L - La) * taps * D + (L - Ld) * E,
        tail_numbers=(L - La) * (taps - 1) * D,
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: both mixers, the
    dense layers, the gates, the tied head."""
    return (s["La"] * s["attn"] + s["Lc"] * s["conv"] + s["Ld"] * s["dense"]
            + s["Lm"] * s["gate"] + s["D"] * s["V"])


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: every matrix of every kept layer,
    every expert, the tied embedding ONCE."""
    s = _sizes(config)
    numbers = _outside_experts(s) + s["Lm"] * s["E"] * s["expert"] + s["small"]
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS: K and V of every attention
    layer (the conv tail does not grow with length)."""
    s = _sizes(config)
    return 2.0 * s["La"] * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def recurrent_state_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of conv tail ``rows`` sequences hold: every conv layer's last
    ``conv_L_cache - 1`` inputs a channel, in the activations' type."""
    s = _sizes(config)
    return float(rows) * s["tail_numbers"] * WEIGHT_BYTES[config["precision"]["activations"]]


def shortconv_step(config: dict, rows: float, chips: int = 1) -> dict:
    """What the conv mixers of ONE decode step over ``rows`` rows must do:
    read every mixer's ``W_in``, taps and ``W_out`` once, read and write the
    rows' tails, and the two products (the taps and the two gates are a few
    multiply-adds a channel)."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    numbers = s["Lc"] * (s["conv"] + s["taps"] * s["D"] + s["D"])  # ... and the norm before
    flops = 2.0 * rows * s["Lc"] * (s["conv"] + (s["taps"] + 2) * s["D"])
    return {"flops": flops / chips,
            "bytes": (numbers * wb + 2.0 * recurrent_state_bytes(config, rows)) / chips}


def experts_hit(config: dict, rows: float) -> float:
    """Distinct experts a layer reads for ``rows`` tokens under EVEN routing:
    E (1 - (1 - k / E)^rows).  All 32 at 128 rows of 4 among 32."""
    s = _sizes(config)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["E"]) ** float(rows))


def expert_layer_step(config: dict, rows: float, hit: float, chips: int = 1) -> dict:
    """What ONE expert block must do in a decode step over ``rows`` rows that
    hit ``hit`` distinct experts: read those and the gate; the products of
    each row's k chosen."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    flops = 2.0 * rows * (s["k"] * s["expert"] + s["gate"])
    return {"flops": flops / chips, "bytes": (hit * s["expert"] + s["gate"]) * wb / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each:
    everything outside the experts once, the experts the step must read
    under EVEN routing, each row's conv tails read AND written, K and V of
    the rows' contexts once an attention layer."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    ctx = float(rows) * float(mean_context)
    moe = expert_layer_step(config, rows, experts_hit(config, rows))
    outside = (s["La"] * s["attn"] + s["Lc"] * s["conv"] + s["Ld"] * s["dense"]
               + s["D"] * s["V"])
    flops = (2.0 * outside * rows + s["Lm"] * moe["flops"]
             + 4.0 * s["La"] * s["H"] * s["hd"] * ctx)
    bytes_ = ((outside + s["small"]) * wb + s["Lm"] * moe["bytes"]
              + 2.0 * recurrent_state_bytes(config, rows)
              + state_bytes_per_token(config) * ctx)
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context: the matmul FLOPs of both mixers, the dense layers, the
    gates, each token's k chosen experts and the tied head, causal attention
    in the attention layers; the weights once with the experts the chunk
    hits, the rows' tails in and out, K and V written and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    per_token = (s["La"] * s["attn"] + s["Lc"] * s["conv"] + s["Ld"] * s["dense"]
                 + s["Lm"] * (s["gate"] + s["k"] * s["expert"]) + s["D"] * s["V"])
    flops = 2.0 * per_token * tokens + 4.0 * s["La"] * s["H"] * s["hd"] * attended
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["Lm"] * experts_hit(config, tokens) * s["expert"] * wb
              + 2.0 * recurrent_state_bytes(config, rows)
              + state_bytes_per_token(config) * rows * (offset + chunk))
    return {"flops": flops / chips, "bytes": bytes_ / chips}
