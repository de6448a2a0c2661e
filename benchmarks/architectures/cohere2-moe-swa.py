"""Architecture ``cohere2-moe-swa``: a Cohere2-MoE decoder (command-a-plus):
sliding-window attention layers beside global layers WITHOUT positions, a
parallel block, sigmoid-routed experts held by SHARE with shared experts
averaged.

    x_0 = E[tokens]                                   no multiplier; E tied to the head
    layer l:  h = LN(x_l);  x_{l+1} = x_l + Attn_l(h) + FFN(h)       ONE norm, one add
    LN(x) = w * (x - mean x) / sqrt(var x + eps)      float32; a weight, NO bias
    Attn:  q = h W_q (H heads x hd), k = h W_k, v = h W_v (K heads x hd); no bias,
           no q/k norm; scores q k^T / sqrt(hd); o = concat(heads) W_o
      "sliding_attention" (l % 4 != 3): rotary over the WHOLE head, interleaved
           pairs (2i, 2i+1), on q and k; query i sees key j iff i - W < j <= i
      "full_attention" (l % 4 == 3): NO positional embedding; every j <= i
    FFN:   s = sigmoid(h W_g) over ALL the experts scored (float32); the k largest
           s chosen; w_e = s_e / sum of the chosen s (norm_topk_prob);
           routed = sum over the chosen e HELD here of w_e SwiGLU_e(h);
           shared = (1 / n) sum over the n shared experts of SwiGLU_s(h);
           FFN(h) = routed + shared
    logits = LN_f(x_L) E^T * logit_scale

**Experts held by share.**  The gate scores every expert of the layer
(``published.num_experts``) and a token's k are chosen among all of them;
this chip holds ``num_experts`` of them from ``expert_first`` on, computes
those of a token's chosen that it holds, and leaves out what an absent
expert would add: the other shares' chips add theirs, the weights are NOT
renormalised over the held, and nothing stands in for the absent chips.
The same holds for the vocabulary (``vocab_first``: rows of the tied
embedding; traffic, logits and sampling over the slice).

**The tree's column order.**  The program's tree holds the q and k columns
of a head in HALVES (``[x_0 .. x_{hd/2-1} | y_0 .. y_{hd/2-1}]``, pair i =
``(x_i, y_i)``: the order its rotation reads), where the published
checkpoint interleaves them (``(2i, 2i+1)``); the loader permutes once, which
changes no product.  The reference puts a head back into the PUBLISHED order
and rotates interleaved pairs, as published.

The reference's weights are the tree the engine serves, upcast to float32
ONE LAYER, and within an expert block ONE EXPERT, at a time, ONE row at a
time, the attention a block of queries and the head a block of the
vocabulary at a time, so that 12k positions fit beside the engine.  It
imports nothing of the program but the model description it is handed.

**What the served rows leave behind.**  The served tokens of a seeded tree
do not tell every fault (PERF.md section 6, PR 38: a row soon serves one token
again and again, which neither a window layer's lower bound nor the global
layer's position rule moves), so ``forward_top2`` also reads what the rows
LEFT in the engine that serves the tree it is handed, each reading held to a
limit of the file's ``agreement``:

- the tokens each held expert of each layer was sent
  (``InferenceEngine.moe_expert_counts()``) against the reference's routing of
  the same tokens: the FIRST layer's share that differs tells a gate taken in a
  lower precision (``gate_mismatch_limit``), a LATER layer's reads the stream
  that reached that gate, and with it every mask below it
  (``gate_mismatch_later_limit``);
- the keys in the pages themselves, every layer's, row by row in the slot that
  served the row: a window layer's as the row's RING of pages holds them
  (``InferenceEngine.window_ring()``: the last ``W`` positions, each in the
  entry its position names), the global layer's as its pages do
  (``global_keys()``: every position).  The first layer's keys read the pages'
  own type and the ring's law (``ring_error_limit``); a later layer's keys are
  a function of everything the layers below added to the stream, so a lower
  bound left out, a ring read from the wrong page or a rotation where the
  model has none each show there, at the positions a prefill wrote and at
  those a decode step wrote apart (``keys_error_later_limit``).  The WORST row
  is held: slots and pages are granted oldest-first, so every row of the
  check still stands when it is read.

Counts are what the mathematics requires of THIS chip: a window layer's
keys and values count ``min(context, W)`` tokens whatever the program reads.
"""

from __future__ import annotations

import functools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES

WINDOW, GLOBAL = "window", "attention"
_QUERY_BLOCK = 128  # queries whose scores over the whole context the reference holds at once
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_VOCAB_BLOCK = 16384  # rows of the tied head upcast at once
# the seeded tree (params): W_g at this gain on 1/sqrt(fan_in) (the gate's logits
# spread, so the eighth expert leads the ninth by more than the bfloat16
# stream's rounding of a logit); every LN weight uniform in +-_NORM_RANGE around
# 1; every embedding row around a mean of its own, _ROW_MEAN of the row's spread;
# W_o at _ATTN_OUT_GAIN on 1/sqrt(fan_in)
_NEWEST_LEFT_OUT = 16  # a row's newest tokens, which the engine's last dispatch may not have fed
_DECIDED_AT_LEAST = 8  # decided positions a row has to have of a kind for that reading to count
_ROUTER_GAIN = 3.0
_NORM_RANGE = 0.1
_ROW_MEAN = 0.5
_ATTN_OUT_GAIN = 2.0

# what the served rows left in the engine: the limit's key in ``agreement`` -> what it holds
_LIMITS = {
    "gate_mismatch_limit": "the first layer's tokens to each held expert, share that differs "
                           "from the reference's",
    "gate_mismatch_later_limit": "a later layer's tokens to each held expert, the largest share "
                                 "that differs from the reference's",
    "ring_error_limit": "the first window layer's keys in the served rows' rings, the worst "
                        "row's distance from the reference's over its norm",
    "keys_error_later_limit": "a later layer's keys in the served rows' pages, the worst row's "
                              "distance from the reference's over its norm",
}

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "moe_d_ff", "head_dim": "attn_head_dim",
    "sliding_window": "sliding_window", "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok", "num_shared_experts": "n_shared_experts",
}


# ------------------------------------------------- the program's description
@functools.lru_cache(maxsize=None)
def _described():
    """The program's description with, beside it, what ``forward_top2``
    reads of the file's ``agreement``."""
    import dataclasses

    from calfkit_tpu.inference.config import ModelConfig

    return dataclasses.make_dataclass("Described", [
        ("agreement_margin", float, 0.0),
        ("agreement_new_tokens", int, 0),
        ("agreement_routing_tie", float, 0.0),
        ("gate_mismatch_limit", float, 0.0),  # 0: the reading is logged, nothing is held to it
        ("gate_mismatch_later_limit", float, 0.0),
        ("ring_error_limit", float, 0.0),
        ("keys_error_later_limit", float, 0.0),
    ], bases=(ModelConfig,), frozen=True)


def layer_kinds(config: dict) -> list:
    """``layer_types`` of the file (HF names) as the program's kinds."""
    every = int(config["layer_switch"])
    types = config.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "sliding_attention"
        for i in range(config["num_hidden_layers"])]
    names = {"sliding_attention": WINDOW, "full_attention": GLOBAL}
    return [names[t] for t in types[: config["num_hidden_layers"]]]


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RuntimeConfig

    for key, want in (("use_parallel_block", True), ("use_qk_norm", False),
                      ("first_k_dense_replace", 0), ("rotary_pct", 1.0),
                      ("position_embedding_type", "rope_gptj"),
                      ("expert_selection_fn", "sigmoid"), ("logit_scale", 1.0),
                      ("shared_expert_combination_strategy", "average"),
                      ("rms_norm_eps", None), ("tie_word_embeddings", True)):
        if config.get(key, want) != want:
            raise ValueError(f"cohere2-moe-swa: {key} other than {want!r} is not described")
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    sizes["n_experts_total"] = config["published"].get("num_experts", config["num_experts"])
    sizes["expert_first"] = int(config.get("expert_first", 0))
    agree = config["agreement"]
    kinds = layer_kinds(config)
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
        kinds = (kinds * sizes["n_layers"])[: sizes["n_layers"]]
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["layer_norm_eps"]), d_ff=sizes["moe_d_ff"],
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        tie_embeddings=True, layer_types=tuple(kinds),
        position_embedding="rope_window", norm="layer", parallel_block=True,
        scoring_func="sigmoid", topk_method="greedy",
        norm_topk_prob=bool(config["norm_topk_prob"]), shared_expert_combine="average",
        agreement_margin=float(agree["margin"]),
        agreement_new_tokens=int(agree["new_tokens"]),
        agreement_routing_tie=float(agree.get("routing_tie", 0.0)),
        # read on the chip at the published widths: at toy widths logged, not held
        **{name: 0.0 if rehearse else float(agree.get(name, 0.0)) for name in _LIMITS},
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

    - the embedding at 1/sqrt(hidden), NOT at unit scale as the two untied
      expert files have it: the head is TIED, so a token's own row scores
      ``|E_t|^2 / rms`` against a spread of ``|E|`` for every other token,
      which is sqrt(hidden) = 64 spreads at any scale of ``E`` unless the
      layers' updates outweigh the embedding in the stream; at unit scale
      the argmax was the input token again at every position, whatever the
      layers computed (512 of 512 served positions "decided and equal" under
      EVERY control: PERF.md section 6, PR 38).  At 1/sqrt(hidden) the
      logits have unit spread and the layers decide them.  Every row around
      a NON-ZERO MEAN of its own (half the row's spread), so that an RMSNorm
      in the LayerNorm's place disagrees with the reference;
    - ``W_o`` at ``_ATTN_OUT_GAIN`` / sqrt(fan_in): random queries and keys
      attend diffusely, so an attention layer's update is an average of
      thousands of values and small beside the FFN's; at twice the plain
      scale a wrong mask or a wrong position rule moves the served tokens
      (the controls) and the FFN's controls still do;
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in), so that its logits
      spread and the top eight are no coin toss;
    - every LayerNorm's ``w`` uniform in +-``_NORM_RANGE`` around 1."""
    if runtime.quantization is not None:
        raise ValueError(f"no initialiser for quantization {runtime.quantization!r}")
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.model import init_params
    from calfkit_tpu.inference.sharding import param_shardings

    c = model_config

    def seeded(key):
        tree = init_params(c, key)
        embed = tree["embed"].astype(jnp.float32)  # at 1/sqrt(hidden), as initialised
        embed = embed + _ROW_MEAN / math.sqrt(c.d_model) * jax.random.normal(
            jax.random.fold_in(key, 99), (embed.shape[0], 1), jnp.float32)
        tree["embed"] = embed.astype(tree["embed"].dtype)
        layers = tree["layers"]
        layers["moe"]["router"] = layers["moe"]["router"] * _ROUTER_GAIN
        layers["attn"]["wo"] = layers["attn"]["wo"] * _ATTN_OUT_GAIN
        for n, (group, name) in enumerate([(tree, "final_norm"), (layers["attn"], "attn_norm")]):
            leaf = group[name]
            group[name] = (leaf.astype(jnp.float32) + jax.random.uniform(
                jax.random.fold_in(key, 100 + n), leaf.shape, jnp.float32,
                -_NORM_RANGE, _NORM_RANGE)).astype(leaf.dtype)
        return tree

    return jax.jit(seeded, out_shardings=param_shardings(c, mesh))(jax.random.key(seed))


# ---------------------------------------------------------- plain reference
def _ln(x, w, eps):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _index(a, i):
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32)


def _published_order(x):
    """A head's columns from the tree's halves ``[x_0.. | y_0..]`` back to
    the published interleaved pairs ``[x_0, y_0, x_1, y_1, ..]``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _rotate_pairs(x, positions, theta: float):
    """Rotary over the WHOLE head on interleaved pairs ``(2i, 2i+1)``
    (rope_gptj): x [S, heads, hd] in the published order, positions [S]."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[:, None].astype(jnp.float32) * freqs  # [S, hd/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


@functools.lru_cache(maxsize=None)
def _attention(kind: str, H: int, K: int, hd: int, window: int, theta: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(h, attn, i):
        """``Attn_l(h)`` of ONE row [S, D]: a block of queries at a time
        against every key; a window layer rotates and takes the lower bound."""
        with jax.default_matmul_precision("highest"):
            S = h.shape[0]
            pos = jnp.arange(S)
            wq, wk, wv, wo = (_index(attn[n], i) for n in ("wq", "wk", "wv", "wo"))
            k = jnp.einsum("sd,dkh->skh", h, wk)
            v = jnp.einsum("sd,dkh->skh", h, wv)
            if kind == WINDOW:
                k = _rotate_pairs(_published_order(k), pos, theta)

            def queries(s0):
                hq = jax.lax.dynamic_slice_in_dim(h, s0, block, axis=0)
                qpos = s0 + jnp.arange(block)
                q = jnp.einsum("sd,dnh->snh", hq, wq)
                if kind == WINDOW:
                    q = _rotate_pairs(_published_order(q), qpos, theta)
                q = q.reshape(block, K, H // K, hd)
                scores = jnp.einsum("skgh,tkh->kgst", q, k) / math.sqrt(hd)
                seen = pos[None, :] <= qpos[:, None]
                if kind == WINDOW:
                    seen = seen & (pos[None, :] > qpos[:, None] - window)
                probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
                o = jnp.einsum("kgst,tkh->skgh", probs, v).reshape(block, H, hd)
                return jnp.einsum("snh,nhd->sd", o, wo)

            out = jax.lax.map(queries, jnp.arange(0, S, block))
            return out.reshape(S, -1)

    return layer


@functools.lru_cache(maxsize=None)
def _gate(eps: float):
    import jax

    @jax.jit
    def logits(h, moe, m):
        """The gate's float32 logits [.., E scored] of layer ``m``."""
        with jax.default_matmul_precision("highest"):
            return h @ _index(moe["router"], m)

    return logits


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, norm: bool, first: int, n_shared: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(h, moe, m):
        """``FFN(h)``: every HELD expert on every token, times a weight that
        is zero outside the chosen; ONE expert's float32 copy at a time; the
        shared experts one at a time, averaged."""
        with jax.default_matmul_precision("highest"):
            s = jax.nn.sigmoid(h @ _index(moe["router"], m))  # [.., E scored]
            E, held = s.shape[-1], moe["w_gate"].shape[1]
            _, top = jax.lax.top_k(s, k)
            w = s * jnp.sum(jax.nn.one_hot(top, E, dtype=jnp.float32), axis=-2)
            if norm:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            w = w[..., first:first + held]

            def one(a, e):  # held expert e of layer m, float32
                return jax.lax.dynamic_slice(
                    a, (m, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(jnp.float32)

            def expert(acc, e):
                out = _swiglu(h, one(moe["w_gate"], e), one(moe["w_up"], e),
                              one(moe["w_down"], e))
                return acc + jnp.take(w, e, axis=-1)[..., None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
            Fe = moe["s_gate"].shape[-1] // n_shared

            def shared(acc, j):  # shared expert j: its columns of s_gate, s_up; its rows of s_down
                gate = jax.lax.dynamic_slice(
                    moe["s_gate"], (m, 0, j * Fe), (1, h.shape[-1], Fe))[0].astype(jnp.float32)
                up = jax.lax.dynamic_slice(
                    moe["s_up"], (m, 0, j * Fe), (1, h.shape[-1], Fe))[0].astype(jnp.float32)
                down = jax.lax.dynamic_slice(
                    moe["s_down"], (m, j * Fe, 0), (1, Fe, h.shape[-1]))[0].astype(jnp.float32)
                return acc + _swiglu(h, gate, up, down), None

            z, _ = jax.lax.scan(shared, jnp.zeros_like(h), jnp.arange(n_shared))
            return y + z / n_shared

    return layer


@functools.lru_cache(maxsize=None)
def _sent(k: int, first: int, held: int, tie: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def counts(logits, n):
        """Of a gate's float32 logits [S, E]: the tokens it sends to each HELD
        expert over a row's first ``n - 1`` and ``n`` tokens [2, held] (the
        last served token is fed to the model only if the engine's last
        dispatch ran past it); and the positions whose choice the bfloat16
        stream may rightly make otherwise [S]: an expert inside the top k that
        leads the first one outside by less than ``tie``, or one outside that
        the last one inside leads by less, is HELD here (a doubt among experts
        that are all held elsewhere moves this chip's sum only through the
        weights' common denominator)."""
        ranked, order = jax.lax.top_k(logits, logits.shape[-1])
        chosen = jax.nn.one_hot(order[:, :k], logits.shape[-1], dtype=jnp.int32).sum(-2)
        fed = jnp.arange(logits.shape[0])[None, :] < (n - 1 + jnp.arange(2))[:, None]
        here = (order >= first) & (order < first + held)
        doubt = jnp.concatenate([ranked[:, :k] - ranked[:, k:k + 1] < tie,
                                 ranked[:, k - 1:k] - ranked[:, k:] < tie], axis=-1)
        return fed.astype(jnp.int32) @ chosen[:, first:first + held], jnp.any(doubt & here, -1)

    return counts


@functools.lru_cache(maxsize=None)
def _keys(kind: str, theta: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def keys(h, attn, i):
        """Layer ``i``'s keys of one row as its pages hold them, float32 [S,
        K, hd] in the tree's halves: a window layer's rotated as published, a
        global layer's as they are."""
        with jax.default_matmul_precision("highest"):
            k = jnp.einsum("sd,dkh->skh", h, _index(attn["wk"], i))
            if kind != WINDOW:
                return k
            k = _rotate_pairs(_published_order(k), jnp.arange(k.shape[0]), theta)
            return jnp.concatenate([k[..., 0::2], k[..., 1::2]], axis=-1)

    return keys


def _walk(params, c, tokens, n: int, left: bool = False):
    """The stream after the last layer, float32, of ONE row [S]; with
    ``left`` also what the row should have LEFT in an engine: every layer's
    tokens to each held expert [L, 2, held], and every layer's keys of the
    positions its pages keep (a window layer's last ``W``, a global layer's
    all) as ``(first position, [positions, K, hd], decided [positions])``:
    a position is decided at a layer if no gate BELOW that layer stood within
    ``agreement.routing_tie`` of another choice among the held experts there
    (a top-k is not continuous: the served stream may rightly have chosen the
    other expert, and everything above then differs by a whole expert)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    layers, eps = params["layers"], float(c.norm_eps)
    experts = _expert_ffn(c.n_experts_per_tok, bool(c.norm_topk_prob), c.expert_first,
                          c.n_shared_experts)
    S = len(tokens)
    block = math.gcd(S, _QUERY_BLOCK)
    x = params["embed"][tokens].astype(jnp.float32)
    sent, kept, decided = [], [], np.ones((S,), bool)
    for il, kind in enumerate(c.layer_types):  # one layer's float32 copy at a time
        attention = _attention(kind, c.n_heads, c.n_kv_heads, c.head_dim,
                               c.sliding_window, float(c.rope_theta), block)
        with jax.default_matmul_precision("highest"):
            h = _ln(x, _index(layers["attn"]["attn_norm"], il), eps)
        if left:
            first = max(n - c.sliding_window, 0) if kind == WINDOW else 0
            k = _keys(kind, float(c.rope_theta))(h, layers["attn"], jnp.int32(il))
            kept.append((first, np.asarray(k[first:n]), decided[first:n]))
            to, tied = _sent(c.n_experts_per_tok, c.expert_first, c.n_routed_experts,
                             float(getattr(c, "agreement_routing_tie", 0.0)))(
                _gate(eps)(h, layers["moe"], jnp.int32(il)), jnp.int32(n))
            sent.append(to)
            decided = decided & ~np.asarray(tied)
        x = x + attention(h, layers["attn"], jnp.int32(il)) + experts(
            h, layers["moe"], jnp.int32(il))
    return x, ((jnp.stack(sent), kept) if left else None)


def forward_logits(params, model_config, tokens, lens):
    """Full forward -> float32 logits [B, S, V], held whole: for the small
    sizes of the tests, which compare logits and never tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = model_config
    out = []
    for row, n in zip(np.asarray(tokens), np.asarray(lens)):
        x, _ = _walk(params, c, row, int(n))
        with jax.default_matmul_precision("highest"):
            h = _ln(x, params["final_norm"].astype(jnp.float32), float(c.norm_eps))
            out.append(np.asarray(h @ params["embed"].astype(jnp.float32).T))
    return np.stack(out)


@functools.lru_cache(maxsize=None)
def _head(eps: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, embed, v0):  # top 2 of one block of the (tied) vocabulary
        with jax.default_matmul_precision("highest"):
            h = _ln(x, final_norm.astype(jnp.float32), eps)
            w = jax.lax.dynamic_slice_in_dim(embed, v0, block, axis=0).astype(jnp.float32)
            top, idx = jax.lax.top_k(h @ w.T, 2)
            return top, idx + v0

    return head


def _top2(x, params, eps):
    """(argmax, top-1 margin) of the logits of ``x`` [S, D]: the head a
    block of the vocabulary at a time, the blocks' top 2 merged."""
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
    rest = np.where(idx == arg[..., None], -np.inf, top)  # overlapping blocks name a token twice
    return arg, best - rest.max(axis=-1)


def _engine_of(params):
    """The engine that serves ``params``, or None: the harness hands
    ``forward_top2`` the tree and nothing else of the engine, and what a gate
    taken in lower precision changes may not show in the served tokens, so
    the check of what the served rows LEFT BEHIND finds the engine by the
    tree it holds (as ``qwen3-next-gdn-moe.py`` does)."""
    import gc

    from calfkit_tpu.inference.engine import InferenceEngine

    return next((e for e in gc.get_objects()
                 if isinstance(e, InferenceEngine) and e.params is params), None)


def _gate_mismatch(engine, sent) -> dict:
    """The engine counts the tokens each held expert of each layer was
    sent; each layer's share of them that differs from the reference's
    routing of the same tokens (sum over held experts of |engine - reference|,
    over the reference's sum): the FIRST layer's, whose gate reads the
    embedding alone, and the largest of the LATER layers', whose gates read
    what the layers below made of the stream.  A row's last served token is
    fed to the model only if the engine's last dispatch for the row ran past
    it, so the reference keeps both counts and the nearer total is taken.
    Needs the engine to have served these rows and nothing else since it
    started, which is how the harness runs the agreement check."""
    import numpy as np

    counts = engine.moe_expert_counts()
    if counts is None:
        return {}
    counts = np.asarray(counts, np.int64)
    both = [sent[:, :, f].sum(1) for f in range(2)]  # [L, held] without / with the last token
    miss = min((np.abs(counts - ref).sum(1) / np.maximum(ref.sum(1), 1) for ref in both),
               key=lambda m: m[0])
    return {"gate_mismatch_by_layer": [round(float(v), 6) for v in miss],
            "gate_mismatch": float(miss[0]),
            **({"gate_mismatch_later": float(miss[1:].max())} if len(miss) > 1 else {})}


def _far(held, want) -> float:
    import numpy as np

    return float(np.sqrt(((held - want) ** 2).sum()) / max(np.sqrt((want ** 2).sum()), 1e-30))


def _keys_error(engine, c, kept, lens, new: int) -> dict:
    """Every layer's keys as the served rows' pages still hold them, against
    the reference's (``kept``: a row's ``_walk``), distance over the
    reference's norm.  A window layer: entry ``r`` of a ring of ``T`` tokens
    holds the newest position ``p = r`` (mod ``T``) written, so the last ``W``
    positions of a row must lie each in the entry its position names; the
    global layer: position ``p`` at ``p``.  A row's ``_NEWEST_LEFT_OUT``
    newest tokens are left out (the engine's last dispatch may or may not
    have fed them).  A row is read in the slot whose first ring is nearest to
    it: slots and pages are granted oldest-first, so the rows of one check all
    stand (a row whose slot or pages were taken again reads near sqrt(2), and
    fails).  ``ring_error`` is the first layer's, the WORST row's: bfloat16
    pages fed by a bfloat16 stream read the stream's rounding, pages in a
    narrower type add their own, a key in another entry than its position
    names reads near 1.  ``keys_error_later`` is the worst of the later
    layers' and rows', the positions a prefill wrote (below ``len - new``)
    and those a decode step wrote apart: a later layer's keys carry what every
    layer below added to the stream.  There only the positions DECIDED at
    that layer count (``_walk``: no gate below within the routing tie; one
    expert the other way is a fifth of such a key, and under a row that serves
    one token again and again it would be every decoded position's at once),
    and a row with fewer than ``_DECIDED_AT_LEAST`` of a kind has no reading
    of that kind."""
    import numpy as np

    if c.layer_types[0] != WINDOW or engine.window_ring(0) is None:
        return {}
    of_kind = {WINDOW: 0, GLOBAL: 0}
    slots, rows, counted = None, [], [0, 0]  # rows: [layer][row] -> (whole, prefill-, decode-written)
    for il, kind in enumerate(c.layer_types):
        ik = of_kind[kind]
        of_kind[kind] += 1
        ring = np.asarray(engine.window_ring(ik), np.float32) if kind == WINDOW else None
        if slots is None:  # the slot that served each row: where the first ring is nearest
            slots = []
            for (first, want, _), n in zip(kept[il], lens):
                at = np.arange(first, max(int(n) - _NEWEST_LEFT_OUT, first + 1))
                held = ring[:, :, at % ring.shape[2]].transpose(0, 2, 1, 3)
                far = ((held - want[None, : len(at)]) ** 2).sum((1, 2, 3))
                slots.append(int(far.argmin()))
        errors = []
        for (first, want, decided), n, slot in zip(kept[il], lens, slots):
            n = int(n)
            at = np.arange(first, max(n - _NEWEST_LEFT_OUT, first + 1))
            if kind == WINDOW:
                held = ring[slot][:, at % ring.shape[2]]
            else:
                held = np.asarray(engine.global_keys(slot, ik), np.float32)[:, at]
            held, want, decided = held.transpose(1, 0, 2), want[: len(at)], decided[: len(at)]
            fed = at < n - new  # the positions a prefill wrote
            counted[0] += int(decided.sum())
            counted[1] += len(at)
            errors.append((_far(held, want), *(
                _far(held[kind_of & decided], want[kind_of & decided])
                if (kind_of & decided).sum() >= _DECIDED_AT_LEAST else 0.0
                for kind_of in (fed, ~fed))))
        rows.append(errors)
    worst = np.asarray(rows).max(1)  # [layer, (whole, prefill-written, decode-written)]
    return {"ring_error_by_row": [round(e[0], 6) for e in rows[0]],
            "ring_error": float(worst[0, 0]),
            "keys_error_by_layer": [[round(float(v), 6) for v in layer[1:]] for layer in worst],
            **({"keys_error_later": float(worst[1:, 1:].max())} if len(rows) > 1 else {}),
            "keys_positions_decided": round(counted[0] / max(counted[1], 1), 4),
            "slots": slots}


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits, a row at a time over the row's own
    length.  Where an engine serves ``params``, also what the rows left
    behind in it (``_gate_mismatch``, ``_keys_error``), each reading beside
    its limit on stderr; a reading over its limit is returned as ONE decided
    position that no token satisfies, so that the harness's own comparison
    reads it."""
    import sys

    import numpy as np

    c = model_config
    engine = _engine_of(params)
    new = getattr(c, "agreement_new_tokens", 0)
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    arg = np.zeros(tokens.shape, np.int64)
    gap = np.zeros(tokens.shape, np.float32)
    sent, kept = [], []
    for r, n in enumerate(lens):
        n = int(n)
        width = -(-n // _QUERY_BLOCK) * _QUERY_BLOCK  # whole query blocks; causal: padding is unseen
        row = np.zeros((width,), tokens.dtype)
        row[:n] = tokens[r, :n]
        x, left = _walk(params, c, row, n, left=engine is not None)
        if left is not None:
            sent.append(left[0])
            kept.append(left[1])
        for s0 in range(0, n, _HEAD_BLOCK):
            a, g = _top2(x[s0:s0 + _HEAD_BLOCK], params, float(c.norm_eps))
            stop = min(s0 + _HEAD_BLOCK, n)
            arg[r, s0:stop], gap[r, s0:stop] = a[: stop - s0], g[: stop - s0]
    readings, over = {}, []
    if engine is not None:
        readings = {
            **_gate_mismatch(engine, np.stack([np.asarray(s, np.int64) for s in sent], axis=1)),
            **_keys_error(engine, c, [list(layer) for layer in zip(*kept)], lens, new)}
        for key, what in _LIMITS.items():
            name, limit = key[: -len("_limit")], getattr(c, key, 0.0)
            if name in readings and limit:
                passes = readings[name] <= limit
                over += [] if passes else [name]
                print(f"benchmarks/architectures/cohere2-moe-swa.py: "
                      f"{'ok  ' if passes else 'FAIL'} {what}: {readings[name]:.6f} "
                      f"(limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    spans = [(max(int(n) - new, 1), int(n)) for n in lens] if new else []
    print(json.dumps({
        "phase": "reference", "architecture": "cohere2-moe-swa",
        "positions": int(lens.sum()), **readings, "over_their_limit": over,
        # a tied head under a loud embedding serves the input token again, whatever
        # the layers compute: what the margin rule cannot show by itself
        "served_tokens": sum(b - a for a, b in spans),
        "distinct_served_tokens": len(
            {int(t) for r, (a, b) in enumerate(spans) for t in tokens[r, a:b]}),
        "served_token_repeats_the_one_before": int(
            sum((tokens[r, a:b] == tokens[r, a - 1:b - 1]).sum() for r, (a, b) in enumerate(spans))),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, K, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                config["head_dim"])
    Fe, V, E = config["intermediate_size"], config["vocab_size"], config["num_experts"]
    kinds = layer_kinds(config)
    return dict(
        D=D, L=L, Lw=kinds.count(WINDOW), Lg=kinds.count(GLOBAL), H=H, K=K, hd=hd, V=V, E=E,
        W=config["sliding_window"], k=config["num_experts_per_tok"],
        scored=config.get("published", {}).get("num_experts", E),
        attn=2 * D * H * hd + 2 * D * K * hd, expert=3 * D * Fe,
        shared=config["num_shared_experts"] * 3 * D * Fe,
        gate=D * config.get("published", {}).get("num_experts", E), small=L * D + D,
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: the attention,
    the gates, the shared experts, the tied head's slice."""
    return s["L"] * (s["attn"] + s["gate"] + s["shared"]) + s["D"] * s["V"]


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: every matrix of every layer, the
    routed experts held here, the tied embedding's slice (once)."""
    s = _sizes(config)
    numbers = _outside_experts(s) + s["L"] * s["E"] * s["expert"] + s["small"]
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def _kv_bytes(config: dict) -> float:
    """Bytes of K and V one token leaves in ONE layer."""
    s = _sizes(config)
    return 2.0 * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS for good: K and V of the global
    layers (a window layer's ring does not grow past its window)."""
    return _sizes(config)["Lg"] * _kv_bytes(config)


def experts_hit(config: dict, rows: float) -> float:
    """Distinct HELD experts a layer reads for ``rows`` tokens under EVEN
    routing over all the experts scored: held (1 - (1 - k / scored)^rows)."""
    s = _sizes(config)
    return s["E"] * (1.0 - (1.0 - s["k"] / s["scored"]) ** float(rows))


def expert_layer_step(config: dict, rows: float, hit: float, chips: int = 1) -> dict:
    """What ONE expert block must do on THIS chip in a decode step over
    ``rows`` rows that hit ``hit`` distinct held experts: read those, the
    shared experts and the gate; the products of a row's share of its chosen
    (k x held / scored of them lie here) and of the shared experts."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    numbers = hit * s["expert"] + s["shared"] + s["gate"]
    here = s["k"] * s["E"] / s["scored"]
    flops = 2.0 * rows * (here * s["expert"] + s["shared"] + s["gate"])
    return {"flops": flops / chips, "bytes": numbers * wb / chips}


def window_layers_step(config: dict, rows: float, window_tokens: float, chips: int = 1) -> dict:
    """What the WINDOW layers' attention cores must do in decode steps whose
    rows attend ``window_tokens`` keys in all, summed over rows, steps AND
    window layers (the engine's ``decode_window_tokens_read``: rows x
    min(len, W) x window layers a step): read each key and value once, score
    it and weigh it for every query head.  The same work whatever implements
    it; ``rows`` plays no part (a row's query is small beside its keys)."""
    s = _sizes(config)
    return {"flops": 4.0 * s["H"] * s["hd"] * window_tokens / chips,
            "bytes": _kv_bytes(config) * window_tokens / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each, on
    THIS chip: everything outside the experts once, the held experts the
    step must read under EVEN routing, the K and V of the global layers over
    the context and of the window layers over ``min(context, W)``."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    attended = float(rows) * (s["Lg"] * float(mean_context)
                              + s["Lw"] * min(float(mean_context), s["W"]))
    moe = expert_layer_step(config, rows, experts_hit(config, rows))
    dense = s["L"] * s["attn"] + s["D"] * s["V"]
    flops = (2.0 * dense * rows + s["L"] * moe["flops"] + 4.0 * s["H"] * s["hd"] * attended)
    bytes_ = (dense + s["small"]) * wb + s["L"] * moe["bytes"] + _kv_bytes(config) * attended
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context, on THIS chip: the matmul FLOPs of the attention, the
    gates, the shared experts and the tokens' share of their chosen experts;
    causal attention over the context in the global layers and over
    ``min(.., W)`` keys a query in the window layers; the weights outside the
    embedding once with the held experts the chunk hits, the K and V written
    and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    mean_seen = offset + (chunk + 1) / 2.0  # keys a query sees, causal
    attended = tokens * (s["Lg"] * mean_seen + s["Lw"] * min(mean_seen, s["W"]))
    here = s["k"] * s["E"] / s["scored"]
    per_token = (s["L"] * (s["attn"] + s["gate"] + s["shared"] + here * s["expert"])
                 + s["D"] * s["V"])
    flops = 2.0 * per_token * tokens + 4.0 * s["H"] * s["hd"] * attended
    read = rows * (s["Lg"] * (offset + chunk) + s["Lw"] * min(offset + chunk, s["W"] + chunk))
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["L"] * experts_hit(config, tokens) * s["expert"] * wb
              + _kv_bytes(config) * read)
    return {"flops": flops / chips, "bytes": bytes_ / chips}
