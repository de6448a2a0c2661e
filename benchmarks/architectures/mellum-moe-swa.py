"""Architecture ``mellum-moe-swa``: a Mellum 2 decoder (Mellum2-12B-A2.5B):
sliding-window attention layers beside global ones, each KIND rotating by its
own law, in a sequential RMSNorm block, softmax-routed experts with no shared
one, an untied head.

    x_0 = E[tokens]                              E and the head are TWO matrices
    layer l:  a = Attn_l(RMS(x_l; w1));  u = x_l + a;  x_{l+1} = u + FFN(RMS(u; w2))
    RMS(x; w) = w * x / sqrt(mean x^2 + eps)     float32; multiplies by w, not 1 + w
    Attn:  q = h W_q (H heads x hd), k = h W_k, v = h W_v (K heads x hd); no bias,
           no q/k norm; q, k rotated by the layer KIND's law in HALVES (pair i =
           (x_i, x_{i+hd/2})); scores q k^T / sqrt(hd); o = concat(heads) W_o
      "sliding_attention" (l % 4 != 3): inv_freq_i = theta^(-2i/hd); cos, sin
           unscaled; query p sees key j iff p - W < j <= p
      "full_attention" (l % 4 == 3): YaRN (transformers' _compute_yarn_parameters):
           d(r) = hd ln(original / (2 pi r)) / (2 ln theta)
           low = floor d(beta_fast), high = ceil d(beta_slow), kept in [0, hd - 1]
           ramp_i = clip((i - low) / (high - low), 0, 1), i = 0 .. hd/2 - 1
           inv_freq_i = (1 - ramp_i) theta^(-2i/hd) + ramp_i theta^(-2i/hd) / factor
           cos and sin BOTH times attention_factor (0.1 ln factor + 1 where the
           file gives none), on q and k; every key j <= p
    FFN:   p = softmax(float32(h) W_g) over ALL the experts scored; the k largest
           chosen; w_e = p_e / sum of the chosen p (norm_topk_prob);
           FFN(h) = sum over the chosen e HELD here of w_e SwiGLU_e(h); no shared
           expert, no bias
    logits = RMS(x_L; w_f) W_head

Departures: none from the equations above.  What the published config does
not say is written under ``assumed`` in the configuration file (no q/k norm,
no bias, the rotation in halves, the tensor names, the MTP head not served).

**Experts held by share.**  As ``cohere2-moe-swa.py`` has it: the gate scores
every expert (``published.num_experts`` where the file cuts them, else
``num_experts``), this chip computes those of a token's chosen that it holds
from ``expert_first`` on, and the weights are NOT renormalised over the held.
The cell's file holds ALL 64 (no ``published.num_experts``); the share test
(``tests/test_cohere2_moe.py``) holds the toy by halves.

The reference's weights are the tree the engine serves, upcast to float32
ONE LAYER, and within an expert block ONE EXPERT, at a time, ONE row at a
time, the attention a block of queries and the head a block of the
vocabulary at a time, so that 12k positions fit beside the engine.  It
imports nothing of the program but the model description it is handed; the
YaRN frequencies are transcribed from the equations above (``_law``), not
taken from ``model.py``.

**What the served rows leave behind.**  As ``cohere2-moe-swa.py``:
``forward_top2`` also reads what the rows LEFT in the engine that serves the
tree it is handed, each reading held to a limit of the file's ``agreement``:
the tokens each held expert of each layer was sent
(``InferenceEngine.moe_expert_counts()``) against the reference's routing of
the same tokens, and the keys in the pages themselves, a window layer's as
the row's RING of pages holds them (``window_ring()``), a global layer's as
its pages do (``global_keys()``).  A rotated key carries its law, so the keys
in the global layers' pages tell a wrong law directly (the plain rotation
there, YaRN without its ``attention_factor``), and a window layer's tell
YaRN where the plain law belongs.

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
_VOCAB_BLOCK = 16384  # columns of the head upcast at once
_NEWEST_LEFT_OUT = 16  # a row's newest tokens, which the engine's last dispatch may not have fed
_DECIDED_AT_LEAST = 8  # decided positions a row has to have of a kind for that reading to count
# the seeded tree (params): W_g at this gain on 1/sqrt(fan_in) (the gate's logits
# spread, so the eighth expert leads the ninth by more than the bfloat16 stream's
# rounding of a logit); every norm's w uniform in +-_NORM_RANGE around 1; W_o at
# _ATTN_OUT_GAIN on 1/sqrt(fan_in)
_ROUTER_GAIN = 3.0
_NORM_RANGE = 0.1
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
    "moe_intermediate_size": "moe_d_ff", "head_dim": "attn_head_dim",
    "sliding_window": "sliding_window", "num_experts": "n_routed_experts",
    "num_experts_per_tok": "n_experts_per_tok",
}


def _the_program_describes_it() -> None:
    """A program whose description knows no rotation by layer kind
    (``config.RopeScaling``) cannot run this architecture: said when the file
    is LOADED, as a fault of the manifest (``benchmarks/run.py`` exits 4 at
    once), not found out later by an ``ImportError`` while the engine is built."""
    from benchmarks.manifest import ManifestError

    try:
        from calfkit_tpu.inference import config
    except ImportError:  # no program at all: run.py says so itself (exit 3)
        return
    if not hasattr(config, "RopeScaling"):
        raise ManifestError(
            "architecture mellum-moe-swa: this program describes no rotation by layer kind "
            "(calfkit_tpu.inference.config.RopeScaling): a window stack's global layers "
            "cannot take YaRN beside the window layers' plain law")


_the_program_describes_it()


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
    names = {"sliding_attention": WINDOW, "full_attention": GLOBAL}
    return [names[t] for t in config["layer_types"][: config["num_hidden_layers"]]]


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import RopeScaling, RuntimeConfig

    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("use_sliding_window", True),
                      ("max_window_layers", 0)):
        if config.get(key, want) != want:
            raise ValueError(f"mellum-moe-swa: {key} other than {want!r} is not described")
    if set(config["mlp_layer_types"][: config["num_hidden_layers"]]) != {"sparse"}:
        raise ValueError("mellum-moe-swa: a layer whose FFN is not sparse is not described")
    rope = config["rope_parameters"]
    plain, scaled = dict(rope["sliding_attention"]), dict(rope["full_attention"])
    if plain.pop("rope_type") != "default" or plain.pop("rope_theta") != scaled.pop("rope_theta"):
        raise ValueError("mellum-moe-swa: the window layers rotate by the plain law, and "
                         "both kinds from ONE rope_theta")
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    sizes["n_experts_total"] = config.get("published", {}).get("num_experts", 0)
    sizes["expert_first"] = int(config.get("expert_first", 0))
    agree = config["agreement"]
    kinds = layer_kinds(config)
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
        kinds = (kinds * sizes["n_layers"])[: sizes["n_layers"]]
        scaled["original_max_position_embeddings"] //= config["rehearsal"]["scale"]
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    described = _described()(
        name=config["name"], rope_theta=float(rope["sliding_attention"]["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), d_ff=sizes["moe_d_ff"],
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        tie_embeddings=False, layer_types=tuple(kinds),
        position_embedding="rope", rope_scaling_global=RopeScaling(**scaled),
        norm="rms", parallel_block=False, scoring_func="softmax", topk_method="greedy",
        norm_topk_prob=bool(config["norm_topk_prob"]),
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
    1/sqrt(fan_in) and every norm at 1; seeded HERE, as
    ``cohere2-moe-swa.py`` seeds its tree and for its reasons:

    - the embedding at UNIT scale, as the untied expert files have it (a
      lookup's fan-in is the one row it reads): the head is a matrix of its
      own at 1/sqrt(hidden), so a token's own row does not outscore the
      others and the layers decide the logits;
    - ``W_o`` at ``_ATTN_OUT_GAIN`` / sqrt(fan_in): random queries and keys
      attend diffusely, so an attention layer's update is an average of
      many values and small beside the FFN's; at twice the plain scale a
      wrong mask or a wrong rotation moves more of the stream;
    - the gate ``W_g`` at ``_ROUTER_GAIN`` / sqrt(fan_in), so that its logits
      spread and the top eight are no coin toss;
    - every RMSNorm's ``w`` (both of a layer and the final one) uniform in
      +-``_NORM_RANGE`` around 1."""
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
        layers["attn"]["wo"] = layers["attn"]["wo"] * _ATTN_OUT_GAIN
        norms = [(tree, "final_norm"), (layers["attn"], "attn_norm"), (layers["moe"], "mlp_norm")]
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

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _index(a, i):
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32)


def yarn_range(hd: int, theta: float, original: int, beta_fast: float, beta_slow: float):
    """``(low, high)``: the pairs between which YaRN's ramp runs."""
    def d(r):
        return hd * math.log(original / (2 * math.pi * r)) / (2 * math.log(theta))

    return max(math.floor(d(beta_fast)), 0), min(math.ceil(d(beta_slow)), hd - 1)


def _law(c, kind: str) -> tuple:
    """The rotation law of a layer KIND as a hashable ``(hd, theta, yarn)``:
    ``yarn`` None for the plain law, else ``(factor, original, beta_fast,
    beta_slow, attention_factor)`` read from the description handed in."""
    s = getattr(c, "rope_scaling_global", None)
    if kind == WINDOW or s is None or s.rope_type == "default":
        return c.head_dim, float(c.rope_theta), None
    scale = s.attention_factor if s.attention_factor is not None else 0.1 * math.log(s.factor) + 1
    return c.head_dim, float(c.rope_theta), (
        float(s.factor), int(s.original_max_position_embeddings), float(s.beta_fast),
        float(s.beta_slow), float(scale))


def frequencies(law: tuple):
    """``(inv_freq [hd/2] float32, what cos and sin are both multiplied by)``
    of a law, transcribed from the equations at the head of the file and
    reckoned on the HOST in float64, rounded once to float32: ``theta^x``
    taken in float32 as ``exp(x ln theta)`` carries the rounding of an
    exponent near 13, a millionth of a frequency, which at 12,000 positions
    is a hundredth of a radian on the fastest pairs (PERF.md section 6, PR
    47): the reference holds the law itself."""
    import numpy as np

    hd, theta, yarn = law
    i = np.arange(hd // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / hd)
    if yarn is None:
        return plain.astype(np.float32), 1.0
    factor, original, beta_fast, beta_slow, scale = yarn
    low, high = yarn_range(hd, theta, original, beta_fast, beta_slow)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((1.0 - ramp) * plain + ramp * plain / factor).astype(np.float32), scale


def _rotate_halves(x, positions, law: tuple):
    """A kind's rotation of x [S, heads, hd] at ``positions`` [S]: pair i is
    ``(x_i, x_{i + hd/2})`` (rotate_half), cos and sin both times the scale."""
    import jax.numpy as jnp

    inv_freq, scale = frequencies(law)
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # [S, hd/2]
    cos, sin = jnp.cos(angles)[:, None, :] * scale, jnp.sin(angles)[:, None, :] * scale
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


@functools.lru_cache(maxsize=None)
def _attention(kind: str, H: int, K: int, window: int, law: tuple, block: int):
    import jax
    import jax.numpy as jnp

    hd = law[0]

    @jax.jit
    def layer(h, attn, i):
        """``Attn_l(h)`` of ONE row [S, D]: a block of queries at a time
        against every key; each kind rotates by its law, a window layer
        takes the lower bound."""
        with jax.default_matmul_precision("highest"):
            S = h.shape[0]
            pos = jnp.arange(S)
            wq, wk, wv, wo = (_index(attn[n], i) for n in ("wq", "wk", "wv", "wo"))
            k = _rotate_halves(jnp.einsum("sd,dkh->skh", h, wk), pos, law)
            v = jnp.einsum("sd,dkh->skh", h, wv)

            def queries(s0):
                hq = jax.lax.dynamic_slice_in_dim(h, s0, block, axis=0)
                qpos = s0 + jnp.arange(block)
                q = _rotate_halves(jnp.einsum("sd,dnh->snh", hq, wq), qpos, law)
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
def _gate():
    import jax

    @jax.jit
    def logits(h, moe, m):
        """The gate's float32 logits [.., E scored] of layer ``m``."""
        with jax.default_matmul_precision("highest"):
            return h @ _index(moe["router"], m)

    return logits


@functools.lru_cache(maxsize=None)
def _expert_ffn(k: int, norm: bool, first: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(h, moe, m):
        """``FFN(h)``: every HELD expert on every token, times a weight that
        is zero outside the chosen; ONE expert's float32 copy at a time."""
        with jax.default_matmul_precision("highest"):
            p = jax.nn.softmax(h @ _index(moe["router"], m), axis=-1)  # [.., E scored]
            E, held = p.shape[-1], moe["w_gate"].shape[1]
            _, top = jax.lax.top_k(p, k)
            w = p * jnp.sum(jax.nn.one_hot(top, E, dtype=jnp.float32), axis=-2)
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
            return y

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
        the last one inside leads by less, is HELD here.  A softmax keeps the
        logits' order, so the choice and the tie are read off the logits."""
        ranked, order = jax.lax.top_k(logits, logits.shape[-1])
        chosen = jax.nn.one_hot(order[:, :k], logits.shape[-1], dtype=jnp.int32).sum(-2)
        fed = jnp.arange(logits.shape[0])[None, :] < (n - 1 + jnp.arange(2))[:, None]
        here = (order >= first) & (order < first + held)
        doubt = jnp.concatenate([ranked[:, :k] - ranked[:, k:k + 1] < tie,
                                 ranked[:, k - 1:k] - ranked[:, k:] < tie], axis=-1)
        return fed.astype(jnp.int32) @ chosen[:, first:first + held], jnp.any(doubt & here, -1)

    return counts


@functools.lru_cache(maxsize=None)
def _keys(law: tuple):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def keys(h, attn, i):
        """Layer ``i``'s keys of one row as its pages hold them, float32 [S,
        K, hd]: rotated by the kind's law, in halves as the tree has them."""
        with jax.default_matmul_precision("highest"):
            k = jnp.einsum("sd,dkh->skh", h, _index(attn["wk"], i))
            return _rotate_halves(k, jnp.arange(k.shape[0]), law)

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
    experts = _expert_ffn(c.n_experts_per_tok, bool(c.norm_topk_prob), c.expert_first)
    S = len(tokens)
    block = math.gcd(S, _QUERY_BLOCK)
    x = params["embed"][tokens].astype(jnp.float32)
    sent, kept, decided = [], [], np.ones((S,), bool)
    for il, kind in enumerate(c.layer_types):  # one layer's float32 copy at a time
        law = _law(c, kind)
        attention = _attention(kind, c.n_heads, c.n_kv_heads, c.sliding_window, law, block)
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _index(layers["attn"]["attn_norm"], il), eps)
        if left:
            first = max(n - c.sliding_window, 0) if kind == WINDOW else 0
            k = _keys(law)(h, layers["attn"], jnp.int32(il))
            kept.append((first, np.asarray(k[first:n]), decided[first:n]))
        x = x + attention(h, layers["attn"], jnp.int32(il))
        with jax.default_matmul_precision("highest"):
            h = _rms(x, _index(layers["moe"]["mlp_norm"], il), eps)
        if left:
            to, tied = _sent(c.n_experts_per_tok, c.expert_first, c.n_routed_experts,
                             float(getattr(c, "agreement_routing_tie", 0.0)))(
                _gate()(h, layers["moe"], jnp.int32(il)), jnp.int32(n))
            sent.append(to)
            decided = decided & ~np.asarray(tied)
        x = x + experts(h, layers["moe"], jnp.int32(il))
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
            h = _rms(x, params["final_norm"].astype(jnp.float32), float(c.norm_eps))
            out.append(np.asarray(h @ params["lm_head"].astype(jnp.float32)))
    return np.stack(out)


@functools.lru_cache(maxsize=None)
def _head(eps: float, block: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, lm_head, v0):  # top 2 of one block of the vocabulary
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), eps)
            w = jax.lax.dynamic_slice_in_dim(lm_head, v0, block, axis=1).astype(jnp.float32)
            top, idx = jax.lax.top_k(h @ w, 2)
            return top, idx + v0

    return head


def _top2(x, params, eps):
    """(argmax, top-1 margin) of the logits of ``x`` [S, D]: the head a
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
                print(f"benchmarks/architectures/mellum-moe-swa.py: "
                      f"{'ok  ' if passes else 'FAIL'} {what}: {readings[name]:.6f} "
                      f"(limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    spans = [(max(int(n) - new, 1), int(n)) for n in lens] if new else []
    print(json.dumps({
        "phase": "reference", "architecture": "mellum-moe-swa",
        "positions": int(lens.sum()), **readings, "over_their_limit": over,
        # a row that serves one token again and again tells little: what the margin
        # rule cannot show by itself
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
    Fe, V, E = config["moe_intermediate_size"], config["vocab_size"], config["num_experts"]
    kinds = layer_kinds(config)
    scored = config.get("published", {}).get("num_experts", E)
    return dict(
        D=D, L=L, Lw=kinds.count(WINDOW), Lg=kinds.count(GLOBAL), H=H, K=K, hd=hd, V=V, E=E,
        W=config["sliding_window"], k=config["num_experts_per_tok"], scored=scored,
        attn=2 * D * H * hd + 2 * D * K * hd, expert=3 * D * Fe,
        gate=D * scored, small=2 * L * D + D,  # two norms a layer, the final one
    )


def _outside_experts(s: dict) -> float:
    """Matmul parameters a step reads whatever the routing: the attention,
    the gates, the head (the embedding is a lookup of the rows' tokens)."""
    return s["L"] * (s["attn"] + s["gate"]) + s["D"] * s["V"]


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: every matrix of every layer, the
    routed experts held here, the embedding AND the untied head."""
    s = _sizes(config)
    numbers = (_outside_experts(s) + s["D"] * s["V"] + s["L"] * s["E"] * s["expert"]
               + s["small"])
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
    ``rows`` rows that hit ``hit`` distinct held experts: read those and the
    gate; the products of a row's share of its chosen (k x held / scored of
    them lie here).  No shared expert."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    numbers = hit * s["expert"] + s["gate"]
    here = s["k"] * s["E"] / s["scored"]
    flops = 2.0 * rows * (here * s["expert"] + s["gate"])
    return {"flops": flops / chips, "bytes": numbers * wb / chips}


def _attended(config: dict, tokens: float, chips: int) -> dict:
    """Keys and values of ``tokens`` (a sum over rows, steps and layers) read
    once each, scored and weighed for every query head."""
    s = _sizes(config)
    return {"flops": 4.0 * s["H"] * s["hd"] * tokens / chips,
            "bytes": _kv_bytes(config) * tokens / chips}


def window_layers_step(config: dict, rows: float, window_tokens: float, chips: int = 1) -> dict:
    """What the WINDOW layers' attention cores must do in decode steps whose
    rows attend ``window_tokens`` keys in all, summed over rows, steps AND
    window layers (the engine's ``decode_window_tokens_read``: rows x
    min(len, W) x window layers a step).  The same work whatever implements
    it; ``rows`` plays no part (a row's query is small beside its keys)."""
    return _attended(config, window_tokens, chips)


def global_layers_step(config: dict, rows: float, global_tokens: float, chips: int = 1) -> dict:
    """What the GLOBAL layers' attention cores must do in decode steps whose
    rows attend ``global_tokens`` keys in all, summed over rows, steps AND
    global layers (the engine's ``decode_global_tokens_read``: rows x len x
    global layers a step).  The same work whatever implements it."""
    return _attended(config, global_tokens, chips)


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
    gates and the tokens' share of their chosen experts, the head for the
    rows' LAST positions only (what a served prompt needs); causal attention
    over the context in the global layers and over ``min(.., W)`` keys a query
    in the window layers; the weights outside the embedding once with the held
    experts the chunk hits, the K and V written and attended."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    mean_seen = offset + (chunk + 1) / 2.0  # keys a query sees, causal
    attended = tokens * (s["Lg"] * mean_seen + s["Lw"] * min(mean_seen, s["W"]))
    here = s["k"] * s["E"] / s["scored"]
    per_token = s["L"] * (s["attn"] + s["gate"] + here * s["expert"])
    flops = (2.0 * per_token * tokens + 2.0 * s["D"] * s["V"] * rows
             + 4.0 * s["H"] * s["hd"] * attended)
    read = rows * (s["Lg"] * (offset + chunk) + s["Lw"] * min(offset + chunk, s["W"] + chunk))
    bytes_ = ((_outside_experts(s) + s["small"]) * wb
              + s["L"] * experts_hit(config, tokens) * s["expert"] * wb
              + _kv_bytes(config) * read)
    return {"flops": flops / chips, "bytes": bytes_ / chips}
