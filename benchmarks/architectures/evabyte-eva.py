"""Architecture ``evabyte-eva``: an EvaByte decoder (``model_type: evabyte``,
``attention_class: eva``; EVA is Zheng et al., "Efficient Attention via
Control Variates", arXiv:2302.04542): a byte-level pre-norm RMSNorm + SwiGLU
stack whose every mixer attends an exact ALIGNED window beside one pooled key
and value for every chunk behind it, under one softmax.

    RMS(x; g) = x / sqrt(mean x^2 + eps) * (1 + g)               norm_add_unit_offset
    h = x + Eva(RMS(x; g1));   y = h + W_down(silu(W_gate u) * W_up u),  u = RMS(h; g2)
    the residual stream, the norms, the softmax and the logits in float32

    per head (H heads of hd, as many KV heads):  q_t, k_t, v_t = W_q u_t, W_k u_t, W_v u_t
    (the tree holds W_q, W_k, W_v as [D, H hd] and W_o as [H hd, D], head-major)
    q_t, k_t rotated at the absolute position t over the whole head, in HALVES
    (pair i = (x_i, x_{i + hd/2}), inv_freq_i = theta^(-2i/hd)); no bias
    learned, per head:  phi, mu in R^hd                            adaptive_phi, adaptive_mu_k
    chunk j = positions c j .. c j + c - 1;   window n = positions W n .. W n + W - 1
    s = hd^-0.5;  for every COMPLETE chunk j:
        a_i  = softmax over i in chunk j of ( s * (k_i . phi) )
        kk_j = sum_i a_i k_i + mu;     vv_j = sum_i a_i v_i
    for a query t, n = t // W:
        L_t = { i : W n <= i <= t }            exact, causal, the query's OWN window only
        R_t = { j : j < (W / c) n }            the chunks of every window BEFORE it
        Z_t = sum_{i in L_t} exp(s q_t . k_i) + sum_{j in R_t} exp(s q_t . kk_j)
        o_t = ( sum_{L_t} exp(s q_t . k_i) v_i + sum_{R_t} exp(s q_t . kk_j) vv_j ) / Z_t
    Eva(u)_t = W_o concat_heads(o_t)

    head: W_head in R^(P V x D), untied; logits_h = W_head[V h : V (h + 1)] RMS(y_t; gf)
          head h predicts byte t + 1 + h; the served token is drawn from h = 0

The structure (one softmax over the window's exact entries and the earlier
windows' chunk entries; a chunk becomes visible only when the query has left
its window) is EVA's as published.  THREE readings are ISSUE 54's own and
stand under ``assumed`` in the configuration file: the pooling weights
``softmax(s k . phi)``; ``mu`` ADDED to the pooled key; the head as ONE
projection of P x V rows in head-major order.

The reference's weights are the tree the engine serves, upcast to float32 a
LAYER at a time, ONE row at a time, a WINDOW at a time (the pooled keys and
values of the windows before ride along), a block of queries at a time, so
that 23k positions fit beside the engine.  Precision "highest"; no cache, no
kernel, no batching; it imports nothing of the program but the model
description it is handed, and the rotation's frequencies are reckoned here.

**What the served rows leave behind.**  ``forward_top2`` also reads what the
rows LEFT in the engine that serves the tree it is handed, each reading held
to a limit of the file's ``agreement``: a row's SUMMARY entries (the pooled
keys AND values of its complete chunks, ``InferenceEngine.global_keys``)
against ``kk`` and ``vv`` above, and its RING (``window_ring``) against the
rotated keys of its last window.  Beside each reading the line says what it
would read were the pooling softmax taken in bfloat16, its weights uniform,
or ``mu`` left out (the reference's own pooled keys against such ones).

Counts are what the mathematics requires of THIS chip: a step's query reads
the keys and values of its own window up to itself and one pooled pair a
chunk of the windows before, whatever the program reads.
"""

from __future__ import annotations

import functools
import json
import math

from benchmarks.opcount import WEIGHT_BYTES

_QUERY_BLOCK = 256  # queries whose scores the reference holds at once
_NEWEST_LEFT_OUT = 16  # a row's newest tokens, which the engine's last dispatch may not have fed
_NORM_RANGE = 0.1  # the seeded tree: every norm's g uniform in +-this around 0
_ATTN_OUT_GAIN = 2.0  # W_o at this on 1/sqrt(fan_in): attention moves more of the stream

# what the served rows left in the engine: the limit's key in ``agreement`` -> what it holds
_LIMITS = {
    "summary_error_limit": "the first layer's pooled keys and values in the served rows' "
                           "summary pages, the worst row's distance from the reference's "
                           "over its norm",
    "summary_error_later_limit": "a later layer's pooled keys and values, the worst layer "
                                 "and row",
    "ring_error_limit": "the first layer's keys in the served rows' rings (their last "
                        "window), the worst row's distance from the reference's over its norm",
}

_PUBLISHED = {  # config.json key -> ModelConfig field
    "vocab_size": "vocab_size", "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "window_size": "window_size", "chunk_size": "chunk_size",
    "num_pred_heads": "num_pred_heads",
}


def _the_program_describes_it() -> None:
    """A program whose description knows no EVA layer cannot run this
    architecture: said when the file is LOADED, as a fault of the manifest
    (``benchmarks/run.py`` exits 4 at once), not found out while the engine
    is built."""
    from benchmarks.manifest import ManifestError

    try:
        from calfkit_tpu.inference import config
    except ImportError:  # no program at all: run.py says so itself (exit 3)
        return
    if "eva" not in getattr(config, "CACHE_KINDS", {}):
        raise ManifestError(
            "architecture evabyte-eva: this program describes no EVA layer "
            "(calfkit_tpu.inference.config.CACHE_KINDS has no 'eva'): an aligned window "
            "beside pooled summaries is none of its cache kinds")


_the_program_describes_it()


# ------------------------------------------------- the program's description
@functools.lru_cache(maxsize=None)
def _described():
    """The program's description with, beside it, what ``forward_top2``
    reads of the file's ``agreement``."""
    import dataclasses

    from calfkit_tpu.inference.config import ModelConfig

    return dataclasses.make_dataclass("Described", [
        ("agreement_new_tokens", int, 0),
        *((name, float, 0.0) for name in _LIMITS),  # 0: the reading is logged, held to nothing
    ], bases=(ModelConfig,), frozen=True)


def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import EVA, RuntimeConfig

    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("attention_class", "eva"),
                      ("rope_scaling", None), ("norm_add_unit_offset", True),
                      ("fp32_skip_add", True), ("fp32_logits", True), ("mixedp_attn", True)):
        if config.get(key, want) != want:
            raise ValueError(f"evabyte-eva: {key} other than {want!r} is not described")
    runtime = dict(config["runtime"])
    sizes = {field: config[key] for key, field in _PUBLISHED.items()}
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    agree = config["agreement"]
    described = _described()(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), max_seq_len=runtime["max_seq_len"],
        dtype=config["precision"]["activations"], tie_embeddings=False,
        layer_types=(EVA,) * sizes["n_layers"], norm_plus_one=True,
        agreement_new_tokens=int(agree["new_tokens"]),
        # read on the chip at the published widths: at toy widths logged, not held
        **{name: 0.0 if rehearse else float(agree.get(name, 0.0)) for name in _LIMITS},
        **sizes,
    )
    return described, RuntimeConfig(**runtime)


# ------------------------------------------------------------------ weights
def params(model_config, runtime, mesh, seed: int):
    """The seeded tree the engine is started with, made on the device from
    the seed in the type it is served in.  The program's own initialiser
    draws every matrix at 1/sqrt(fan_in), every norm's g at 0 and ``phi``,
    ``mu`` uniform in +-hd^-0.5 (as the release initialises them: assumed);
    seeded HERE as the other architectures seed theirs and for their reasons:
    the embedding at UNIT scale (a lookup's fan-in is the one row it reads;
    the head is a matrix of its own, so the layers decide the logits), ``W_o``
    at ``_ATTN_OUT_GAIN`` / sqrt(fan_in) (random queries attend diffusely, so
    an attention update is an average of many values and small beside the
    FFN's: at twice the plain scale a wrong mask, a wrong pooling or a
    summary seen too early moves more of the stream), every norm's ``g``
    uniform in +-``_NORM_RANGE``."""
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
        layers["wo"] = layers["wo"] * _ATTN_OUT_GAIN
        for n, (group, name) in enumerate(
                [(tree, "final_norm"), (layers, "attn_norm"), (layers, "mlp_norm")]):
            leaf = group[name]
            group[name] = (leaf.astype(jnp.float32) + jax.random.uniform(
                jax.random.fold_in(key, 100 + n), leaf.shape, jnp.float32,
                -_NORM_RANGE, _NORM_RANGE)).astype(leaf.dtype)
        return tree

    return jax.jit(seeded, out_shardings=param_shardings(c, mesh))(jax.random.key(seed))


# ---------------------------------------------------------- plain reference
def _rms(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + g)


def _index(a, i):
    import jax
    import jax.numpy as jnp

    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False).astype(jnp.float32)


def frequencies(hd: int, theta: float):
    """``inv_freq [hd/2]`` of the plain law, reckoned on the HOST in float64
    and rounded once to float32 (``theta^x`` taken in float32 carries the
    rounding of its exponent, which at 20,000 positions is a hundredth of a
    radian on the fastest pairs: the reference holds the law itself)."""
    import numpy as np

    return (theta ** (-2.0 * np.arange(hd // 2, dtype=np.float64) / hd)).astype(np.float32)


def _rotate_halves(x, positions, hd: int, theta: float):
    """x [S, heads, hd] rotated at ``positions`` [S]: pair i is (x_i, x_{i + hd/2})."""
    import jax.numpy as jnp

    angles = positions[:, None].astype(jnp.float32) * frequencies(hd, theta)  # [S, hd/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def pooled(k, v, phi, mu, c: int, how: str = "as published"):
    """``kk``, ``vv`` [n, H, hd] of the chunks of k, v [n c, H, hd]: the
    equations at the head of the file.  ``how`` names a FAULT to read the
    size of: "bfloat16 softmax" (the weights' logits and softmax taken in
    bfloat16), "uniform weights" (a_i = 1 / c), "no mu"."""
    import jax
    import jax.numpy as jnp

    hd = k.shape[-1]
    kc, vc = k.reshape(-1, c, *k.shape[1:]), v.reshape(-1, c, *v.shape[1:])  # [n, c, H, hd]
    if how == "bfloat16 softmax":
        logits = jnp.sum(kc.astype(jnp.bfloat16) * phi.astype(jnp.bfloat16), axis=-1)
        a = jax.nn.softmax(logits * jnp.bfloat16(hd ** -0.5), axis=1).astype(jnp.float32)
    else:
        a = jax.nn.softmax(jnp.sum(kc * phi, axis=-1) * hd ** -0.5, axis=1)  # over the chunk
    if how == "uniform weights":
        a = jnp.full_like(a, 1.0 / c)
    kk = jnp.sum(a[..., None] * kc, axis=1)
    return (kk if how == "no mu" else kk + mu), jnp.sum(a[..., None] * vc, axis=1)


@functools.lru_cache(maxsize=None)
def _layer_fn(H: int, hd: int, W: int, c: int, theta: float, eps: float, block: int):
    import jax
    import jax.numpy as jnp

    per = W // c  # chunks a window

    @jax.jit
    def layer(x, layers, i, windows):
        """Layer ``i`` over ONE row x [S, D] of which the first ``windows``
        windows are the row's (S whole windows, the longest row's: one
        compile serves every row): a window at a time, in order, the pooled
        keys and values of the windows before riding along.  -> (x', kk [S /
        c, H, hd], vv, the rotated keys of the last TWO windows run [2 W, H,
        hd]: positions W (windows - 2) .. W windows - 1)."""
        with jax.default_matmul_precision("highest"):
            S = x.shape[0]
            wq, wk, wv, wo = (_index(layers[n], i) for n in ("wq", "wk", "wv", "wo"))
            gate, up, down = (_index(layers[n], i) for n in ("w_gate", "w_up", "w_down"))
            phi, mu = _index(layers["phi"], i), _index(layers["mu"], i)
            g1, g2 = _index(layers["attn_norm"], i), _index(layers["mlp_norm"], i)

            def window(n, carry):
                kk_all, vv_all, newest, out = carry
                xn = jax.lax.dynamic_slice_in_dim(x, n * W, W, axis=0)
                pos = n * W + jnp.arange(W)
                u = _rms(xn, g1, eps)
                q = _rotate_halves((u @ wq).reshape(W, H, hd), pos, hd, theta)
                k = _rotate_halves((u @ wk).reshape(W, H, hd), pos, hd, theta)
                v = (u @ wv).reshape(W, H, hd)
                behind = jnp.arange(kk_all.shape[0]) < per * n  # R_t: chunks of windows before

                def queries(s0):
                    qb = jax.lax.dynamic_slice_in_dim(q, s0, block, axis=0)
                    own = jnp.einsum("snh,tnh->nst", qb, k) * hd ** -0.5  # [H, block, W]
                    causal = jnp.arange(W)[None, :] <= (s0 + jnp.arange(block))[:, None]
                    own = jnp.where(causal[None], own, -jnp.inf)
                    far = jnp.einsum("snh,jnh->nsj", qb, kk_all) * hd ** -0.5
                    far = jnp.where(behind[None, None, :], far, -jnp.inf)
                    p = jax.nn.softmax(jnp.concatenate([own, far], axis=-1), axis=-1)
                    return (jnp.einsum("nst,tnh->snh", p[..., :W], v)
                            + jnp.einsum("nsj,jnh->snh", p[..., W:], vv_all))

                o = jax.lax.map(queries, jnp.arange(0, W, block)).reshape(W, H, hd)
                h = xn + o.reshape(W, H * hd) @ wo
                u2 = _rms(h, g2, eps)
                y = h + (jax.nn.silu(u2 @ gate) * (u2 @ up)) @ down
                kk, vv = pooled(k, v, phi, mu, c)
                return (jax.lax.dynamic_update_slice_in_dim(kk_all, kk, n * per, axis=0),
                        jax.lax.dynamic_update_slice_in_dim(vv_all, vv, n * per, axis=0),
                        jnp.concatenate([newest[W:], k]),
                        jax.lax.dynamic_update_slice_in_dim(out, y, n * W, axis=0))

            zeros = jnp.zeros((S // c, H, hd), jnp.float32)
            kk_all, vv_all, newest, out = jax.lax.fori_loop(
                0, windows, window,
                (zeros, zeros, jnp.zeros((2 * W, H, hd), jnp.float32), jnp.zeros_like(x)))
            return out, kk_all, vv_all, newest

    return layer


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, lm_head):
        with jax.default_matmul_precision("highest"):
            return _rms(x, final_norm.astype(jnp.float32), eps) @ lm_head.astype(jnp.float32)

    return head


def _walk(params, c, tokens, n: int, width: int = 0):
    """One row's float32 stream after every layer -> (x [width, D], and per
    layer what the row should have LEFT in an engine: (kk, vv [width / c, H,
    hd], the keys of the last two of the row's windows [2 W, H, hd])).
    ``width``: whole windows, the longest row's (0: this row's own)."""
    import jax.numpy as jnp
    import numpy as np

    W = c.window_size
    windows = -(-n // W)  # causal and aligned: what lies past the row's length is unseen
    row = np.zeros((max(width, windows * W),), np.int32)
    row[:n] = tokens[:n]
    x = params["embed"][jnp.asarray(row)].astype(jnp.float32)
    layer = _layer_fn(c.n_heads, c.head_dim, W, c.chunk_size, float(c.rope_theta),
                      float(c.norm_eps), min(_QUERY_BLOCK, W))
    left = []
    for i in range(c.n_layers):
        x, kk, vv, newest = layer(x, params["layers"], jnp.int32(i), jnp.int32(windows))
        left.append((kk, vv, newest))
    return x, left


def _width(c, lens) -> int:
    return -(-int(max(lens)) // c.window_size) * c.window_size


def forward_heads(params, model_config, tokens, lens):
    """The float32 logits of EVERY prediction head at every position of
    padded ``tokens`` [B, S] -> [B, S, P, V] (zeros past a row's length)."""
    import numpy as np

    c = model_config
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    out = np.zeros((*tokens.shape, c.num_pred_heads, c.vocab_size), np.float32)
    for r, n in enumerate(lens):
        x, _ = _walk(params, c, tokens[r], int(n), _width(c, lens))
        logits = _head_fn(float(c.norm_eps))(x[: int(n)], params["final_norm"], params["lm_head"])
        out[r, : int(n)] = np.asarray(logits).reshape(int(n), c.num_pred_heads, c.vocab_size)
    return out


def forward_logits(params, model_config, tokens, lens):
    """Head 0's float32 logits [B, S, V]: the served token's."""
    return forward_heads(params, model_config, tokens, lens)[:, :, 0]


def _engine_of(params):
    """The engine that serves ``params``, or None (the harness hands
    ``forward_top2`` the tree and nothing else of the engine)."""
    import gc

    from calfkit_tpu.inference.engine import InferenceEngine

    return next((e for e in gc.get_objects()
                 if isinstance(e, InferenceEngine) and e.params is params), None)


def _far(held, want) -> float:
    import numpy as np

    return float(np.sqrt(((held - want) ** 2).sum()) / max(np.sqrt((want ** 2).sum()), 1e-30))


def _left_behind(engine, c, left, lens, new: int = 0) -> dict:
    """What the served rows' pages still hold against the reference's
    (``left``: a row's ``_walk``), each a distance over the reference's norm,
    the WORST row's.  A row's ``_NEWEST_LEFT_OUT`` newest tokens are left out
    (the engine's last dispatch may or may not have fed them).  SUMMARIES:
    entry ``j`` of a row's summary pages against ``kk_j`` and ``vv_j`` for
    every chunk complete by then.  RING: entry ``p`` mod the ring's tokens
    against the rotated key of position ``p`` of the window of the row's
    newest fed token.  A row is read in the slot whose first layer's
    summaries of the row's PROMPT (all but its ``new`` newest tokens) are
    nearest to it (slots and pages are granted oldest-first, so the rows of
    one check all stand; a row whose slot or pages were taken again reads
    near sqrt(2), and fails)."""
    import numpy as np

    W, cs = c.window_size, c.chunk_size
    fed = [max(int(n) - _NEWEST_LEFT_OUT, 1) for n in lens]
    rings = np.asarray(engine.window_ring(0), np.float32)  # [slots, K, ring tokens, hd]
    held_tokens = rings.shape[2]

    def ring_of(row, held):
        """The positions of the window of the row's newest FED token that the
        ring still holds whatever the last dispatches wrote (they may have run
        ``_NEWEST_LEFT_OUT`` past the row's end), and the reference's keys there."""
        n, m = int(lens[row]), fed[row]
        at = np.arange(max(W * ((m - 1) // W), n + _NEWEST_LEFT_OUT - held_tokens), m)
        newest = np.asarray(left[row][0][2], np.float32)  # positions S - 2 W .. S - 1
        want = newest[at - (-(-n // W) * W - 2 * W)]
        return held[:, at % held_tokens].transpose(1, 0, 2), want

    # the slot that served each row: where the first layer's pooled keys of the row's
    # PROMPT are nearest (a prompt is the row's own; two rows may well be served the
    # same tokens after it, and a first layer's key is its token's at its position)
    pooled_keys = [np.asarray(engine.global_keys(s, 0), np.float32).transpose(1, 0, 2)
                   for s in range(rings.shape[0])]
    slots = []
    for r, n in enumerate(lens):
        prompt = max((int(n) - new) // cs, 1)
        want = np.asarray(left[r][0][0], np.float32)[:prompt]
        slots.append(int(np.argmin([_far(held[:prompt], want) for held in pooled_keys])))
    ring_error = [_far(*ring_of(r, rings[s])) for r, s in enumerate(slots)]
    by_layer = []
    for il in range(c.n_layers):
        worst = 0.0
        for r, (m, s) in enumerate(zip(fed, slots)):
            done = m // cs
            if not done:
                continue
            for values in (False, True):
                held = np.asarray(engine.global_keys(s, il, values=values), np.float32)
                want = np.asarray(left[r][il][1 if values else 0], np.float32)[:done]
                worst = max(worst, _far(held[:, :done].transpose(1, 0, 2), want))
        by_layer.append(round(worst, 6))
    return {"ring_error_by_row": [round(e, 6) for e in ring_error],
            "ring_error": max(ring_error),
            "summary_error_by_layer": by_layer, "summary_error": by_layer[0],
            **({"summary_error_later": max(by_layer[1:])} if len(by_layer) > 1 else {}),
            "slots": slots}


def _fault_sizes(params, c, tokens, n: int) -> dict:
    """How far the reference's own pooled keys of the FIRST layer move under
    each fault a limit has to catch (``pooled``'s ``how``), over their norm:
    the second reading beside a served row's ``summary_error``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    W, cs, hd = c.window_size, c.chunk_size, c.head_dim
    m = min(n, W) // cs * cs
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(np.asarray(tokens[:m], np.int32))].astype(jnp.float32)
        u = _rms(x, _index(layers["attn_norm"], 0), float(c.norm_eps))
        k = _rotate_halves((u @ _index(layers["wk"], 0)).reshape(m, -1, hd),
                           jnp.arange(m), hd, float(c.rope_theta))
        v = (u @ _index(layers["wv"], 0)).reshape(m, -1, hd)
        phi, mu = _index(layers["phi"], 0), _index(layers["mu"], 0)
        want = np.asarray(pooled(k, v, phi, mu, cs)[0])
        return {f"summary_error_if_{how.replace(' ', '_')}":
                round(_far(np.asarray(pooled(k, v, phi, mu, cs, how)[0]), want), 6)
                for how in ("bfloat16 softmax", "uniform weights", "no mu")}


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of head 0's float32 logits, a row at a time over the
    row's own length.  Where an engine serves ``params``, also what the rows
    left behind in it (``_left_behind``), each reading beside its limit on
    stderr; a reading over its limit is returned as ONE decided position that
    no token satisfies, so that the harness's own comparison reads it."""
    import sys

    import numpy as np

    c = model_config
    engine = _engine_of(params)
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    arg = np.zeros(tokens.shape, np.int64)
    gap = np.zeros(tokens.shape, np.float32)
    left = []
    for r, n in enumerate(lens):
        n = int(n)
        x, row_left = _walk(params, c, tokens[r], n, _width(c, lens))
        if engine is not None:
            left.append([tuple(np.asarray(a) for a in layer) for layer in row_left])
        logits = np.asarray(_head_fn(float(c.norm_eps))(
            x[:n], params["final_norm"], params["lm_head"]))[:, : c.vocab_size]
        order = np.sort(logits, axis=-1)
        arg[r, :n], gap[r, :n] = logits.argmax(-1), order[:, -1] - order[:, -2]
    readings, over = {}, []
    if engine is not None:
        readings = {**_left_behind(engine, c, left, lens, getattr(c, "agreement_new_tokens", 0)),
                    **_fault_sizes(params, c, tokens[0], int(lens[0]))}
        for key, what in _LIMITS.items():
            name, limit = key[: -len("_limit")], getattr(c, key, 0.0)
            if name in readings and limit:
                passes = readings[name] <= limit
                over += [] if passes else [name]
                print(f"benchmarks/architectures/evabyte-eva.py: "
                      f"{'ok  ' if passes else 'FAIL'} {what}: {readings[name]:.6f} "
                      f"(limit <= {limit})", file=sys.stderr, flush=True)
    if over:  # one decided position that no token satisfies: the harness refuses it
        arg[0, lens[0] - 2], gap[0, lens[0] - 2] = -1, np.finfo(gap.dtype).max
    new = getattr(c, "agreement_new_tokens", 0)
    spans = [(max(int(n) - new, 1), int(n)) for n in lens] if new else []
    print(json.dumps({
        "phase": "reference", "architecture": "evabyte-eva",
        "positions": int(lens.sum()), **readings, "over_their_limit": over,
        # rows whose served steps crossed a window's edge: what the margin rule then covers
        "rows_served_across_an_edge": sum(
            (a - 1) // c.window_size != (b - 1) // c.window_size for a, b in spans),
        "served_tokens": sum(b - a for a, b in spans),
        "distinct_served_tokens": len(
            {int(t) for r, (a, b) in enumerate(spans) for t in tokens[r, a:b]}),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, L, H = config["hidden_size"], config["num_hidden_layers"], config["num_attention_heads"]
    hd, F, V = D // H, config["intermediate_size"], config["vocab_size"]
    return dict(D=D, L=L, H=H, hd=hd, F=F, V=V, P=config["num_pred_heads"],
                W=config["window_size"], c=config["chunk_size"],
                layer=4 * D * H * hd + 3 * D * F, small=2 * D + 2 * H * hd)


def weight_bytes(config: dict) -> float:
    """Bytes of weights THIS chip holds: the layers held, the embedding, the
    final norm and the head's P x V rows (the served path samples here)."""
    s = _sizes(config)
    numbers = s["L"] * (s["layer"] + s["small"]) + s["D"] + s["V"] * s["D"] * (1 + s["P"])
    return numbers * WEIGHT_BYTES[config["precision"]["weights"]]


def _kv_bytes(config: dict) -> float:
    """Bytes of ONE entry (a key and a value, every head) in ONE layer."""
    s = _sizes(config)
    return 2.0 * s["H"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS for good: a pooled pair a chunk
    in every layer behind the window (the ring does not grow)."""
    s = _sizes(config)
    return s["L"] * _kv_bytes(config) / s["c"]


def live_entries(config: dict, context: float) -> tuple[float, float]:
    """(exact entries of its own window, pooled entries behind it) a query
    at ``context`` tokens reads in one layer."""
    s = _sizes(config)
    windows = math.floor(context / s["W"])
    return context - windows * s["W"] + 1, windows * (s["W"] // s["c"])


def eva_cache_step(config: dict, window_entries: float, summary_entries: float,
                   chips: int = 1) -> dict:
    """What the EVA layers' attention cores must do in decode steps whose
    rows attend ``window_entries`` exact and ``summary_entries`` pooled
    entries in all, summed over rows, steps AND layers (the engine's
    ``decode_eva_window_tokens_read`` and ``decode_eva_summaries_read``):
    each entry's key and value read once, scored and weighed for every head.
    The same work whatever implements the read."""
    s = _sizes(config)
    entries = float(window_entries) + float(summary_entries)
    return {"flops": 4.0 * s["H"] * s["hd"] * entries / chips,
            "bytes": _kv_bytes(config) * entries / chips}


def eva_chunk(config: dict, window_pairs: float, summary_pairs: float, chunks_pooled: float,
              chips: int = 1) -> dict:
    """What the EVA layers' attention and pooling must do in prefill chunks
    whose own positions attend ``window_pairs`` (query, exact key) and
    ``summary_pairs`` (query, pooled entry) pairs and pool ``chunks_pooled``
    chunks, each summed over rows and layers (the engine's
    ``chunk_attn_pairs_eva_window``, ``_summary`` and ``eva_chunks_pooled``):
    a pair is scored and weighed in every head, 4 x H x hd operations; the
    pooling scores and weighs a chunk's c positions, reads their keys and
    values and writes one pooled pair."""
    s = _sizes(config)
    pairs = float(window_pairs) + float(summary_pairs)
    pooled_bytes = float(chunks_pooled) * (s["c"] + 1) * _kv_bytes(config)
    return {"flops": (4.0 * s["H"] * s["hd"] * pairs
                      + 4.0 * s["H"] * s["hd"] * s["c"] * float(chunks_pooled)) / chips,
            "bytes": pooled_bytes / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each, on
    THIS chip: every matrix of the layers held and the head once, the exact
    entries of each row's own window and the pooled ones behind it."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    exact, far = live_entries(config, float(mean_context))
    cache = eva_cache_step(config, rows * s["L"] * exact, rows * s["L"] * far)
    dense = s["L"] * s["layer"] + s["D"] * s["V"] * s["P"]
    return {"flops": (2.0 * dense * rows + cache["flops"]) / chips,
            "bytes": ((dense + s["L"] * s["small"] + s["D"]) * wb + cache["bytes"]) / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context, on THIS chip: the matmul FLOPs of the layers, the head
    for the rows' LAST positions only; causal attention inside each aligned
    window and over the pooled entries of the windows before; the pooling of
    the chunk; the weights once, the keys and values written, the pooled
    entries read."""
    s = _sizes(config)
    wb = WEIGHT_BYTES[config["precision"]["weights"]]
    tokens = rows * chunk
    at = offset + (chunk - 1) / 2.0  # the mean query
    exact, far = live_entries(config, at)
    attn = eva_chunk(config, s["L"] * tokens * exact, s["L"] * tokens * far,
                     s["L"] * tokens / s["c"])
    flops = (2.0 * s["L"] * s["layer"] * tokens + 2.0 * s["D"] * s["V"] * s["P"] * rows
             + attn["flops"])
    written = rows * s["L"] * (chunk + (offset + chunk) / s["c"])
    bytes_ = ((s["L"] * (s["layer"] + s["small"]) + s["D"] * s["V"] * s["P"]) * wb
              + _kv_bytes(config) * written + attn["bytes"])
    return {"flops": flops / chips, "bytes": bytes_ / chips}
