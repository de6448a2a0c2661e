"""Architecture ``granite-hybrid``: Mamba-2 layers beside attention in one stack.

HF ``GraniteMoeHybrid`` with no routed experts (``num_local_experts`` 0),
as ``ibm-granite/granite-4.0-h-micro`` publishes it.  Behind the interface
``manifest.load_architecture`` checks: the program's model description from
a configuration file, the seeded parameter tree, the plain float32
reference, and the operations and bytes the mathematics requires.

Architecture, by the keys of the model's ``config.json`` (D = hidden_size):

- top: ``x = embed[tokens] * embedding_multiplier``; after the last layer
  ``logits = (rmsnorm(x) @ embed.T) / logits_scaling`` (tied head).
- a layer: ``x = x + residual_multiplier * mixer(rmsnorm_1(x))``, then
  ``x = x + residual_multiplier * mlp(rmsnorm_2(x))`` with the SwiGLU MLP
  of ``shared_intermediate_size`` in EVERY layer; ``layer_types`` says which
  mixer each layer has.
- attention mixer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads, no bias, NO rotary embedding
  (``position_embedding_type`` "nope"), scores scaled by
  ``attention_multiplier`` (not 1/sqrt(head_dim)), causal.
- Mamba-2 mixer: ``[z | xBC | dt] = h W_in`` (widths d_inner | d_inner +
  2 G N | H); ``xBC = silu(causal_depthwise_conv1d(xBC, w) + b)``, split
  ``x | B | C``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per
  head; per head ``S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t`` and
  ``y_t = S_t C_t + D x_t``; ``y = rmsnorm(y * silu(z)) * w_norm`` over a
  group's channels; ``out = y W_out``.

The reference runs the recurrence as a plain ``lax.scan`` over positions:
no chunked (SSD) form, no cache, no kernel.  ``mamba_chunk_size`` is a
property of the chunked algorithm and plays no part here.

Departures from the published description: none in the mathematics.  HF
keeps ``A_log``, ``D`` and ``dt_bias`` in the model's type and upcasts them
where they are used; the tree here holds them in float32.  HF stores the
MLP's gate and up projections fused (``shared_mlp.input_linear``) and the
tree holds them apart, which changes no product.  HF's cache keeps the SSM
state in the model's type; the configuration states float32 for it
(``precision.state``) because the recurrence is carried over the whole
sequence and a bfloat16 state rounds at every step.

The reference's weights are the tree the engine serves, upcast to float32
ONE LAYER AT A TIME and a few rows at a time so that it fits beside the
engine on the chip.  It imports nothing of the program but the model
description it is handed.  Counts are what the mathematics requires:
weights once a step at the stated precision, each row's recurrent state
read AND written once a step, attention reading only the KV attended.
"""

from __future__ import annotations

import functools
import json

from benchmarks.opcount import WEIGHT_BYTES

ATTENTION, MAMBA = "attention", "mamba"
_ROWS_AT_ONCE = 2  # rows the reference carries through a layer together
_HEAD_BLOCK = 256  # positions whose logits the reference holds at once
_served_tokens = 0  # agreement.new_tokens of the configuration model() last read


# ------------------------------------------------- the program's description
def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig

    global _served_tokens
    _served_tokens = int(config["agreement"]["new_tokens"])
    if config.get("num_local_experts", 0):
        raise ValueError("granite-hybrid runs no routed experts (num_local_experts > 0)")
    runtime = dict(config["runtime"])
    sizes = {
        "vocab_size": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"], "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"], "d_ff": config["shared_intermediate_size"],
        "layer_types": tuple(config["layer_types"]),
        "mamba_n_heads": config["mamba_n_heads"], "mamba_d_head": config["mamba_d_head"],
        "mamba_d_state": config["mamba_d_state"], "mamba_n_groups": config["mamba_n_groups"],
        "mamba_d_conv": config["mamba_d_conv"], "mamba_chunk_size": config["mamba_chunk_size"],
    }
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        toy = dict(config["rehearsal"]["model"])
        toy["layer_types"] = tuple(toy["layer_types"])
        sizes.update(toy)
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    if config["position_embedding_type"] != "nope":
        raise ValueError("granite-hybrid: position_embedding_type other than 'nope'")
    if sizes["mamba_n_heads"] * sizes["mamba_d_head"] != config["mamba_expand"] * sizes[
            "d_model"] and not rehearse:
        raise ValueError("mamba_n_heads x mamba_d_head differs from mamba_expand x hidden_size")
    described = ModelConfig(
        name=config["name"], norm_eps=float(config["rms_norm_eps"]),
        max_seq_len=runtime["max_seq_len"], dtype=config["precision"]["activations"],
        state_dtype=config["precision"]["state"],
        tie_embeddings=bool(config["tie_word_embeddings"]),
        position_embedding="none",
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]), **sizes,
    )
    return described, RuntimeConfig(**runtime)


# ------------------------------------------------------------------ weights
def params(model_config, runtime, mesh, seed: int):
    """``None``: the engine's own initialiser makes the tree on the device
    in one jitted call from the seed, born in the type it is served in.
    That initialiser draws the matrices that write to the stream
    1/residual_multiplier larger than 1/sqrt(fan_in), and ``A`` and ``dt``
    from the family's ranges (``assumed`` in the configuration file), so
    that the 40 layers, not the input token's embedding under the tied
    head, decide the logits (``calfkit_tpu/inference/model.py``:
    ``init_params``)."""
    if runtime.quantization is not None:
        raise ValueError(f"no initialiser for quantization {runtime.quantization!r}")
    return None


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


def _mlp(x, w, eps, rm):
    import jax
    import jax.numpy as jnp

    h = _rms(x, w["mlp_norm"], eps)
    gate = jnp.einsum("bsd,df->bsf", h, w["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, w["w_up"])
    return x + rm * jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"])


@functools.lru_cache(maxsize=None)
def _attention_layer(n_heads: int, n_kv: int, head_dim: int, scale: float, eps: float, rm: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layer(x, attn, mlp, ia, il, lens):  # x [B, S, D] float32
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            w = _f32(attn, ia)
            h = _rms(x, w["attn_norm"], eps)
            q = jnp.einsum("bsd,dnh->bsnh", h, w["wq"])  # no rotary embedding
            k = jnp.einsum("bsd,dkh->bskh", h, w["wk"])
            v = jnp.einsum("bsd,dkh->bskh", h, w["wv"])
            qg = q.reshape(B, S, n_kv, n_heads // n_kv, head_dim)
            scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) * scale
            t = jnp.arange(S)
            mask = (t[None, :] <= t[:, None])[None] & (t[None, None, :] < lens[:, None, None])
            scores = jnp.where(mask[:, None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bkgst,btkh->bskgh", probs, v).reshape(B, S, n_heads, head_dim)
            x = x + rm * jnp.einsum("bsnh,nhd->bsd", out, w["wo"])
            return _mlp(x, _f32(mlp, il), eps, rm)

    return layer


@functools.lru_cache(maxsize=None)
def _mamba_layer(n_heads: int, d_head: int, d_state: int, groups: int, d_conv: int,
                 eps: float, rm: float):
    import jax
    import jax.numpy as jnp

    d_inner = n_heads * d_head
    per_group = n_heads // groups

    @jax.jit
    def layer(x, mamba, mlp, im, il):  # x [B, S, D] float32
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            w = _f32(mamba, im)
            h = _rms(x, w["mixer_norm"], eps)
            zxbcdt = jnp.einsum("bsd,ed->bse", h, w["w_in"])
            conv_dim = d_inner + 2 * groups * d_state
            z = zxbcdt[..., :d_inner]
            xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
            dt = jax.nn.softplus(zxbcdt[..., d_inner + conv_dim:] + w["dt_bias"])  # [B, S, H]
            # causal depthwise conv: tap k sees the input d_conv - 1 - k back
            padded = jnp.pad(xbc, ((0, 0), (d_conv - 1, 0), (0, 0)))
            pre = sum(padded[:, k:k + S] * w["conv_w"][k] for k in range(d_conv))
            xbc = jax.nn.silu(pre + w["conv_b"])
            xs = xbc[..., :d_inner].reshape(B, S, n_heads, d_head)
            Bm = xbc[..., d_inner:d_inner + groups * d_state].reshape(B, S, groups, d_state)
            Cm = xbc[..., d_inner + groups * d_state:].reshape(B, S, groups, d_state)
            Bm = jnp.repeat(Bm, per_group, axis=2)  # a group's B and C serve its heads
            Cm = jnp.repeat(Cm, per_group, axis=2)
            A = -jnp.exp(w["A_log"])  # [H]

            def position(state, inputs):  # state [B, H, P, N]
                x_t, dt_t, b_t, c_t = inputs
                decay = jnp.exp(dt_t * A)[..., None, None]
                state = decay * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
                y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) + w["D"][None, :, None] * x_t
                return state, y_t

            _, y = jax.lax.scan(
                position, jnp.zeros((B, n_heads, d_head, d_state), jnp.float32),
                (xs.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)),
            )
            y = y.swapaxes(0, 1).reshape(B, S, d_inner) * jax.nn.silu(z)
            grouped = y.reshape(B, S, groups, d_inner // groups)
            var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
            y = (grouped / jnp.sqrt(var + eps)).reshape(B, S, d_inner) * w["norm"]
            x = x + rm * jnp.einsum("bse,ed->bsd", y, w["w_out"])
            return _mlp(x, _f32(mlp, il), eps, rm)

    return layer


@functools.lru_cache(maxsize=None)
def _head(eps: float, scaling: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def head(x, final_norm, lm_head):  # lm_head [V, D] (tied) or [D, V]
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), eps)
            w = lm_head.astype(jnp.float32)
            spec = "bsd,vd->bsv" if lm_head.shape[-1] == x.shape[-1] else "bsd,dv->bsv"
            top, idx = jax.lax.top_k(jnp.einsum(spec, h, w) / scaling, 2)
            return idx[..., 0], top[..., 0] - top[..., 1]

    return head


def _hidden(params, model_config, tokens, lens):
    """The stream after the last layer, float32, for a few rows [B, S]."""
    import jax.numpy as jnp

    c = model_config
    eps, rm = float(c.norm_eps), float(c.residual_multiplier)
    attention = _attention_layer(c.n_heads, c.n_kv_heads, c.head_dim,
                                 float(c.attention_multiplier), eps, rm)
    mamba = _mamba_layer(c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state, c.mamba_n_groups,
                         c.mamba_d_conv, eps, rm)
    layers = params["layers"]
    x = params["embed"][tokens].astype(jnp.float32) * float(c.embedding_multiplier)
    row_lens = jnp.asarray(lens)
    ia = im = 0
    for il, kind in enumerate(c.layer_types):  # one layer's float32 copy at a time
        if kind == ATTENTION:
            x = attention(x, layers["attn"], layers["mlp"], jnp.int32(ia), jnp.int32(il), row_lens)
            ia += 1
        else:
            x = mamba(x, layers["mamba"], layers["mlp"], jnp.int32(im), jnp.int32(il))
            im += 1
    return x


def forward_logits(params, model_config, tokens, lens):
    """Full forward -> float32 logits [B, S, V], held whole: for the small
    sizes of the tests, which compare logits and never tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = model_config
    x = _hidden(params, c, np.asarray(tokens), np.asarray(lens))
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"].astype(jnp.float32), float(c.norm_eps))
        head = params.get("lm_head")
        if head is None:
            logits = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(jnp.float32))
        else:
            logits = jnp.einsum("bsd,dv->bsv", h, head.astype(jnp.float32))
        return np.asarray(logits / float(c.logits_scaling))


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits.  Also logs what the margin rule
    cannot show by itself: how many distinct tokens the engine served in
    the rows' last ``agreement.new_tokens`` positions, and how often the
    reference's choice is just its input token again."""
    import numpy as np

    c = model_config
    head = _head(float(c.norm_eps), float(c.logits_scaling))
    lm_head = params.get("lm_head", params["embed"])
    tokens, lens = np.asarray(tokens), np.asarray(lens)
    args, gaps = [], []
    for r0 in range(0, tokens.shape[0], _ROWS_AT_ONCE):
        rows = slice(r0, r0 + _ROWS_AT_ONCE)
        x = _hidden(params, c, tokens[rows], lens[rows])
        arg, gap = [], []
        for s0 in range(0, x.shape[1], _HEAD_BLOCK):  # the logits a block of positions at a time
            a, g = head(x[:, s0:s0 + _HEAD_BLOCK], params["final_norm"], lm_head)
            arg.append(np.asarray(a))
            gap.append(np.asarray(g))
        args.append(np.concatenate(arg, axis=1))
        gaps.append(np.concatenate(gap, axis=1))
    arg, gap = np.concatenate(args), np.concatenate(gaps)
    own = np.arange(tokens.shape[1])[None, :] < lens[:, None]
    served = [tokens[r, max(lens[r] - _served_tokens, 0):lens[r]] for r in range(len(lens))]
    print(json.dumps({
        "phase": "reference", "architecture": "granite-hybrid",
        "positions": int(own.sum()),
        "reference_argmax_is_its_input_token": int(((arg == tokens) & own).sum()),
        "served_tokens": int(sum(len(s) for s in served)),
        "distinct_served_tokens": len({int(t) for s in served for t in s}),
        "served_token_repeats_the_one_before": int(sum((s[1:] == s[:-1]).sum() for s in served)),
    }), flush=True)
    return arg, gap


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or D // H
    F, V = config["shared_intermediate_size"], config["vocab_size"]
    Hm, P, N = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    G, dc = config["mamba_n_groups"], config["mamba_d_conv"]
    d_inner = Hm * P
    conv_dim = d_inner + 2 * G * N
    Lm = sum(t == MAMBA for t in config["layer_types"])
    La = L - Lm
    attn_mats = D * H * hd + 2 * D * K * hd + H * hd * D
    mamba_mats = D * (d_inner + conv_dim + Hm) + d_inner * D
    mamba_small = conv_dim * (dc + 1) + 3 * Hm + d_inner  # conv, A_log, D, dt_bias, norm
    mlp = 3 * D * F
    return dict(
        D=D, L=L, La=La, Lm=Lm, H=H, K=K, hd=hd, F=F, V=V, Hm=Hm, P=P, N=N, G=G, dc=dc,
        d_inner=d_inner, conv_dim=conv_dim,
        matmul_params=La * attn_mats + Lm * mamba_mats + L * mlp + D * V,  # tied: the head once
        other_params=Lm * mamba_small + 2 * L * D + D,
        ssm_numbers=Lm * Hm * P * N, conv_numbers=Lm * conv_dim * (dc - 1),
    )


def weight_bytes(config: dict) -> float:
    """Bytes of weights one step must read: every matrix, the tied head
    once, the conv taps and the per-head and per-channel vectors, at the
    stated weight precision (the embedding as an input is a gather of one
    row a token and is not counted twice)."""
    s = _sizes(config)
    return (s["matmul_params"] + s["other_params"]) * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token ADDS: K and V of the attention
    layers alone (the recurrent state does not grow with length)."""
    s = _sizes(config)
    return 2.0 * s["La"] * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def recurrent_state_bytes(config: dict, rows: float = 1.0) -> float:
    """Bytes of recurrent state ``rows`` sequences hold: every Mamba
    layer's SSM state at ``precision.state`` and its conv state at the
    activations' precision."""
    s = _sizes(config)
    return float(rows) * (
        s["ssm_numbers"] * WEIGHT_BYTES[config["precision"]["state"]]
        + s["conv_numbers"] * WEIGHT_BYTES[config["precision"]["activations"]]
    )


def recurrent_state_step(config: dict, rows: float, chips: int = 1) -> dict:
    """What one decode step must do to the recurrent state of ``rows``
    rows: read it and write it (bytes), and the update and readout of the
    SSM state (3 multiply-adds a number: decay, input, readout)."""
    s = _sizes(config)
    return {"flops": 6.0 * s["ssm_numbers"] * rows / chips,
            "bytes": 2.0 * recurrent_state_bytes(config, rows) / chips}


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each:
    the weights once, each row's recurrent state read AND written, the KV
    of the attention layers over the context."""
    s = _sizes(config)
    ctx = float(rows) * float(mean_context)
    state = recurrent_state_step(config, rows)
    flops = (2.0 * s["matmul_params"] * rows + 4.0 * s["La"] * s["H"] * s["hd"] * ctx
             + state["flops"])
    bytes_ = weight_bytes(config) + state["bytes"] + state_bytes_per_token(config) * ctx
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context: the matmul FLOPs of both mixers, the MLP and the head,
    the scan's (as the recurrence counts them: the chunked form's extra
    work is the algorithm's, not the mathematics'), causal attention in the
    attention layers; the weights once, the rows' recurrent state in and
    out, the KV written and attended."""
    s = _sizes(config)
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    flops = (2.0 * s["matmul_params"] * tokens + 4.0 * s["La"] * s["H"] * s["hd"] * attended
             + 6.0 * s["ssm_numbers"] * tokens)
    bytes_ = (weight_bytes(config) + 2.0 * recurrent_state_bytes(config, rows)
              + state_bytes_per_token(config) * rows * (offset + chunk))
    return {"flops": flops / chips, "bytes": bytes_ / chips}
