"""Architecture ``dense-gqa``: the Llama-style pre-norm decoder.

Everything the harness and the trace readers need to know about one kind
of model, behind the interface ``manifest.load_architecture`` checks:
the program's model description from a configuration file, the seeded
parameter tree, the plain float32 reference, and the operations and bytes
the mathematics requires.

Architecture (InternLM2 and Mistral-7B-v0.3 share it): token embedding;
per layer RMSNorm -> Q, K, V projections without bias -> rotary embedding
on Q and K (rotate-half pairing, base ``rope_theta``) -> causal
grouped-query attention (``num_key_value_heads`` KV heads, each shared by
H/K query heads) scaled by 1/sqrt(head_dim) -> output projection ->
residual; RMSNorm -> SwiGLU MLP (silu(x W_gate) * (x W_up)) W_down ->
residual; final RMSNorm; untied LM head.  Departures: none in the
mathematics.  InternLM2 stores Q, K and V as one fused ``wqkv`` matrix and
declares dynamic-NTK rope scaling, which changes nothing below the 32k
positions it was trained for; Mistral-7B-v0.3 declares no sliding window.

The reference's weights are the tree the engine serves (so both sides see
the same numbers), dequantised where the configuration serves int8, and
upcast to float32 ONE LAYER AT A TIME so the reference fits beside the
engine on the chip.  Counts are what the mathematics requires, not what a
given program happens to do: weights are read once a step at the
configuration's stated precision, attention reads only the KV attended.
"""

from __future__ import annotations

import functools
import math

from benchmarks.opcount import WEIGHT_BYTES


# ------------------------------------------------- the program's description
def model(config: dict, rehearse: bool):
    """The program's ModelConfig and RuntimeConfig from a configuration
    file.  Only what the file states is set; the rest is as defaulted."""
    from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig

    runtime = dict(config["runtime"])
    sizes = {
        "vocab_size": config["vocab_size"], "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"], "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"], "d_ff": config["intermediate_size"],
    }
    if rehearse:  # CPU rehearsal: toy widths, every length divided by scale
        sizes.update(config["rehearsal"]["model"])
        runtime.update(config["rehearsal"]["runtime"])
        runtime["compilation_cache"] = False
    if "window_buckets" in runtime:
        runtime["window_buckets"] = tuple(runtime["window_buckets"])
    described = ModelConfig(
        name=config["name"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]), max_seq_len=runtime["max_seq_len"],
        dtype=config["precision"]["activations"],
        tie_embeddings=bool(config["tie_word_embeddings"]), **sizes,
    )
    if described.head_dim != (config.get("head_dim") or described.head_dim) and not rehearse:
        raise ValueError("head_dim of the file differs from hidden_size / heads")
    return described, RuntimeConfig(**runtime)


# ------------------------------------------------------------------ weights
def params(model_config, runtime, mesh, seed: int):
    """The parameter tree the engine is started with, made on the device
    in one jitted call from the seed, in the type it is served in, born
    sharded.  ``None`` where the engine's own initialiser does exactly
    that (bf16: one jitted call with ``out_shardings``).  The int8 tree is
    built here, in the layout the program's ``quantize_shardings``
    describes: the program's ``random_quantized_params_host`` builds the
    same tree in numpy on the host, which every run would pay for."""
    if runtime.quantization is None:
        return None
    if runtime.quantization != "int8":
        raise ValueError(f"no initialiser for quantization {runtime.quantization!r}")
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.quant import (
        LAYER_REDUCTION_AXES,
        LM_HEAD_REDUCTION_AXES,
        quantize_shardings,
    )
    from calfkit_tpu.inference.sharding import param_shardings

    c = model_config
    L, D, H, K, hd, F, V = (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                            c.head_dim, c.d_ff, c.vocab_size)
    shapes = {
        "wq": (L, D, H, hd), "wk": (L, D, K, hd), "wv": (L, D, K, hd),
        "wo": (L, H, hd, D), "w_gate": (L, D, F), "w_up": (L, D, F),
        "w_down": (L, F, D),
    }
    dtype = jnp.dtype(c.dtype)
    shardings = quantize_shardings(param_shardings(c, mesh), bits=8)

    def leaf(key, shape, axes):
        fan_in = math.prod(shape[a] for a in axes)
        scale_shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
        # uniform int8 has a standard deviation of 73.3: the scale gives the
        # dequantised weights the variance the bf16 initialiser has
        return {
            "q8": jax.random.randint(key, shape, -127, 128, dtype=jnp.int8),
            "scale": jnp.full(scale_shape, 1.0 / (73.3 * math.sqrt(fan_in)), jnp.float32),
        }

    def build(key):
        keys = jax.random.split(key, len(shapes) + 2)
        layers = {
            name: leaf(keys[i], shape, LAYER_REDUCTION_AXES[name])
            for i, (name, shape) in enumerate(shapes.items())
        }
        layers["attn_norm"] = jnp.ones((L, D), dtype)
        layers["mlp_norm"] = jnp.ones((L, D), dtype)
        tree = {
            "embed": (jax.random.normal(keys[-1], (V, D), jnp.float32)
                      / math.sqrt(D)).astype(dtype),
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
        }
        if not c.tie_embeddings:
            tree["lm_head"] = leaf(keys[-2], (D, V), LM_HEAD_REDUCTION_AXES)
        return tree

    return jax.jit(build, out_shardings=shardings)(jax.random.key(seed))


# ---------------------------------------------------------- plain reference
def _dequant(leaf):
    """A weight leaf as float32: plain arrays pass, {"q8", "scale"} leaves
    are q8 * scale (the program's stated int8 format)."""
    import jax.numpy as jnp

    if isinstance(leaf, dict):
        return leaf["q8"].astype(jnp.float32) * leaf["scale"].astype(jnp.float32)
    return leaf.astype(jnp.float32)


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


@functools.lru_cache(maxsize=None)
def _layer_fn(n_heads: int, n_kv: int, head_dim: int, theta: float, eps: float):
    import jax
    import jax.numpy as jnp

    def rope(x, pos):  # x [B, S, N, hd]
        half = head_dim // 2
        freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        ang = pos[:, :, None].astype(jnp.float32) * freqs  # [B, S, half]
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    @jax.jit
    def layer(x, layers, i, lens):  # x [B, S, D] f32; layer i of the stacked tree
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), layers
            )
            w = {k: _dequant(v) for k, v in lp.items()}
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            h = _rms(x, w["attn_norm"], eps)
            q = rope(jnp.einsum("bsd,dnh->bsnh", h, w["wq"]), pos)
            k = rope(jnp.einsum("bsd,dkh->bskh", h, w["wk"]), pos)
            v = jnp.einsum("bsd,dkh->bskh", h, w["wv"])
            group = n_heads // n_kv
            qg = q.reshape(B, S, n_kv, group, head_dim)
            scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) / jnp.sqrt(float(head_dim))
            t = jnp.arange(S)
            mask = (t[None, :] <= t[:, None])[None] & (t[None, None, :] < lens[:, None, None])
            scores = jnp.where(mask[:, None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("bkgst,btkh->bskgh", probs, v).reshape(B, S, n_heads, head_dim)
            x = x + jnp.einsum("bsnh,nhd->bsd", attn, w["wo"])
            h = _rms(x, w["mlp_norm"], eps)
            gate = jnp.einsum("bsd,df->bsf", h, w["w_gate"])
            up = jnp.einsum("bsd,df->bsf", h, w["w_up"])
            return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, w["w_down"])

    return layer


def forward_top2(params, model_config, tokens, lens):
    """Full forward of padded ``tokens`` [B, S] -> (argmax [B, S], top-1
    margin [B, S]) of the float32 logits."""
    import jax
    import jax.numpy as jnp

    c = model_config
    layer = _layer_fn(c.n_heads, c.n_kv_heads, c.head_dim, float(c.rope_theta), float(c.norm_eps))
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(c.n_layers):  # one layer's float32 copy at a time
        x = layer(x, params["layers"], jnp.int32(i), lens)

    @jax.jit
    def head(x, final_norm, lm_head):
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), float(c.norm_eps))
            logits = jnp.einsum("bsd,dv->bsv", h, lm_head)
            top, idx = jax.lax.top_k(logits, 2)
            return idx[..., 0], top[..., 0] - top[..., 1]

    lm_head = params.get("lm_head")
    lm_head = _dequant(lm_head) if lm_head is not None else params["embed"].astype(jnp.float32).T
    return head(x, params["final_norm"], lm_head)


# ------------------------------------------------------ operations and bytes
def _sizes(config: dict) -> dict:
    D, L = config["hidden_size"], config["num_hidden_layers"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or D // H
    F, V = config["intermediate_size"], config["vocab_size"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return dict(D=D, L=L, H=H, K=K, hd=hd, F=F, V=V, per_layer=per_layer,
                matmul_params=L * per_layer + D * V)


def weight_bytes(config: dict) -> float:
    """Bytes of weights one step must read: every layer matrix and the
    head at the stated weight precision (norms are negligible; the
    embedding is a gather of one row a token)."""
    return _sizes(config)["matmul_params"] * WEIGHT_BYTES[config["precision"]["weights"]]


def state_bytes_per_token(config: dict) -> float:
    """Bytes of sequence state a token adds: K and V of every layer."""
    s = _sizes(config)
    return 2.0 * s["L"] * s["K"] * s["hd"] * WEIGHT_BYTES[config["precision"]["kv"]]


def decode_step(config: dict, rows: float, mean_context: float, chips: int = 1) -> dict:
    """One decode step over ``rows`` rows of ``mean_context`` tokens each,
    per chip under tensor parallelism over ``chips``: FLOPs and bytes."""
    s = _sizes(config)
    ctx = float(rows) * float(mean_context)
    flops = 2.0 * s["matmul_params"] * rows + 4.0 * s["L"] * s["H"] * s["hd"] * ctx
    bytes_ = weight_bytes(config) + state_bytes_per_token(config) * ctx
    return {"flops": flops / chips, "bytes": bytes_ / chips}


def prefill_chunk(config: dict, rows: int, chunk: int, offset: int, chips: int = 1) -> dict:
    """One prefill chunk of ``chunk`` tokens a row at ``offset`` tokens of
    earlier context: FLOPs and bytes per chip."""
    s = _sizes(config)
    tokens = rows * chunk
    attended = rows * chunk * (offset + (chunk + 1) / 2.0)  # causal
    flops = 2.0 * s["matmul_params"] * tokens + 4.0 * s["L"] * s["H"] * s["hd"] * attended
    bytes_ = weight_bytes(config) + state_bytes_per_token(config) * rows * (offset + chunk)
    return {"flops": flops / chips, "bytes": bytes_ / chips}
