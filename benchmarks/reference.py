"""The plain reference: a decoder block stack in straightforward jax.numpy,
float32, matmul precision "highest", no kernels, no cache, no batching
tricks — and the comparison that decides the agreement part of ``correct``.

Architecture (InternLM2 and Mistral-7B-v0.3 share it; the published
descriptions are the Llama-style pre-norm decoder): token embedding;
per layer RMSNorm -> Q, K, V projections without bias -> rotary embedding
on Q and K (rotate-half pairing, base ``rope_theta``) -> causal
grouped-query attention (``num_key_value_heads`` KV heads, each shared by
H/K query heads) scaled by 1/sqrt(head_dim) -> output projection ->
residual; RMSNorm -> SwiGLU MLP (silu(x W_gate) * (x W_up)) W_down ->
residual; final RMSNorm; untied LM head.  Departures: none in the
mathematics.  InternLM2 stores Q, K and V as one fused ``wqkv`` matrix and
declares dynamic-NTK rope scaling, which changes nothing below the 32k
positions it was trained for; Mistral-7B-v0.3 declares no sliding window.

Weights come from the engine's own parameter tree (so both sides see the
same numbers), dequantised where the configuration serves int8, and are
upcast to float32 ONE LAYER AT A TIME so the reference fits beside the
engine on the chip.
"""

from __future__ import annotations

import functools


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


def agreement(params, model_config, prompts, outputs, margin: float,
              min_compared: int = 8) -> dict:
    """Teacher-forced: the reference recomputes every position of prompt +
    generated tokens; wherever its top-1 margin exceeds ``margin`` the
    engine's token has to be its argmax (the rule chip_smoke.py uses), and
    at least ``min_compared`` positions have to be decided that way."""
    import numpy as np

    seqs = [p + o for p, o in zip(prompts, outputs)]
    width = -(-max(len(s) for s in seqs) // 64) * 64
    tokens = np.zeros((len(seqs), width), np.int32)
    for r, s in enumerate(seqs):
        tokens[r, : len(s)] = s
    lens = np.asarray([len(s) for s in seqs], np.int32)
    arg, gap = forward_top2(params, model_config, tokens, lens)
    arg, gap = np.asarray(arg), np.asarray(gap)
    total = compared = equal = 0
    finite = True
    for r, (prompt, out) in enumerate(zip(prompts, outputs)):
        finite &= bool(np.isfinite(gap[r, : lens[r]]).all())
        for i, tok in enumerate(out):
            at = len(prompt) - 1 + i  # the position whose logits chose out[i]
            total += 1
            if gap[r, at] > margin:
                compared += 1
                equal += int(arg[r, at] == tok)
    return {"positions": total, "compared": compared, "equal": equal,
            "margin": margin, "min_compared": min_compared, "finite": finite,
            "ok": finite and compared >= min_compared and equal == compared}
