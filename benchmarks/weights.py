"""Weights from ``--seed``, made on the device in one jitted call, in the
type they are served in, born sharded.

bf16 configurations use the engine's own initialiser (one jitted call from
the seed with ``out_shardings``).  The int8 tree is built here: random int8
leaves and constant scales in exactly the layout the program's
``quantize_shardings`` describes, passed to the engine as ``params=`` — the
program's ``random_quantized_params_host`` builds the same tree in numpy on
the host, which every run of every check would pay for.
"""

from __future__ import annotations

import math


def fold_seed(seed: int) -> int:
    """``--seed`` can pass 2**31; JAX keys take 32 signed bits."""
    return int(seed) % (2**31 - 9)


def int8_params(model_config, mesh, seed: int):
    """Random weight-only-int8 parameters for ``model_config`` on ``mesh``."""
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
        params = {
            "embed": (jax.random.normal(keys[-1], (V, D), jnp.float32)
                      / math.sqrt(D)).astype(dtype),
            "layers": layers,
            "final_norm": jnp.ones((D,), dtype),
        }
        if not c.tie_embeddings:
            params["lm_head"] = leaf(keys[-2], (D, V), LM_HEAD_REDUCTION_AXES)
        return params

    return jax.jit(build, out_shardings=shardings)(jax.random.key(fold_seed(seed)))
