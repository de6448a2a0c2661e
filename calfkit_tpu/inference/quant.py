"""Weight-only int8 quantization.

Decode is HBM-bound: weights are read once per generated token, so storing
matmul weights as int8 (+ bf16 per-output-channel scales) halves the
dominant traffic and lets Llama-3-8B fit a single 16 GB v5e chip.  XLA fuses
the dequant (convert+multiply) into the matmul's operand load — no
materialized bf16 copy.

Representation: a quantized tensor is the pytree leaf-pair
``{"q8": int8[...], "scale": f32 broadcastable}``; :func:`dequant` is the
single read-side seam (identity for plain arrays), applied at every weight
use in :mod:`calfkit_tpu.inference.model`.
"""

from __future__ import annotations

import math
import re
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# which layer weights quantize, and their INPUT (reduction/contraction)
# axes — scales are per-output-channel (max over these axes)
LAYER_REDUCTION_AXES: dict[str, tuple[int, ...]] = {
    "wq": (1,),  # [L, D, H, hd] — reduce D
    "wk": (1,),
    "wv": (1,),
    "wo": (1, 2),  # [L, H, hd, D] — reduce (H, hd)
    "w_gate": (1,),  # [L, D, F]
    "w_up": (1,),
    "w_down": (1,),  # [L, F, D]
}
LM_HEAD_REDUCTION_AXES: tuple[int, ...] = (0,)  # [D, V] — reduce D


def quantize_tensor(w: jax.Array, reduction_axes: tuple[int, ...]) -> dict[str, jax.Array]:
    """int8 symmetric quantization with per-output-channel scales.

    ``reduction_axes`` are the matmul's contraction dims; every other dim
    keeps its own scale (rank preserved — the scale broadcasts and reuses
    the full tensor's sharding spec).
    """
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=reduction_axes, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q8 = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {"q8": q8, "scale": scale.astype(jnp.float32)}


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "q8" in leaf and "scale" in leaf


# int4 leaves carry their packing axis + group size IN THE KEY
# (``q4an<n>g<group>`` where the packing axis is the n-th FROM THE RIGHT,
# i.e. axis = ndim - n): pytree leaves must stay arrays (device_put /
# sharding trees map over values), so the two static ints ride the dict
# structure instead of a side-channel.  Right-relative indexing is what
# keeps the key valid after ``lax.scan`` slices the layer axis off the
# LEFT of every per-layer weight.
_Q4_KEY = "q4an{n}g{group}"
_Q4_RE = re.compile(r"^q4an(\d+)g(\d+)$")


def q4_key_of(leaf: dict) -> "tuple[str, int, int] | None":
    """→ (key, n_from_right, group); axis = array.ndim - n_from_right."""
    for key in leaf:
        m = _Q4_RE.match(key)
        if m:
            return key, int(m.group(1)), int(m.group(2))
    return None


def is_quantized4(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "scale" in leaf and q4_key_of(leaf) is not None


@jax.named_scope("dequant")
def dequant(leaf: Any, dtype: Any = jnp.bfloat16) -> jax.Array:
    """The read-side seam: plain arrays pass through.  The multiply runs in
    f32 (the scale's storage precision) and casts once — XLA fuses the
    convert+multiply into the consuming matmul's operand load."""
    if is_quantized(leaf):
        return (leaf["q8"].astype(jnp.float32) * leaf["scale"]).astype(dtype)
    if isinstance(leaf, dict) and "scale" in leaf:
        found = q4_key_of(leaf)
        if found is not None:
            key, n_right, group = found
            axis = leaf[key].ndim - n_right
            return _dequant4(leaf[key], leaf["scale"], axis, group, dtype)
    return leaf


def _dequant4(
    packed: jax.Array, scale: jax.Array, axis: int, group: int, dtype: Any
) -> jax.Array:
    """Unpack two 4-bit values per byte along ``axis`` (low nibble = even
    element, high = odd; values biased by +8) and apply the group-wise
    scales."""
    lo = (packed & 0x0F).astype(jnp.int8) - 8
    hi = ((packed >> 4) & 0x0F).astype(jnp.int8) - 8
    w = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(packed.shape)
    shape[axis] *= 2
    w = w.reshape(shape)
    n = shape[axis]
    n_groups = n // group
    if n_groups > 1:
        gshape = shape[:axis] + [n_groups, group] + shape[axis + 1:]
        sshape = (
            list(scale.shape[:axis]) + [n_groups, 1]
            + list(scale.shape[axis + 1:])
        )
        w = (
            w.reshape(gshape).astype(jnp.float32) * scale.reshape(sshape)
        ).reshape(shape)
    else:
        w = w.astype(jnp.float32) * scale
    return w.astype(dtype)


DEFAULT_Q4_GROUP = 128


def _q4_group_for(n: int, group: int) -> int:
    """Group size along the packing axis: the requested group when it
    divides the axis, else the whole axis (per-channel fallback)."""
    return group if group and n % group == 0 else n


def quantize_tensor4(
    w: jax.Array, reduction_axes: tuple[int, ...],
    group: int = DEFAULT_Q4_GROUP,
) -> dict[str, jax.Array]:
    """int4 symmetric quantization: values in [-7, 7] biased to [1, 15],
    two per byte packed along the LAST reduction axis, with group-wise
    scales along that axis (finer than int8's per-output-channel — the
    standard accuracy recovery for 4-bit).  Other reduction axes keep
    per-element scale granularity (scale dims stay full there), which is
    strictly finer than int8's reduce-over-everything."""
    axis = reduction_axes[-1]
    n = w.shape[axis]
    if n % 2:
        raise ValueError(f"int4 packing needs an even axis, got {n}")
    g = _q4_group_for(n, group)
    n_groups = n // g
    shape = list(w.shape)
    gshape = shape[:axis] + [n_groups, g] + shape[axis + 1:]
    w32 = w.astype(jnp.float32).reshape(gshape)
    absmax = jnp.max(jnp.abs(w32), axis=axis + 1, keepdims=True)
    scale = jnp.maximum(absmax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(w32 / scale), -7, 7).reshape(shape)
    biased = (q + 8).astype(jnp.uint8)
    lo = jax.lax.slice_in_dim(biased, 0, n, 2, axis)
    hi = jax.lax.slice_in_dim(biased, 1, n, 2, axis)
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    # scale stored with n_groups at the packing axis (drop the kept-1 dim)
    scale = scale.reshape(
        list(scale.shape[:axis + 1]) + list(scale.shape[axis + 2:])
    )
    return {_Q4_KEY.format(n=w.ndim - axis, group=g): packed,
            "scale": scale.astype(jnp.float32)}


def quantize_params(
    params: Params, *, consume: bool = False, bits: int = 8
) -> Params:
    """Quantize the large matmul weights; norms and embeddings stay bf16.

    ``consume=True`` pops tensors out of the input tree as they quantize so
    each full-precision original frees before the next allocates — peak
    memory stays ~1x model size instead of 1.5x (this is what lets an 8B
    random-init quantize on a 16 GB chip).

    ``bits`` selects int8 (per-output-channel scales) or int4 (packed
    nibbles + group-wise scales — half the decode weight stream again).

    The embedding table stays unquantized: it is a gather at the bottom and
    (when untied) the lm_head handles the top; quantizing gathers gives no
    bandwidth win proportional to its complexity.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    qt = quantize_tensor if bits == 8 else quantize_tensor4
    layers = params["layers"]
    if "moe" in layers or "mamba" in layers or "gdn" in layers or "conv" in layers:
        raise ValueError(
            "expert, latent-attention, Mamba, delta-rule (Gated DeltaNet, Kimi Delta "
            "Attention) and short-convolution leaves have no scales: only the "
            "dense decoder's tree is quantized"
        )
    out: Params = {"embed": params["embed"], "final_norm": params["final_norm"]}
    qlayers: Params = {}
    for name in list(layers):
        w = layers.pop(name) if consume else layers[name]
        if name in LAYER_REDUCTION_AXES:
            qlayers[name] = qt(w, LAYER_REDUCTION_AXES[name])
        else:
            qlayers[name] = w  # norms
        del w
    out["layers"] = qlayers
    if "lm_head" in params:
        head = params.pop("lm_head") if consume else params["lm_head"]
        out["lm_head"] = qt(head, LM_HEAD_REDUCTION_AXES)
    return out


def quantize_array_host(
    w: Any, reduction_axes: tuple[int, ...], *, bits: int = 8,
    group: int = DEFAULT_Q4_GROUP,
) -> dict[str, Any]:
    """Numpy-side quantization for the checkpoint loader: only the packed
    tensor + small scale ever reach the device, so a 16 GB chip loads an
    8B model without a transient bf16 copy."""
    import numpy as np

    w32 = np.asarray(w, dtype=np.float32)
    if bits == 8:
        absmax = np.max(np.abs(w32), axis=reduction_axes, keepdims=True)
        scale = np.maximum(absmax / 127.0, 1e-8).astype(np.float32)
        q8 = np.clip(np.round(w32 / scale), -127, 127).astype(np.int8)
        return {"q8": q8, "scale": scale}
    axis = reduction_axes[-1]
    n = w32.shape[axis]
    if n % 2:  # same contract as quantize_tensor4, same clear error
        raise ValueError(f"int4 packing needs an even axis, got {n}")
    g = _q4_group_for(n, group)
    n_groups = n // g
    shape = list(w32.shape)
    gshape = shape[:axis] + [n_groups, g] + shape[axis + 1:]
    wg = w32.reshape(gshape)
    absmax = np.max(np.abs(wg), axis=axis + 1, keepdims=True)
    scale = np.maximum(absmax / 7.0, 1e-8).astype(np.float32)
    q = np.clip(np.round(wg / scale), -7, 7).reshape(shape)
    biased = (q + 8).astype(np.uint8)
    index_lo = [slice(None)] * len(shape)
    index_hi = [slice(None)] * len(shape)
    index_lo[axis] = slice(0, n, 2)
    index_hi[axis] = slice(1, n, 2)
    packed = biased[tuple(index_lo)] | (biased[tuple(index_hi)] << 4)
    scale = scale.reshape(
        list(scale.shape[:axis + 1]) + list(scale.shape[axis + 2:])
    )
    return {_Q4_KEY.format(n=w32.ndim - axis, group=g): packed, "scale": scale}


def quantize_shardings(shardings: Params, *, bits: int = 8) -> Params:
    """Mirror a sharding pytree onto the quantized structure.

    int8: q8 keeps the tensor's spec; the scale clears the spec at every
    reduction axis (those dims are singletons after keepdims and can't
    stay sharded — scales are tiny, replicating them is free).

    int4: the packed tensor keeps the spec (halving an axis preserves
    divisibility); the scale clears the spec ONLY at the packing axis
    (its dim becomes n_groups — replicated for divisibility safety) and
    keeps it elsewhere (other reduction dims stay full-size in int4's
    finer scale granularity, so e.g. wo's tp-sharded head axis stays
    sharded)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def expand(ns: Any, reduction_axes: tuple[int, ...]) -> Any:
        spec = list(ns.spec) + [None] * 8  # pad: P() may be shorter than rank
        cleared = reduction_axes if bits == 8 else reduction_axes[-1:]
        for axis in cleared:
            spec[axis] = None
        scale_ns = NamedSharding(ns.mesh, P(*spec[: len(ns.spec)]))
        if bits == 8:
            return {"q8": ns, "scale": scale_ns}
        # the key's group value is resolved at quantize time from the real
        # axis size; shardings are matched by STRUCTURE via tree-map over
        # the params tree, so mirror whatever key the params carry
        return {"__q4__": ns, "scale": scale_ns}

    out: Params = {
        "embed": shardings["embed"],
        "final_norm": shardings["final_norm"],
    }
    layers = shardings["layers"]
    qlayers: Params = {}
    for name, ns in layers.items():
        if name in LAYER_REDUCTION_AXES:
            qlayers[name] = expand(ns, LAYER_REDUCTION_AXES[name])
        else:
            qlayers[name] = ns
    out["layers"] = qlayers
    if "lm_head" in shardings:
        out["lm_head"] = expand(shardings["lm_head"], LM_HEAD_REDUCTION_AXES)
    return out


def align_quant_sharding_keys(shardings: Params, params: Params) -> Params:
    """Rename int4 placeholder keys (``__q4__``) in a sharding tree to the
    concrete ``q4a<axis>g<group>`` keys the params tree carries, so the
    two trees are structurally identical for device_put/jit donation."""

    def walk(sh: Any, pr: Any) -> Any:
        if isinstance(sh, dict) and "__q4__" in sh and isinstance(pr, dict):
            found = q4_key_of(pr)
            if found is None:
                raise ValueError("params leaf is not int4 but shardings are")
            key, _axis, _group = found
            return {key: sh["__q4__"], "scale": sh["scale"]}
        if isinstance(sh, dict):
            return {k: walk(v, pr[k] if isinstance(pr, dict) else pr)
                    for k, v in sh.items()}
        return sh

    return walk(shardings, params)


def random_quantized_params_host(
    config: Any, seed: int = 0, dtype: Any = None, *, bits: int = 8
) -> Params:
    """Random 8B-SHAPED params built quantized on the host.

    For benchmarking big models without a checkpoint: a device-side random
    init would transiently hold the full bf16 tree (~16 GB for Llama-3-8B —
    the whole chip), so instead generate int8 weights + unit-ish scales in
    numpy, one tensor at a time, and let the caller device_put them into
    quantized shardings.  Values are meaningless; shapes, dtypes, and HBM
    traffic are exactly the serving path's.
    """
    import ml_dtypes  # jax dependency: numpy bfloat16 support
    import numpy as np

    rng = np.random.default_rng(seed)
    np_dtype = np.dtype(dtype) if dtype else np.dtype(ml_dtypes.bfloat16)

    L, D, H, K, hd, F, V = (
        config.n_layers, config.d_model, config.n_heads, config.n_kv_heads,
        config.head_dim, config.d_ff, config.vocab_size,
    )

    def q(shape, reduction_axes):
        fan_in = math.prod(shape[a] for a in reduction_axes)
        if bits == 8:
            q8 = rng.integers(-127, 128, size=shape, dtype=np.int8)
            scale_shape = tuple(
                1 if i in reduction_axes else s for i, s in enumerate(shape)
            )
            scale = np.full(
                scale_shape, 1.0 / (127.0 * np.sqrt(fan_in)), np.float32
            )
            return {"q8": q8, "scale": scale}
        axis = reduction_axes[-1]
        g = _q4_group_for(shape[axis], DEFAULT_Q4_GROUP)
        packed_shape = tuple(
            s // 2 if i == axis else s for i, s in enumerate(shape)
        )
        packed = rng.integers(0, 256, size=packed_shape, dtype=np.uint8)
        scale_shape = tuple(
            shape[axis] // g if i == axis else s for i, s in enumerate(shape)
        )
        scale = np.full(
            scale_shape, 1.0 / (7.0 * np.sqrt(fan_in)), np.float32
        )
        return {_Q4_KEY.format(n=len(shape) - axis, group=g): packed, "scale": scale}

    def dense(shape, fan_in):
        return (rng.standard_normal(shape, dtype=np.float32)
                / np.sqrt(fan_in)).astype(np_dtype)

    params: Params = {
        "embed": dense((V, D), D),
        "layers": {
            "wq": q((L, D, H, hd), LAYER_REDUCTION_AXES["wq"]),
            "wk": q((L, D, K, hd), LAYER_REDUCTION_AXES["wk"]),
            "wv": q((L, D, K, hd), LAYER_REDUCTION_AXES["wv"]),
            "wo": q((L, H, hd, D), LAYER_REDUCTION_AXES["wo"]),
            "w_gate": q((L, D, F), LAYER_REDUCTION_AXES["w_gate"]),
            "w_up": q((L, D, F), LAYER_REDUCTION_AXES["w_up"]),
            "w_down": q((L, F, D), LAYER_REDUCTION_AXES["w_down"]),
            "attn_norm": np.ones((L, D), np_dtype),
            "mlp_norm": np.ones((L, D), np_dtype),
        },
        "final_norm": np.ones((D,), np_dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = q((D, V), LM_HEAD_REDUCTION_AXES)
    return params
