"""The Pallas TPU kernel for the routed experts of ONE decode step: the
experts the step's real rows HIT, read where they lie in the stack.

Why a kernel: the dense form (``moe.experts_dense``) multiplies every held
expert by every row, so a step reads them all, and of experts held by SHARE
(``config.expert_share``: 16 of 128, 128 of 512, 64 of 512 scored) a step's
rows hit only some: ~2.5 of command-a-plus's 16, ~84 of Qwen3-Next's 128, ~27
of Ling's 64 (PERF.md sections 5 and 6, PR 53).  The compiler's grouped
kernel (``lax.ragged_dot``) reads the hit alone and pays a sort, a gather, a
scatter and a fixed cost that a step's few hundred pairs do not fill.  Up to
128 rows a weight is under the chip's ridge (197 TFLOP/s / 819 GB/s = 240
rows), so the step IS the weight stream and every row can go through every
hit expert, masked by its weight: nothing is sorted or gathered.

:func:`moe_step_pallas` takes the STACKED leaves ``[Lm, E, D, Fe]`` /
``[Lm, E, Fe, D]`` and the layer index, as ``moe.experts_grouped`` does (a
layer sliced out of the stack for a custom call is a COPY of 0.8-1.6 GB).
Prefetched scalars carry the layer, the hit experts' ids (ascending, the last
repeated past ``n_hit``) and ``n_hit``; the grid is (experts held, width
tiles) and the weights' block index maps read ``(m, ids[i], ., tile)``.  A
grid step past ``n_hit`` names the block already resident, so it starts no
copy and computes nothing.  In VMEM, a hit expert's width tile at a time:

    g = h W_gate[e][:, tile]    u = h W_up[e][:, tile]         float32
    act = silu(g) u w_e                    w_e [T, 1]: zero outside the chosen
    acc += bf16(act) W_down[e][tile, :]                        float32

``acc`` holds the sum over experts and width in float32 and is cast once, at
the last grid step: the dense form's mathematics with no intermediate
rounded to the activations' type but ``act``.  No capacity, no dropped pair;
of experts held by share a pair whose expert is held elsewhere has no column
in ``gates`` and adds nothing, as in the other two forms.

With no expert hit (every row inactive) the output is zero; the pipeline's
first block is still copied (2 MB), which no step of a running engine meets.

Who chooses it: ``InferenceEngine._resolved_moe_step_impl``, once at
construction, beside ``_resolved_ssm_impl`` and under the same
``attention_impl`` values: the kernel on a TPU, on one device, for experts
held by share whose shapes are whole tiles (:func:`moe_step_ok`); else the
form ``moe.dense_form`` gives, the dense one being the reference.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from calfkit_tpu.inference.pallas_attention import PallasShapeError, _note_trace

# one weight block of a grid step, [D, tile] or [tile, D]: three of them are
# double-buffered (12 MB at 2 MB a block), and a width tile is whole lane
# tiles: 256 of command-a-plus's 4096 x 4096, all 512 of Qwen3-Next's
# 2048 x 512, 384 of Ling's 2560 x 768 (PERF.md section 6, PR 53)
_BLOCK_BYTES = 2 << 20


def moe_step_ok(d_model: int, moe_d_ff: int, dtype: Any) -> bool:
    """Whether :func:`_moe_step_kernel` can take these experts on a TPU:
    bfloat16 or float32 matrices whose two sides are whole lane tiles.
    What fails this keeps the form ``moe.dense_form`` gives."""
    return (
        jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
        and d_model % 128 == 0 and moe_d_ff % 128 == 0
    )


def _width_tile(d_model: int, moe_d_ff: int, itemsize: int) -> int:
    """The widest tile of whole lane tiles that divides the experts' width
    and keeps a block within ``_BLOCK_BYTES`` (one lane tile at least); a
    width that is no whole lane tiles (a toy's, interpreted) is one tile."""
    if moe_d_ff % 128:
        return moe_d_ff
    fits = [t for t in range(128, moe_d_ff + 1, 128)
            if moe_d_ff % t == 0 and d_model * t * itemsize <= _BLOCK_BYTES]
    return max(fits, default=128)


def hit_experts_first(hit: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``hit`` [E] bool -> (ids [E] int32, n [1] int32): the hit experts'
    ids ascending, the LAST repeated past ``n`` (none hit: zeros), by a
    compare of every place with every expert's rank: no sort."""
    E = hit.shape[0]
    each = jnp.arange(E, dtype=jnp.int32)
    rank = jnp.cumsum(hit, dtype=jnp.int32) - 1  # a hit expert's place in the list
    n = jnp.sum(hit, dtype=jnp.int32)
    place = jnp.minimum(each, jnp.maximum(n - 1, 0))
    at = hit[None, :] & (rank[None, :] == place[:, None])  # [place, expert]
    return jnp.sum(jnp.where(at, each[None, :], 0), axis=1), n.reshape(1)


def _moe_step_kernel(
    _m_ref, ids_ref, n_ref,  # scalar-prefetch (SMEM); the layer is the index maps'
    h_ref,  # [T, D]
    gates_ref,  # [T, E] float32: a row's weight for each held expert
    wg_ref, wu_ref,  # [D, tile] of expert ids[i]
    wd_ref,  # [tile, D]
    y_ref,  # [T, D]
    acc,  # [T, D] float32
):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _first():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(i < n_ref[0])
    def _hit():
        h = h_ref[...]
        g = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(h, wu_ref[...], preferred_element_type=jnp.float32)
        gates = gates_ref[...]
        of_expert = lax.broadcasted_iota(jnp.int32, gates.shape, 1) == ids_ref[i]
        w = jnp.sum(jnp.where(of_expert, gates, 0.0), axis=1, keepdims=True)  # [T, 1]
        act = (g * jax.nn.sigmoid(g) * u * w).astype(h.dtype)
        acc[...] += jnp.dot(act, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _last():
        y_ref[...] = acc[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_step_pallas(
    h: jax.Array,  # [T, D], normed: ALL of a step's rows
    gates: jax.Array,  # [T, E] float32: zero outside a row's chosen, and for an inactive row
    hit: jax.Array,  # [E] bool: the experts the REAL rows chose
    stack: dict,  # the STACKED leaves: w_gate, w_up [Lm, E, D, Fe]; w_down [Lm, E, Fe, D]
    m: Any,  # this layer's index in the stack (traced)
    *,
    interpret: bool = False,
) -> jax.Array:
    """``sum_{e hit} (silu(h W_gate[e]) * (h W_up[e]) * gates[:, e]) W_down[e]``
    of layer ``m`` -> [T, D] in ``h``'s type: ``moe.experts_dense`` where
    ``gates`` is zero for every expert not ``hit``, reading the hit alone."""
    T, D = h.shape
    Lm, E, _, Fe = stack["w_gate"].shape
    dtype = stack["w_gate"].dtype
    if not interpret and not moe_step_ok(D, Fe, dtype):
        raise PallasShapeError(
            f"the expert step kernel takes bfloat16 or float32 experts of whole lane "
            f"tiles: {D} x {Fe}, {dtype} is not (moe_step_ok)"
        )
    _note_trace("moe_step", interpret)
    itemsize = jnp.dtype(dtype).itemsize
    tile = _width_tile(D, Fe, itemsize)
    tiles = Fe // tile
    with jax.named_scope("group"):
        ids, n = hit_experts_first(hit)

    def of_tile(i, j, n_ref):  # a step past the hit names the block before it
        return jnp.where(i < n_ref[0], j, tiles - 1)

    up_spec = pl.BlockSpec(
        (None, None, D, tile),
        lambda i, j, m_ref, ids_ref, n_ref: (m_ref[0], ids_ref[i], 0, of_tile(i, j, n_ref)))
    down_spec = pl.BlockSpec(
        (None, None, tile, D),
        lambda i, j, m_ref, ids_ref, n_ref: (m_ref[0], ids_ref[i], of_tile(i, j, n_ref), 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, j, *_: (0,) * len(shape))  # noqa: E731
    held = 6 * D * tile * itemsize + T * D * (4 * h.dtype.itemsize + 4) + 2 * T * max(E, 128) * 4
    return pl.pallas_call(
        _moe_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, tiles),
            in_specs=[whole(T, D), whole(T, E), up_spec, up_spec, down_spec],
            out_specs=whole(T, D),
            scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, held + (8 << 20)),
        ),
        interpret=interpret,
        name="experts",  # its scope: the caller's ``moe`` and this, as the dense form's products
    )(
        jnp.asarray(m, jnp.int32).reshape(1), ids, n,
        h, gates.astype(jnp.float32), stack["w_gate"], stack["w_up"], stack["w_down"],
    )
