"""The Pallas TPU kernel for the gated delta rule's decode step: one pass
over a row's ``S``.

Why a kernel: the update ``S' = exp(g) S + k (x) delta`` needs ``delta =
beta (v - (exp(g) S)^T k)``, a reduction over ALL of a head's decayed ``S``,
before it can write one number of it.  XLA reads the layer's ``S`` for that
reduction, reads it AGAIN for the update (and ``S_old`` for the ``active``
select) and writes it, over every slot whatever takes part: three passes
over 1.57 GB a decode step of the Ling cell where one read and one write of
the rows that took part are the work (PERF.md section 6, PR 42).  A fusion
cannot hold a head's ``[d_k, d_v]`` tile between the reduction and the
update; a kernel can.  This is ``pallas_ssm.py``'s plan (PR 30), which the
chip had proved for Mamba-2's step.

:func:`delta_step_pallas` takes the WHOLE stacked state ``[Lg, B, Hv, d_k,
d_v]`` and the layer index as a prefetched scalar; the state stays in HBM,
goes out where it came in (``input_output_aliases``) and is never sliced or
copied.  One program walks the ACTIVE rows of that layer (a row that is not
``active`` is neither read nor written: its state keeps every bit, its ``o``
is zero, and it costs nothing), a row in a few pieces of whole heads, each
piece copied into VMEM, advanced there and copied back to where it lay, with
several reads and writes in flight under the arithmetic.  In VMEM, a head
at a time, in float32 on the VPU, in ``gdn.delta_step_xla``'s order:

    Sd = exp(g) S (by key ROW)      u = Sd^T k      oq = Sd^T q
    delta = (v - u) beta            S' = Sd + k (x) delta
    o = oq + (k . q) delta          (= S'^T q without reading S' again)

A head's tile is ``d_v`` lanes wide and its key rows lie along the
sublanes, so ``exp(g)``, ``k`` and ``q`` have to stand in a COLUMN, one
value a sublane, while they arrive lane-dense: each turn is a select on the
diagonal of a ``[d_k, d_k]`` tile and a reduction, which is exact.  ``u``,
``delta`` and ``o`` are lane-dense rows as they are.  What one value a HEAD
scales (``beta``, ``k . q``) arrives as a row of ``d_v`` equal numbers.

One kernel for both delta-rule mixers: a decay by head (Gated DeltaNet, ``g``
[B, Hv]) is broadcast over the head's key rows by the wrapper, which is
bit-equal to the head's scale, and the body knows the channel form alone
(Kimi Delta Attention, ``g`` [B, Hv, d_k]).

Who chooses it: ``InferenceEngine._resolved_ssm_impl``, once at
construction, beside ``_resolved_attn_impl`` and under the same
``attention_impl`` values: the kernel on a TPU, one device, a float32 state
of whole tiles (:func:`delta_step_in_place_ok`); else
``gdn.delta_step_xla``, which is the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from calfkit_tpu.inference.pallas_attention import PallasShapeError, _note_trace
from calfkit_tpu.inference.pallas_ssm import active_rows_first

# whole heads of a row in one copy, and reads started ahead of the arithmetic
# (twice as many pieces are held): 8 heads of 128 x 128, 512 KB pieces of
# both cells' 2 MB row, 4 MB of VMEM, as pallas_ssm.py's (PERF.md section 6)
_PIECE_BYTES = 512 << 10
_READS_AHEAD = 4
# ONE head a turn of the loop over a piece: the copies are the time (a body
# without arithmetic read the same 5.00 ms a step of the Ling cell's shape as
# this one, and two or four heads a turn the same), and a longer body only
# lengthens the lowering that EVERY program holding the kernel pays


def delta_step_in_place_ok(n_heads: int, d_k: int, d_v: int, dtype) -> bool:
    """Whether :func:`_delta_step_kernel` can take this state on a TPU: a
    float32 state whose heads are whole tiles (``d_v`` whole lane tiles,
    ``d_k`` whole sublane tiles).  What fails this keeps the XLA body."""
    return jnp.dtype(dtype) == jnp.float32 and n_heads > 0 and d_v % 128 == 0 and d_k % 8 == 0


def _delta_step_kernel(
    layer_ref, order_ref, n_ref,  # scalar-prefetch (SMEM)
    _state_in,  # the state in HBM: the SAME buffer as ``state`` below
    decay_ref, k_ref, q_ref,  # [B, Hv, d_k] exp(g), k, q: lane-dense
    v_ref, beta_ref, kq_ref,  # [B, Hv, d_v] v; beta and k . q of the head, d_v times
    state,  # [Lg, B, Hv * d_k, d_v] in HBM, read and written through this name
    o_ref,  # [B, Hv, d_v]
    buf, read_sems, write_sems,
    *, pieces: int,
):
    slots, piece_lines, _ = buf.shape
    ahead = slots // 2
    dk = k_ref.shape[2]
    piece_heads = piece_lines // dk
    layer = layer_ref[0]
    n_items = n_ref[0] * pieces  # (active row, piece of it), in order
    on_diagonal = (
        lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        == lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    )

    # lax.div / lax.rem, not ``//`` / ``%``: every one of those is a jit and a
    # sign rule for Mosaic to lower again in EVERY program that holds the kernel
    def lies_at(i):
        return state.at[
            layer, order_ref[lax.div(i, pieces)],
            pl.ds(lax.rem(i, pieces) * piece_lines, piece_lines),
        ]

    def read(i, slot):
        return pltpu.make_async_copy(lies_at(i), buf.at[slot], read_sems.at[slot])

    def write(i, slot):
        return pltpu.make_async_copy(buf.at[slot], lies_at(i), write_sems.at[slot])

    @pl.when(n_ref[0] < o_ref.shape[0])
    def _rows_that_stand():
        o_ref[...] = jnp.zeros_like(o_ref)

    def first(i, carry):
        read(i, i).start()
        return carry

    lax.fori_loop(0, jnp.minimum(ahead, n_items), first, None)

    def item(i, carry):
        slot = lax.rem(i, slots)
        row = order_ref[lax.div(i, pieces)]
        first_head = lax.rem(i, pieces) * piece_heads  # of the piece, in the row
        read(i, slot).wait()

        def head(j, carry):
            h = pl.ds(first_head + j, 1)
            at = pl.ds(pl.multiple_of(j * dk, dk), dk)

            def column(ref):  # a lane-dense row [1, d_k] -> one value a sublane
                return jnp.sum(
                    jnp.where(on_diagonal, ref[row, h, :], 0.0), axis=1, keepdims=True
                )

            k = column(k_ref)
            Sd = buf[slot, at, :] * column(decay_ref)
            u = jnp.sum(Sd * k, axis=0, keepdims=True)  # [1, d_v]
            oq = jnp.sum(Sd * column(q_ref), axis=0, keepdims=True)
            delta = (v_ref[row, h, :] - u) * beta_ref[row, h, :]
            buf[slot, at, :] = Sd + k * delta
            o_ref[row, h, :] = oq + kq_ref[row, h, :] * delta
            return carry

        lax.fori_loop(0, piece_heads, head, None)
        write(i, slot).start()
        coming = i + ahead

        @pl.when(coming < n_items)
        def _next():
            its_slot = lax.rem(coming, slots)

            @pl.when(coming >= slots)
            def _is_free():
                write(coming - slots, its_slot).wait()

            read(coming, its_slot).start()

        return carry

    lax.fori_loop(0, n_items, item, None)

    def last(slot, carry):  # the writes nothing waited for yet (a wait reads the size alone)
        write(slot, slot).wait()
        return carry

    lax.fori_loop(0, jnp.minimum(slots, n_items), last, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_step_pallas(
    all_S: jax.Array,  # [Lg, B, Hv, d_k, d_v] float32, the WHOLE stacked state
    im: jax.Array,  # scalar int32: which layer's slice
    q: jax.Array,  # [B, Hv, d_k]
    k: jax.Array,  # [B, Hv, d_k]
    v: jax.Array,  # [B, Hv, d_v]
    beta: jax.Array,  # [B, Hv]
    g: jax.Array,  # [B, Hv] log decay, <= 0; or one a key channel [B, Hv, d_k]
    active: jax.Array | None,  # [B] bool; None: every row advances
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of layer ``im`` over the stacked state -> (o [B, Hv,
    d_v] float32, the state with that layer's slice advanced).
    ``gdn.delta_step_xla`` argument for argument, but for the ``o`` of a row
    that is not active: zero here (the row is not read), what the row would
    have given there; no caller uses either.  The state is aliased in to
    out: a caller that donates it gets it back in place, every other
    layer's slice untouched."""
    Lg, B, Hv, dk, dv = all_S.shape
    if not interpret and not delta_step_in_place_ok(Hv, dk, dv, all_S.dtype):
        raise PallasShapeError(
            f"the delta step kernel takes a float32 state of whole tiles: "
            f"{Hv} heads of d_k {dk} x d_v {dv}, {all_S.dtype} is not "
            "(delta_step_in_place_ok)"
        )
    _note_trace("delta_step", interpret)
    f32 = jnp.float32
    head_bytes = dk * dv * 4
    piece = max(d for d in range(1, Hv + 1) if Hv % d == 0 and d * head_bytes <= max(
        _PIECE_BYTES, head_bytes))
    decay = jnp.exp(g.astype(f32))
    if decay.ndim == 2:  # one decay a head: every key row of it scales alike
        decay = jnp.broadcast_to(decay[..., None], (B, Hv, dk))
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    of_head = lambda x: jnp.broadcast_to(x.astype(f32)[..., None], (B, Hv, dv))  # noqa: E731
    order, n = active_rows_first(active, B)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slots = 2 * _READS_AHEAD
    # the rows' inputs and ``o`` stand whole in VMEM beside the pieces
    held = 4 * B * Hv * (3 * dk + 4 * dv) + slots * piece * head_bytes
    new_S, o = pl.pallas_call(
        functools.partial(_delta_step_kernel, pieces=Hv // piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[in_hbm] + [in_vmem] * 6,
            out_specs=[in_hbm, in_vmem],
            scratch_shapes=[
                pltpu.VMEM((slots, piece * dk, dv), f32),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Lg, B, Hv * dk, dv), all_S.dtype),
            jax.ShapeDtypeStruct((B, Hv, dv), f32),
        ),
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, held + (8 << 20)),
        ),
        interpret=interpret,
        name="state",
    )(
        jnp.asarray(im, jnp.int32).reshape(1), order, n,
        all_S.reshape(Lg, B, Hv * dk, dv),
        decay, k, q, v, of_head(beta), of_head(jnp.sum(k * q, axis=-1)),
    )
    return o, new_S.reshape(all_S.shape)
