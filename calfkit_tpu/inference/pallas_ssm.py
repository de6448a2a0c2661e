"""The Pallas TPU kernel for the Mamba-2 decode step's pass over the state.

Why a kernel: XLA writes the updated state through a ``dynamic-update-slice``
root into the stacked state and will not give that fusion a second, reduced
output, so the readout ``y = S' C`` is a fusion of its own that reads the
layer's state AGAIN: three passes over 4.83 GB a decode step where two are
the work (PERF.md section 6, PRs 27 and 30).  Here a layer's state is read
ONCE and written ONCE, and ``y`` comes out of the same pass.

:func:`ssm_step_pallas` takes the WHOLE stacked state ``[Lm, B, H, P, N]``
and the layer index as a prefetched scalar; the state stays in HBM, goes
out where it came in (``input_output_aliases``) and is never sliced or
copied.  One program walks the ACTIVE rows of that layer (a row that is not
``active`` is neither read nor written: its state keeps every bit, its
``y`` is zero, and it costs nothing), a row in a few pieces, each piece
copied into VMEM, advanced there and copied back to where it lay, with
several reads and writes in flight under the arithmetic.  In VMEM, chunk
by chunk of 128 (head, p) lines, in float32:

    S' = S * exp(dt A) + (dt x) (x) B        y = sum_n S' C

The skip term ``D x`` and everything around the state (the conv, the
projections, the gated norm) stay the XLA they are.

A state line is ``N`` lanes wide and the (head, p) lines lie along the
sublanes, so ``dt x`` has to stand in a COLUMN, one value a sublane, while
it arrives lane-dense, and ``y`` has to leave lane-dense while the
reduction over ``N`` leaves it in a column.  Both turns are a select on the
diagonal of a ``[128, 128]`` tile and a reduction, which are exact.  (A
tile transpose does the same turn and measured slower: PERF.md section 6.)

Who chooses it: ``InferenceEngine._resolved_ssm_impl``, once at
construction, beside ``_resolved_attn_impl`` and under the same
``attention_impl`` values: the kernel on a TPU, one device, a float32 state
of whole tiles (:func:`ssm_step_in_place_ok`); else ``mamba.ssm_step_xla``,
which is the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from calfkit_tpu.inference.pallas_attention import PallasShapeError, _note_trace

_CHUNK = 128  # (head, p) lines worked at a time: a [128, N] slab of a row
# chunks of a row in one copy, and reads started ahead of the arithmetic
# (twice as many pieces are held): 512 KB pieces of granite-4.0-h-micro's
# 2 MB row, 4 MB of VMEM.  Measured flat from 128 KB x 8 to 2 MB x 2 on the
# v5e: the copies, not the arithmetic, are the time (PERF.md section 6)
_PIECE_CHUNKS = 8
_READS_AHEAD = 4
# chunks in one turn of the loop over a piece: two keep the arithmetic under
# the copies (one: 14.8 ms a step where two read 14.0); all eight, unrolled,
# bought nothing more and cost every program that holds the kernel 0.7-1 s
# of lowering, 36 s of a run's set-up (PERF.md section 6)
_UNROLL = 2


def ssm_step_in_place_ok(n_heads: int, n_groups: int, d_head: int, d_state: int, dtype) -> bool:
    """Whether :func:`_ssm_step_kernel` can take this state on a TPU: a
    float32 state whose lines are whole lane tiles (``d_state % 128``),
    whose heads are whole sublane tiles that divide a chunk of 128 lines or
    are whole chunks (8, 16, 32, 64, 128, 256...: a chunk's decay is a few
    heads' or one head's), and whose groups are whole chunks (a chunk reads
    ONE group's ``B`` and ``C``).  What fails this keeps the XLA body."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and d_state % 128 == 0
        and d_head % 8 == 0
        and (_CHUNK % d_head == 0 or d_head % _CHUNK == 0)
        and (n_heads // n_groups * d_head) % _CHUNK == 0
    )


def active_rows_first(active: jax.Array | None, rows: int) -> tuple[jax.Array, jax.Array]:
    """(order [rows] int32, n [1] int32): the ACTIVE rows first, in their
    order, and how many they are: what a kernel that walks them prefetches
    (this one and ``pallas_gdn.py``'s).  None: every row."""
    if active is None:
        return jnp.arange(rows, dtype=jnp.int32), jnp.full((1,), rows, jnp.int32)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(jnp.int32)
    return order, jnp.sum(active, dtype=jnp.int32).reshape(1)


def _ssm_step_kernel(
    layer_ref, order_ref, n_ref,  # scalar-prefetch (SMEM)
    _state_in,  # the state in HBM: the SAME buffer as ``state`` below
    a_ref,  # [B, 1, H] exp(dt A)
    u_ref,  # [B, H * P / T, T] dt x of each line, lane-dense
    b_ref, c_ref,  # [B, G, N]
    state,  # [Lm, B, H * P, N] in HBM, read and written through this name
    y_ref,  # [B, H * P / T, T]
    buf, read_sems, write_sems,
    *, pieces: int, head_lines: int, group_lines: int, unroll: int,
):
    slots, piece_lines, _ = buf.shape
    ahead = slots // 2
    T = u_ref.shape[2]
    layer = layer_ref[0]
    n_items = n_ref[0] * pieces  # (active row, piece of it), in order
    on_diagonal = (
        lax.broadcasted_iota(jnp.int32, (T, T), 0)
        == lax.broadcasted_iota(jnp.int32, (T, T), 1)
    )
    line = lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    head_lane = lax.broadcasted_iota(jnp.int32, (1, a_ref.shape[2]), 1)

    def lies_at(i):
        return state.at[
            layer, order_ref[i // pieces], pl.ds((i % pieces) * piece_lines, piece_lines)
        ]

    def read(i, slot):
        return pltpu.make_async_copy(lies_at(i), buf.at[slot], read_sems.at[slot])

    def write(i, slot):
        return pltpu.make_async_copy(buf.at[slot], lies_at(i), write_sems.at[slot])

    @pl.when(n_ref[0] < y_ref.shape[0])
    def _rows_that_stand():
        y_ref[...] = jnp.zeros_like(y_ref)

    for i in range(ahead):  # static
        @pl.when(i < n_items)
        def _first():
            read(i, i).start()

    def decay_of(k, row):
        """[T, 1]: each line of chunk k its head's decay."""
        a_row = a_ref[row]  # [1, H]

        def of_head(h):
            return jnp.max(
                jnp.where(head_lane == h, a_row, -jnp.inf), axis=1, keepdims=True
            )

        if head_lines >= T:
            return of_head((k * T) // head_lines)
        a = jnp.zeros((T, 1), jnp.float32)
        for e in range(T // head_lines):  # static: the heads of a chunk
            mine = (line >= e * head_lines) & (line < (e + 1) * head_lines)
            a = jnp.where(mine, of_head(k * (T // head_lines) + e), a)
        return a

    def item(i, carry):
        slot = i % slots
        row = order_ref[i // pieces]
        read(i, slot).wait()

        def chunk(j):
            k = (i % pieces) * (piece_lines // T) + j  # of the row
            g = (k * T) // group_lines
            at = pl.ds(pl.multiple_of(j * T, T), T)
            # dt x from a lane-dense row into a column: one value a sublane
            u = jnp.sum(
                jnp.where(on_diagonal, u_ref[row, pl.ds(k, 1), :], 0.0),
                axis=1, keepdims=True,
            )
            new = buf[slot, at, :] * decay_of(k, row) + u * b_ref[row, pl.ds(g, 1), :]
            y = jnp.sum(new * c_ref[row, pl.ds(g, 1), :], axis=1, keepdims=True)
            y_ref[row, pl.ds(k, 1), :] = jnp.sum(  # and y back into a row
                jnp.where(on_diagonal, y, 0.0), axis=0, keepdims=True
            )
            buf[slot, at, :] = new

        def chunks(jj, carry):
            for j in range(unroll):
                chunk(jj * unroll + j)
            return carry

        lax.fori_loop(0, piece_lines // T // unroll, chunks, None)
        write(i, slot).start()
        coming = i + ahead

        @pl.when(coming < n_items)
        def _next():
            @pl.when(coming >= slots)
            def _its_slot_is_free():
                write(coming - slots, coming % slots).wait()

            read(coming, coming % slots).start()

        return carry

    lax.fori_loop(0, n_items, item, None)
    for slot in range(slots):  # the writes nothing waited for yet
        @pl.when(slot < n_items)
        def _last():
            write(0, slot).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step_pallas(
    all_ssm: jax.Array,  # [Lm, B, H, P, N] float32, the WHOLE stacked state
    im: jax.Array,  # scalar int32: which layer's slice
    decay: jax.Array,  # [B, G, E] exp(dt A)
    dtx: jax.Array,  # [B, G, E, P] dt x
    Bm: jax.Array,  # [B, G, N]
    Cm: jax.Array,  # [B, G, N]
    active: jax.Array | None,  # [B] bool; None: every row advances
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of layer ``im`` over the stacked state -> (y [B, G,
    E, P] float32 without the skip term, the state with that layer's slice
    advanced).  ``mamba.ssm_step_xla`` argument for argument, but for the
    ``y`` of a row that is not active: zero here (the row is not read),
    what the row would have given there; no caller uses either.  The state
    is aliased in to out: a caller that donates it gets it back in place,
    every other layer's slice untouched."""
    Lm, B, H, P, N = all_ssm.shape
    G = Bm.shape[1]
    if not interpret and not ssm_step_in_place_ok(H, G, P, N, all_ssm.dtype):
        raise PallasShapeError(
            f"the SSM step kernel takes a float32 state of whole tiles: "
            f"{H} heads in {G} group(s), d_head {P}, d_state {N}, "
            f"{all_ssm.dtype} is not (ssm_step_in_place_ok)"
        )
    _note_trace("ssm_step", interpret)
    lines = H * P
    group_lines = lines // G
    # 128 wherever the compiled kernel runs; a toy shape (interpreted) is
    # worked a group at a time
    T = _CHUNK if group_lines % _CHUNK == 0 else group_lines
    chunks = lines // T
    piece = max(d for d in range(1, _PIECE_CHUNKS + 1) if chunks % d == 0)
    unroll = max(d for d in range(1, _UNROLL + 1) if piece % d == 0)
    f32 = jnp.float32
    order, n = active_rows_first(active, B)
    in_vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slots = 2 * _READS_AHEAD
    new_ssm, y = pl.pallas_call(
        functools.partial(
            _ssm_step_kernel, pieces=chunks // piece, head_lines=P, group_lines=group_lines,
            unroll=unroll,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[in_hbm, in_vmem, in_vmem, in_vmem, in_vmem],
            out_specs=[in_hbm, in_vmem],
            scratch_shapes=[
                pltpu.VMEM((slots, piece * T, N), f32),
                pltpu.SemaphoreType.DMA((slots,)),
                pltpu.SemaphoreType.DMA((slots,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Lm, B, lines, N), all_ssm.dtype),
            jax.ShapeDtypeStruct((B, chunks, T), f32),
        ),
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=32 << 20,
        ),
        interpret=interpret,
        name="ssm",
    )(
        jnp.asarray(im, jnp.int32).reshape(1), order, n,
        all_ssm.reshape(Lm, B, lines, N),
        decay.astype(f32).reshape(B, 1, H), dtx.astype(f32).reshape(B, chunks, T),
        Bm.astype(f32), Cm.astype(f32),
    )
    return y.reshape(dtx.shape), new_ssm.reshape(all_ssm.shape)
