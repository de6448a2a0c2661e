"""The paged decode kernel of K and V pairs and the ONE place that chooses it
(a latent pool's body: tests/test_latent_decode_attention.py).

- The paged decode ATTENTION kernel's corners (PRs 25 and 28;
  ``pallas_attention._paged_decode_kernel``, interpret mode): row lengths
  around a page edge, rows that read nothing, shared tables, the proof
  that a dead page is never read, the shape rule, the pool's stored form
  (``model.positions_per_row``: f = 1, 2 and 4 positions a row).
- The ORDERS OF ROWS its one copy pipeline can get wrong (PR 55: a row's
  first block is started by the row before it): empty rows first, last,
  between and everywhere, one row alone, walks of one whole block and of a
  partial last one, in the plain form and in the window form on wrapped
  rings; and the same orders under the TPU interpreter, where a buffer no
  copy wrote is NaN, a copy lands only when it is waited for, a copy left
  unwaited is an error and a wait for one never started is a time-out.
- The selector (``InferenceEngine._resolved_attn_impl``, PR 29): an
  explicit kernel request outside the rule is refused at construction,
  and under ``pallas_interpret`` no program of a paged engine builds any
  kernel but the paged decode read: prefill, chunks, verify and the
  prefix-cache seed read through XLA, token for token with ``"xla"``.
"""

import asyncio
from dataclasses import replace

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from calfkit_tpu.inference import model as M  # noqa: E402
from calfkit_tpu.inference import pallas_attention as PA  # noqa: E402
from calfkit_tpu.inference.config import (  # noqa: E402
    RuntimeConfig,
    SpecConfig,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine  # noqa: E402

# --------------------------------------------------------------------------- #
# the paged decode attention kernel (pallas_attention._paged_decode_kernel),
# interpret mode, at the head shapes of Mistral (K 8, G 4, hd 128), granite
# (K 8, G 4, hd 64) and TinyLlama (K 4, G 8, hd 64), and at a head of 32;
# page 64.  The pool comes as it is STORED (model.positions_per_row): heads
# of 64 two positions a row, heads of 32 four, heads of 128 as declared.
# --------------------------------------------------------------------------- #

PD_WIDTHS = {"mistral": (8, 4, 128), "granite": (8, 4, 64), "tinyllama": (4, 8, 64),
             "heads-of-32": (4, 4, 32)}
PD_PAGE, PD_WPAGES, PD_PMAX = 64, 4, 6
PD_WINDOW = PD_WPAGES * PD_PAGE

# name -> (row lengths, rows that are inactive, (row, row) sharing a table)
PAGED_DECODE_CASES = {
    "len-0": ([0], (), None),
    "len-1": ([1], (), None),
    "page-minus-1": ([PD_PAGE - 1], (), None),
    "page": ([PD_PAGE], (), None),
    "page-plus-1": ([PD_PAGE + 1], (), None),
    "odd": ([33, 191], (), None),
    "full-window": ([PD_WINDOW], (), None),
    "mixed": ([0, 1, PD_PAGE - 1, PD_PAGE, PD_PAGE + 1, PD_WINDOW, 130, 17], (), None),
    "inactive-row-on-trash-page": ([70, 100, 9], (1,), None),
    "shared-table": ([150, 150, 40], (), (0, 1)),
    "one-row-partial-last-block": ([150], (), None),
    **{name: (lens, (), None) for name, lens in {
        # the orders of rows the copy pipeline walks (PR 55), four rows each
        "zero-between-live": [130, 0, 0, 70],
        "zero-first": [0, 0, 200, 64],
        "zero-last": [256, 10, 0, 0],
        "every-row-zero": [0, 0, 0, 0],
        "one-live-of-four": [0, 0, 129, 0],
        "walks-of-one-block": [128, 128, 100, 128],  # two pages: a block of 2 exactly
        "partial-last-blocks": [192, 65, 192, 129],
        "walks-of-one-page": [1, 64, 33, 2],  # the first copies reach three rows ahead
    }.items()},
}
# the cases of four rows: one pool size, so one program a (widths, block, form)
ROW_ORDERS = sorted(name for name, (lens, _, _) in PAGED_DECODE_CASES.items() if len(lens) == 4)
# the WINDOW form's law in these tests: a ring of PD_WPAGES entries, every live
# row wrapped (its length + RING_WRAP keys written), a window of RING_W
RING_W, RING_WRAP = 141, 300
FORMS = [(case, "plain") for case in sorted(PAGED_DECODE_CASES)] + [
    (case, "ring") for case in ROW_ORDERS]


def _paged_decode_case(name: str, dtype, seed: int = 0, widths: str = "mistral",
                       form: str = "plain"):
    """(q, pool_k, pool_v, tables, lens, live) for one named case: every
    row's pages are its own (page 0 is the trash page), an inactive row's
    table is all trash and its length 0 as ``decode_step_ring_paged``
    hands it down, a shared table is one row's copied to the other.

    ``form="ring"``: a live row's table is a RING of ``PD_WPAGES`` pages of
    its own, wrapped (``RING_WRAP`` keys more than the case's length were
    written), and ``live`` are the pages a window of ``RING_W`` reaches."""
    import jax.numpy as jnp
    import numpy as np

    K, G, hd = PD_WIDTHS[widths]
    lens, inactive, shared = PAGED_DECODE_CASES[name]
    lens = [0 if b in inactive else n for b, n in enumerate(lens)]
    B = len(lens)
    rng = np.random.default_rng(seed)
    ring = form == "ring"
    if ring:
        lens = [n + RING_WRAP if n else 0 for n in lens]
    if B == 4:  # ROW_ORDERS: every ring whole, whatever the lengths
        n_pages = 1 + B * PD_WPAGES + 3
    else:
        n_pages = 1 + sum(-(-n // PD_PAGE) for n in lens) + 3  # + unused pages
    tables = np.zeros((B, PD_PMAX), np.int32)
    live = np.zeros((n_pages,), bool)
    nxt = 1
    for b, n in enumerate(lens):
        if n == 0:
            continue
        own = PD_WPAGES if ring else -(-n // PD_PAGE)
        tables[b, :own] = np.arange(nxt, nxt + own)
        nxt += own
    if shared is not None:
        tables[shared[1]] = tables[shared[0]]
    for b, n in enumerate(lens):
        if ring and n:  # the ring's entries that hold [n - RING_W + 1, n)
            reached = np.arange(max(n - RING_W + 1, 0) // PD_PAGE, -(-n // PD_PAGE))
            live[tables[b, reached % PD_WPAGES]] = True
        else:
            live[tables[b, : -(-n // PD_PAGE)]] = True
    shape = (2, n_pages, K, PD_PAGE, hd)
    pool_k = rng.standard_normal(shape).astype(np.float32)
    pool_v = rng.standard_normal(shape).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, K, G, hd)), dtype)
    return (
        q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
        live,
    )


def _stored(pool_side):
    """A pool side declared ``[L, N, K, page, hd]`` as ``make_page_pool``
    stores it: ``[L, N, K, page / f, f * hd]``, the same numbers in the same
    order (``f`` = 1: the array itself)."""
    *lead, page, hd = pool_side.shape
    f = M.positions_per_row(hd, page, pool_side.dtype)
    return pool_side.reshape(*lead, page // f, f * hd)


def _paged_decode_both(q, pool_k, pool_v, tables, lens, *, pages_per_block, form="plain",
                       interpret=True):
    """The kernel (layer 1 of the whole pool AS STORED) beside the XLA law
    it replaces: ``masked_attention_source`` over each row's window, taken
    out of the DECLARED pool by plain indexing (``form="ring"``: over the
    row's whole ring, under ``_window_ring_valid``)."""
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M
    from calfkit_tpu.inference.pallas_attention import (
        paged_decode_attention_pallas,
    )

    ring = form == "ring"
    got = paged_decode_attention_pallas(
        q, _stored(pool_k), _stored(pool_v), jnp.int32(1), tables[:, :PD_WPAGES] if ring else tables,
        lens, wpages=PD_WPAGES, interpret=interpret, pages_per_block=pages_per_block,
        **({"window_starts": jnp.maximum(lens - RING_W + 1, 0)} if ring else {}),
    )
    if ring:
        valid = M._window_ring_valid(PD_WINDOW, lens, lens, RING_W)
    else:
        valid = jnp.arange(PD_WINDOW)[None, :] < lens[:, None]

    def window(side):  # [N, K, page, hd] -> [B, K, wp * page, hd]
        rows = jnp.moveaxis(side[tables[:, :PD_WPAGES]], 2, 1)
        return rows.reshape(*rows.shape[:2], PD_WINDOW, -1)

    o, m, z = M.masked_attention_source(q, window(pool_k[1]), window(pool_v[1]), valid)
    return got, (o, m[..., 0], z[..., 0])


class TestPagedDecodeKernelCorners:
    @pytest.mark.parametrize("pages_per_block", [1, 2])
    @pytest.mark.parametrize("case,form", FORMS)
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_matches_gathered_window(self, widths, case, form, pages_per_block):
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            case, jnp.float32, widths=widths, form=form
        )
        got, want = _paged_decode_both(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), tables, lens,
            pages_per_block=pages_per_block, form=form,
        )
        for name, g, w in zip("omz", got, want):
            # one pass over the window against a block at a time: the
            # same sums in another order
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
                err_msg=f"{case}: {name} diverged",
            )
        # a row that reads nothing stays finite at the floor
        empty = np.asarray(lens) == 0
        assert (np.asarray(got[1])[empty] == np.float32(-1e29)).all()
        assert (np.asarray(got[2])[empty] == 0).all()

    @pytest.mark.parametrize("case", ["mixed", "shared-table"])
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_bf16_operands_f32_accumulation(self, widths, case):
        """The configuration's precision: bf16 q, K, V into the products,
        float32 scores, statistics and accumulator."""
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            case, jnp.bfloat16, widths=widths
        )
        got, want = _paged_decode_both(
            q, jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16), tables, lens,
            pages_per_block=2,
        )
        assert all(a.dtype == jnp.float32 for a in got)
        norm = lambda o, m, z: np.asarray(o / jnp.maximum(z[..., None], 1e-30))
        # p is rounded to bf16 against a running maximum here and against
        # the row's own there: agreement to bf16's 8 bits, not float32's
        np.testing.assert_allclose(norm(*got), norm(*want), atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(got[1]), np.asarray(want[1]), rtol=1e-5
        )

    @pytest.mark.parametrize("pages_per_block", [1, 2, 4])
    @pytest.mark.parametrize(
        "case,form",
        [(case, "plain") for case in ("mixed", "inactive-row-on-trash-page", "shared-table")]
        + [(case, form) for case in ROW_ORDERS for form in ("plain", "ring")],
    )
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_dead_pages_are_never_read(self, widths, case, form, pages_per_block):
        """Every page no row's length reaches — the trash page, the tail of
        each table, the unused pages of the pool, both layers', in the window
        form the entries of a ring behind the window's lower bound — is NaN;
        the result is finite and equal to the clean pool's: a first block
        started on the wrong row's behalf would bring one in.  The XLA
        gather reads them all (and masks them), so it gets the clean pool."""
        import jax.numpy as jnp
        import numpy as np

        q, pool_k, pool_v, tables, lens, live = _paged_decode_case(
            case, jnp.float32, seed=5, widths=widths, form=form
        )
        dirty_k, dirty_v = pool_k.copy(), pool_v.copy()
        dirty_k[:, ~live] = np.nan
        dirty_v[:, ~live] = np.nan
        dirty_k[0] = dirty_v[0] = np.nan  # another layer's pages
        got, _ = _paged_decode_both(
            q, jnp.asarray(dirty_k), jnp.asarray(dirty_v), tables, lens,
            pages_per_block=pages_per_block, form=form,
        )
        clean, want = _paged_decode_both(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), tables, lens,
            pages_per_block=pages_per_block, form=form,
        )
        for g, c, w in zip(got, clean, want):
            assert np.isfinite(np.asarray(g)).all()
            np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5
            )

    @pytest.mark.parametrize("slots", [0, 2])
    @pytest.mark.parametrize("form", ["plain", "ring"])
    @pytest.mark.parametrize("case", ROW_ORDERS)
    def test_rows_follow_one_another_in_one_copy_pipeline(self, case, form, slots):
        """The orders of rows under the TPU interpreter (``pltpu.InterpretParams``),
        which keeps the copies' books as a chip does: a buffer slot no copy
        wrote is NaN, a copy LANDS only when it is waited for (so a block
        computed without its own wait reads NaN or the slot's last tenant),
        two accesses no wait orders are a race, a copy started and never
        waited for fails the call at its end, and a wait for a copy nobody
        started never returns, which is a time-out here and not a hang.
        Dead pages are NaN as above.  Heads of 128 on pages of 16, two pages
        a block: four slots of a block by the rule, and two."""
        import threading

        import jax.numpy as jnp
        import numpy as np
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu_interpreter
        from jax.experimental.pallas import tpu as pltpu

        page, K, G, hd = 16, 2, 2, 128
        scale = PD_PAGE // page  # the cases' lengths are in pages of 64
        q, pool_k, pool_v, tables, lens, live = _paged_decode_case(
            case, jnp.float32, seed=7, form=form)
        rng = np.random.default_rng(11)
        pool_k, pool_v = (rng.standard_normal((2, len(live), K, page, hd)).astype(np.float32)
                          for _ in range(2))
        pool_k[:, ~live] = pool_v[:, ~live] = np.nan
        pool_k[0] = pool_v[0] = np.nan
        q = q[:, :K, :G]
        lens = -(-lens // scale)  # the same walks in pages, a quarter the keys
        window = -(-RING_W // scale)
        more = {"window_starts": jnp.maximum(lens - window + 1, 0)} if form == "ring" else {}
        if form == "ring":  # the pages the shorter window reaches are among the case's
            reached = np.zeros_like(live)
            for b, n in enumerate(np.asarray(lens)):
                if n:
                    at = np.arange(max(n - window + 1, 0) // page, -(-n // page))
                    reached[np.asarray(tables)[b, at % PD_WPAGES]] = True
            assert not (reached & ~live).any()
        call = lambda interpret: PA.paged_decode_attention_pallas(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.int32(1), tables[:, :PD_WPAGES],
            lens, wpages=PD_WPAGES, interpret=interpret, slots=slots, **more)
        assert PA.paged_decode_slots(2 * 2 * K * page * hd * 4) == 4
        want = call(True)
        got = []

        def on_the_interpreter():
            PA.paged_decode_attention_pallas.clear_cache()
            with pltpu.force_tpu_interpret_mode(pltpu.InterpretParams(
                    dma_execution_mode="on_wait", detect_races=True)):
                got.extend(np.asarray(x) for x in call(False))

        tpu_interpreter.reset_tpu_interpret_mode_state()
        runner = threading.Thread(target=on_the_interpreter, daemon=True)
        runner.start()
        runner.join(60)
        PA.paged_decode_attention_pallas.clear_cache()
        assert not runner.is_alive(), "a wait for a copy that was never started"
        assert len(got) == 3, "the interpreter refused the call: a copy left unwaited?"
        assert not tpu_interpreter.races.races_found
        for g, w in zip(got, want):
            assert np.isfinite(g).all()
            np.testing.assert_array_equal(g, np.asarray(w))

    @pytest.mark.parametrize(
        "head_dim,page,dtype,ok",
        [
            (128, 64, "bfloat16", True),
            (128, 16, "bfloat16", True),
            (256, 8, "float32", True),
            (64, 64, "bfloat16", True),  # two positions a lane row (TinyLlama)
            (64, 32, "bfloat16", True),  # ... on a packed page of 16 rows
            (32, 64, "bfloat16", True),  # four positions a lane row
            (64, 16, "float32", True),
            (128, 8, "bfloat16", False),  # half a packed sublane tile
            (128, 16, "int8", False),
            (64, 16, "bfloat16", False),  # a packed page under a sublane tile
            (64, 8, "float32", False),
            (96, 64, "bfloat16", False),  # a head that does not divide 128
            (80, 64, "float32", False),
            (192, 64, "bfloat16", False),  # nor is whole lane tiles
        ],
    )
    def test_shape_rule(self, head_dim, page, dtype, ok):
        from calfkit_tpu.inference.pallas_attention import (
            paged_decode_in_place_ok,
        )

        assert paged_decode_in_place_ok(head_dim, page, dtype) is ok

    # (not "walks-of-one-page": the maximum over a row of ONE or two keys is a
    # score near 0, and the PARENT's kernel is 1.4e-5 of it off the XLA sum
    # there in bfloat16, past this test's relative limit; the float32 tests
    # above hold that case, and the kernel is the parent's bit for bit on it)
    @pytest.mark.parametrize(
        "case", ["mixed", *(c for c in ROW_ORDERS if c != "walks-of-one-page")])
    @pytest.mark.parametrize("widths", sorted(PD_WIDTHS))
    def test_the_kernel_on_the_stored_pool_is_the_xla_read_of_it(self, widths, case):
        """``make_page_pool`` stores a head narrower than a lane tile ``f``
        positions a row (f = 1, 2, 2, 4 at these widths), the same numbers
        in the same order; the kernel copies a page's stored rows whole and
        ``gather_window_paged`` takes its gathered rows apart: one pool,
        two readers, one result.  Nothing of the pool is relaid by either:
        the kernel's operand IS the stored array."""
        import jax.numpy as jnp
        import numpy as np

        from calfkit_tpu.inference import pallas_attention as PA

        K, G, hd = PD_WIDTHS[widths]
        f = {128: 1, 64: 2, 32: 4}[hd]
        assert M.positions_per_row(hd, PD_PAGE, jnp.bfloat16) == f
        config = replace(preset("debug"), d_model=K * G * hd, n_heads=K * G, n_kv_heads=K,
                         dtype="bfloat16")
        made = M.make_page_pool(config, 5, PD_PAGE)
        assert all(side.shape == (config.n_layers, 5, K, PD_PAGE // f, f * hd) for side in made)
        # what the engine's start-up line says of it: each side's stored shape and f
        from calfkit_tpu.inference.engine import _stored_layout

        side = f"bfloat16[{config.n_layers}, 5, {K}, {PD_PAGE // f}, {f * hd}] f={f}"
        assert _stored_layout(config, *made) == f"K {side}, V {side}"

        q, pool_k, pool_v, tables, lens, _ = _paged_decode_case(
            case, jnp.bfloat16, widths=widths
        )
        pool_k, pool_v = (jnp.asarray(side, jnp.bfloat16) for side in (pool_k, pool_v))
        stored_k, stored_v = _stored(pool_k), _stored(pool_v)
        # row r of a stored page: positions f * r .. f * r + f - 1 side by side
        np.testing.assert_array_equal(
            np.asarray(stored_k[1, 2, 3, 5], np.float32),
            np.asarray(pool_k[1, 2, 3, 5 * f:(5 + 1) * f], np.float32).ravel(),
        )
        # the XLA read of the stored pool is plain indexing of the declared one
        gathered = M.gather_window_paged(stored_k[1], tables, PD_WPAGES, hd)
        rows = jnp.moveaxis(pool_k[1][tables[:, :PD_WPAGES]], 2, 1)
        np.testing.assert_array_equal(
            np.asarray(gathered, np.float32),
            np.asarray(rows.reshape(*rows.shape[:2], PD_WINDOW, hd), np.float32))
        # and the kernel's read of it is the XLA read's, at bf16's 8 bits
        got = PA.paged_decode_attention_pallas(
            q, stored_k, stored_v, jnp.int32(1), tables, lens, wpages=PD_WPAGES, interpret=True)
        valid = jnp.arange(PD_WINDOW)[None, :] < lens[:, None]
        o, m, z = M.masked_attention_source(
            q, gathered, M.gather_window_paged(stored_v[1], tables, PD_WPAGES, hd), valid)
        norm = lambda o, z: np.asarray(o / jnp.maximum(z[..., None], 1e-30))
        np.testing.assert_allclose(norm(got[0], got[2]), norm(o, z[..., 0]), atol=2e-2)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(m[..., 0]), rtol=1e-5)

    def test_a_pool_outside_the_rule_is_stored_as_declared(self):
        """A page the packing would leave a partial sublane tile (a bf16 page
        of 16 at a head of 64: 8 rows), a head that does not divide a lane
        tile, and a latent pool's two parts: ``f`` = 1, the pool as declared."""
        import jax.numpy as jnp

        assert M.positions_per_row(64, 16, jnp.bfloat16) == 1
        assert M.positions_per_row(64, 16, jnp.float32) == 2
        assert M.positions_per_row(96, 64, jnp.bfloat16) == 1
        assert M.positions_per_row(256, 64, jnp.bfloat16) == 1
        assert M.positions_per_row(32, 64, jnp.float8_e4m3fn) == 1  # 16 rows under a tile of 32
        assert M.positions_per_row(32, 128, jnp.float8_e4m3fn) == 4
        small = M.make_page_pool(preset("debug"), 3, 16)  # heads of 16 on a page of 16
        assert small[0].shape == (2, 3, 2, 16, 16)
        latent = M.make_page_pool(preset("kimi-vl-a3b-instruct"), 3, 64)  # the rope side: 64 wide
        assert [side.shape[2:] for side in latent] == [(1, 64, 512), (1, 64, 64)]

    @pytest.mark.parametrize(
        "head_dim,page,dtype",
        [
            (64, 8, "float32"),  # a packed page of 4 rows, under a sublane tile
            (96, 64, "bfloat16"),  # a head that neither is nor divides a lane tile
            (128, 8, "bfloat16"),  # half a packed sublane tile
            (64, 64, "bfloat16"),  # a pool handed over as DECLARED where it is stored packed
        ],
    )
    def test_other_shapes_are_refused(self, head_dim, page, dtype):
        """Outside the shape rule there is no kernel, and a direct call
        says so while it is traced: nothing is built, nothing else runs.
        Nor is a pool relaid for the kernel: one that does not come in its
        stored form is refused."""
        import jax.numpy as jnp

        from calfkit_tpu.inference import pallas_attention as PA

        pool = jnp.zeros((1, 5, 2, page, head_dim), dtype)
        q = jnp.zeros((2, 2, 4, head_dim), dtype)
        tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
        lens = jnp.asarray([page + 3, page], jnp.int32)
        before = dict(PA.KERNEL_TRACES)
        with pytest.raises(PA.PallasShapeError, match="paged_decode_in_place_ok"):
            PA.paged_decode_attention_pallas(
                q, pool, pool, jnp.int32(0), tables, lens, wpages=2,
                interpret=True,
            )
        assert dict(PA.KERNEL_TRACES) == before  # nothing was built


# --------------------------------------------------------------------------- #
# the selector: one kernel, chosen in one place
# --------------------------------------------------------------------------- #


def _debug_config(**over):
    return replace(preset("debug"), **over)


# heads of 128 (K 1, G 2) / 64 / 96 / the debug preset's own 16
HEADS = {
    128: dict(d_model=256, n_heads=2, n_kv_heads=1),
    64: dict(d_model=256, n_heads=4, n_kv_heads=2),
    96: dict(d_model=192, n_heads=2, n_kv_heads=1),
    16: {},
}
# name -> (head_dim, dtype, what differs from the eligible engine): each is
# one clause of the rule of ``_resolved_attn_impl`` broken, the rest kept
OUTSIDE_THE_RULE = {
    "dense-layout": (128, "bfloat16", {"kv_layout": "dense"}),
    "tp-2": (128, "bfloat16", {"tp": 2}),
    "dp-2": (128, "bfloat16", {"dp": 2}),
    "heads-of-16": (16, "bfloat16", {}),
    "heads-of-96": (96, "bfloat16", {}),
    "page-of-4": (128, "float32", {"page_size": 4, "prefill_chunk": 16}),
    "bf16-page-of-8": (128, "bfloat16", {"page_size": 8}),
    # heads of 64 read two positions a row: a bf16 page of 16 is 8 rows
    "heads-of-64-bf16-page-of-16": (64, "bfloat16", {}),
}


def _rule_engine(head_dim, dtype, **over):
    rt = RuntimeConfig(**{
        "max_batch_size": 2, "max_seq_len": 128, "prefill_chunk": 16,
        "kv_layout": "paged", "page_size": 16, **over,
    })
    return InferenceEngine(_debug_config(dtype=dtype, **HEADS[head_dim]), rt)


@pytest.mark.parametrize("case", sorted(OUTSIDE_THE_RULE))
@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_explicit_kernel_outside_its_rule_is_refused(impl, case):
    """An explicit kernel request waives the platform test alone.  Outside
    the rest of the rule there is no kernel to build: the engine is refused
    at construction, by name, where it once built the kernels that lost."""
    head_dim, dtype, over = OUTSIDE_THE_RULE[case]
    before = dict(PA.KERNEL_TRACES)
    with pytest.raises(PA.PallasShapeError, match="paged_decode_in_place_ok"):
        _rule_engine(head_dim, dtype, attention_impl=impl, **over)
    assert dict(PA.KERNEL_TRACES) == before
    # "auto" and "xla" construct wherever they did, and read through XLA
    for other in ("auto", "xla"):
        engine = _rule_engine(head_dim, dtype, attention_impl=other, **over)
        assert engine._attn_impl == "xla"


def test_explicit_kernel_inside_its_rule_constructs():
    """The eligible engine the cases above each break one clause of."""
    for impl in ("pallas", "pallas_interpret"):
        assert _rule_engine(128, "bfloat16", attention_impl=impl)._attn_impl == impl
        assert _rule_engine(64, "float32", attention_impl=impl)._attn_impl == impl


@pytest.mark.parametrize(
    "fn,chooses",
    [
        ("forward", False),
        ("decode_step_ring", False),
        ("verify_step_ring", False),
        ("verify_step_ring_paged", False),
        ("decode_step_ring_paged", True),
    ],
)
def test_only_the_paged_decode_step_takes_an_implementation(fn, chooses):
    """``attn_impl`` reaches the one function that reads the pool for a
    decode step; prefill, chunks, dense decode and verify take none."""
    import inspect

    assert ("attn_impl" in inspect.signature(getattr(M, fn)).parameters) is chooses


WIDE = _debug_config(name="debug-wide", **HEADS[128])
REPEATS = [3, 4, 5, 6] * 6  # n-gram drafts hit

# more requests than slots, prompts across page edges.  Fixed prompts: on
# random weights greedy argmax can amplify a benign reordering of float sums
CROWD = [(list(range(1 + i, 20 + 3 * i)), 5 + 3 * i, {}) for i in range(7)]


def _built(engine, cache: str, head) -> bool:
    """Whether the engine built (so ran) a program whose key starts with
    ``head`` (a tag, or ``int`` for the untagged keys)."""
    return any(
        isinstance(key[0], head) if isinstance(head, type) else key[0] == head
        for key in getattr(engine, cache)
    )


# program -> (runtime overrides, jobs, whether the program under test ran)
PROGRAMS = {
    "overlap-decode-dispatch": (
        {}, [([1, 5, 9], 14, {})],
        lambda e: e.runtime.overlap_dispatch and _built(e, "_decode_jits", int)),
    "lockstep-decode-tick": (
        {"overlap_dispatch": False}, [([1, 5, 9], 14, {})],
        lambda e: _built(e, "_decode_jits", int)),
    "ragged-dispatch-with-chunk": (
        {}, CROWD,
        lambda e: e.stats.unified_dispatches > 0 and _built(e, "_decode_jits", "ragged")),
    "bifurcated-chunk-lane": (
        {"ragged_waves": False}, CROWD,
        lambda e: _built(e, "_prefill_jits", "chunk")
        and not _built(e, "_decode_jits", "ragged")),
    "unchunked-prefill-wave": (
        {"chunked_prefill": False},
        [(list(range(2, 21)), 9, {}), ([4, 4, 7], 6, {})],
        lambda e: _built(e, "_prefill_jits", int)),
    "prefix-cache-hit": (
        {"prefix_cache": True, "sequential": True},
        [(list(range(1, 41)), 5, {}), (list(range(1, 41)) + [9, 8], 6, {})],
        lambda e: e.stats.prefix_hits > 0 and _built(e, "_prefill_jits", "seed")),
    "ngram-speculation": (
        {"speculative": SpecConfig(k=3)},
        [(REPEATS, 12, {}), ([7, 7, 7, 7, 7, 7], 9, {})],
        lambda e: e.stats.spec_proposed > 0 and _built(e, "_decode_jits", "verify")),
}


@pytest.fixture(scope="module")
def wide_params():
    return M.init_params(WIDE, jax.random.key(1), dtype=jnp.float32)


async def _serve(params, jobs, sequential=False, **over):
    rt = RuntimeConfig(**{
        "max_batch_size": 4, "max_seq_len": 128, "prefill_chunk": 16,
        "decode_steps_per_dispatch": 4, "page_size": 16, "kv_layout": "paged",
        "chunked_prefill": True, **over,
    })
    engine = InferenceEngine(WIDE, rt, params=params)

    async def one(prompt, n, kw):
        return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]

    await engine.start()
    try:
        if sequential:
            return [await one(*job) for job in jobs], engine
        return await asyncio.gather(*[one(*job) for job in jobs]), engine
    finally:
        await engine.stop()


@pytest.mark.parametrize("program", sorted(PROGRAMS))
async def test_only_the_paged_decode_read_builds_a_kernel(wide_params, program):
    """A paged engine with eligible heads under ``pallas_interpret``: every
    program it runs is the ``"xla"`` engine's, token for token, and the
    paged decode read is the only kernel any of them traced."""
    over, jobs, ran = PROGRAMS[program]
    want, xla = await _serve(wide_params, jobs, attention_impl="xla", **over)
    # the entry point is a jit of its own: traced once a process a shape
    PA.paged_decode_attention_pallas.clear_cache()
    PA.KERNEL_TRACES.clear()
    got, pal = await _serve(
        wide_params, jobs, attention_impl="pallas_interpret", **over
    )
    assert got == want
    assert all(len(s) == n for s, (_, n, _) in zip(got, jobs))
    # a speculating engine's every tick is a verify dispatch, which reads
    # through XLA: it builds no kernel at all
    decodes = _built(pal, "_decode_jits", int) or _built(pal, "_decode_jits", "ragged")
    assert decodes == (program != "ngram-speculation")
    assert set(PA.KERNEL_TRACES) == (
        {("paged_decode", "interpreted")} if decodes else set()
    )
    assert ran(xla) and ran(pal), f"{program} never ran"


# --------------------------------------------------------------------------- #
# the pool's stored form (PR 49): a head narrower than a lane tile is stored
# f positions a row, and no request can tell
# --------------------------------------------------------------------------- #

# heads -> (the debug preset at that head, page, f): float32 pages of 8 stored rows
PACKED = {
    64: (_debug_config(name="debug-64", dtype="float32", **HEADS[64]), 16, 2),
    32: (_debug_config(name="debug-32", dtype="float32", d_model=128, n_heads=4, n_kv_heads=2),
         32, 4),
}


@pytest.fixture(scope="module")
def packed_params():
    return {hd: M.init_params(cfg, jax.random.key(2), dtype=jnp.float32)
            for hd, (cfg, _, _) in PACKED.items()}


@pytest.mark.parametrize("hd,program", [
    *[(64, program) for program in sorted(PROGRAMS)],
    (32, "ragged-dispatch-with-chunk"), (32, "prefix-cache-hit"), (32, "ngram-speculation"),
])
async def test_a_stored_pool_serves_what_the_declared_pool_served(
        monkeypatch, packed_params, hd, program):
    """Every program that reads or writes the pool (decode dispatches, ragged
    dispatches, the wave's landing, the prefix cache's seed, verify), on a
    pool stored ``f`` positions a row: token for token what the SAME engine
    serves with the rule switched off (``f`` = 1: the pool as declared, the
    parent's), through the XLA read and through the kernel in interpret
    mode on the stored pool."""
    config, page, f = PACKED[hd]
    over, jobs, ran = PROGRAMS[program]
    over = {**over, "page_size": page, "prefill_chunk": 32}

    async def serve(impl):
        rt = RuntimeConfig(**{
            "max_batch_size": 4, "max_seq_len": 128, "decode_steps_per_dispatch": 4,
            "kv_layout": "paged", "chunked_prefill": True, "attention_impl": impl,
            **{k: v for k, v in over.items() if k != "sequential"}})
        engine = InferenceEngine(config, rt, params=packed_params[hd])

        async def one(prompt, n, kw):
            return [t async for t in engine.generate(prompt, max_new_tokens=n, **kw)]

        await engine.start()
        try:
            if over.get("sequential"):
                return [await one(*job) for job in jobs], engine
            return await asyncio.gather(*[one(*job) for job in jobs]), engine
        finally:
            await engine.stop()

    with monkeypatch.context() as declared:
        declared.setattr(M, "positions_per_row", lambda width, page, dtype: 1)
        want, parent = await serve("xla")
    assert parent._k.shape[3:] == (page, hd)
    got, xla = await serve("xla")
    assert xla._k.shape[3:] == (page // f, f * hd) == (8, 128)
    assert got == want
    PA.paged_decode_attention_pallas.clear_cache()
    kernel, pal = await serve("pallas_interpret")
    assert pal._attn_impl == "pallas_interpret" and kernel == want
    assert ran(parent) and ran(xla) and ran(pal), f"{program} never ran"
