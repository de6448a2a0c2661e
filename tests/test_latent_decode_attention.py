"""The latent (MLA) decode kernel and the place that chooses it.

- The kernel's corners (PR 32; ``pallas_attention._latent_decode_kernel``,
  interpret mode) against the law it replaces, the first source of
  ``model.mla_merged_decode_attention`` over ``gather_window_paged``: ragged
  row lengths around a page edge, rows that read nothing, a shuffled block
  table, the proof that a dead page is never read, the shape rule, the rope
  side's view; at toy widths (128 | 64, 4 heads, pages of 16, float32) and
  at Kimi-VL-A3B's (512 | 64, 16 heads, pages of 64, bfloat16).
- The selector (``InferenceEngine._resolved_attn_impl``): a latent pool
  inside the rule builds THIS kernel and no other, and an engine at toy
  size under ``pallas_interpret`` serves the logits of the float32
  reference (``benchmarks/architectures/deepseek-mla-moe.py``) at the
  tolerance ``tests/test_mla_moe.py`` holds the XLA read to, through
  chunked prefill, prefix reuse and slot reuse.
"""

from __future__ import annotations

import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference import pallas_attention as PA
from calfkit_tpu.inference.engine import InferenceEngine

from test_mla_moe import generated
from tests.arch_harness import MLA_MOE as FAMILY  # the toy stack, its reference and its limit
from tests.arch_harness import Spy, both_forms_at_toy_size  # noqa: F401 - autouse here too

LOGIT_TOL, TOY = FAMILY.logit_tol, FAMILY.toy

# name -> (heads, r, dr, page, dtype)
WIDTHS = {
    "toy": (4, 128, 64, 16, jnp.float32),
    "kimi": (16, 512, 64, 64, jnp.bfloat16),
}
WPAGES, PMAX = 4, 6
SCALE = 1.0 / math.sqrt(128 + 64)  # 1 / sqrt(dn + dr), whatever is absorbed


def lengths(case: str, page: int) -> tuple[list[int], tuple[int, ...]]:
    """(row lengths, rows that are not active) of a named case."""
    window = WPAGES * page
    return {
        "len-0": ([0], ()),
        "len-1": ([1], ()),
        "page-minus-1": ([page - 1], ()),
        "page": ([page], ()),
        "page-plus-1": ([page + 1], ()),
        "partial-last-page": ([2 * page + page // 2 + 1, 3 * page - 1], ()),
        "full-window": ([window], ()),
        "mixed": ([0, 1, page - 1, page, page + 1, window, 2 * page + 2, 17 % window], ()),
        "inactive-row": ([page + 6, 2 * page, 9], (1,)),
    }[case]


CASES = ["len-0", "len-1", "page-minus-1", "page", "page-plus-1", "partial-last-page",
         "full-window", "mixed", "inactive-row"]


def make_case(case: str, widths: str, seed: int = 0, shuffled: bool = True):
    """(q_lat, q_rope, pool_c, pool_r, tables, lens, live): a row's pages
    are its own, in SHUFFLED order over the pool (page 0 is the trash
    page); a row that is not active keeps its table and reads with length
    0, as ``decode_step_ring_paged`` hands it down."""
    H, r, dr, page, dtype = WIDTHS[widths]
    lens, inactive = lengths(case, page)
    B = len(lens)
    rng = np.random.default_rng(seed)
    need = [-(-n // page) for n in lens]
    n_pages = 1 + sum(need) + 3  # + pages no row holds
    ids = rng.permutation(np.arange(1, n_pages)) if shuffled else np.arange(1, n_pages)
    tables = np.zeros((B, PMAX), np.int32)
    at = 0
    for b, n in enumerate(need):
        tables[b, :n] = ids[at:at + n]
        at += n
    read = [0 if b in inactive else n for b, n in enumerate(lens)]
    live = np.zeros((n_pages,), bool)
    for b, n in enumerate(read):
        live[tables[b, : -(-n // page)]] = True
    pool_c = rng.standard_normal((2, n_pages, 1, page, r)).astype(np.float32)
    pool_r = rng.standard_normal((2, n_pages, 1, page, dr)).astype(np.float32)
    q_lat = jnp.asarray(rng.standard_normal((B, H, r)), dtype)
    q_rope = jnp.asarray(rng.standard_normal((B, H, dr)), dtype)
    return (q_lat, q_rope, pool_c, pool_r, jnp.asarray(tables),
            jnp.asarray(read, jnp.int32), live)


def window_source(q_lat, q_rope, pool_c, pool_r, tables, lens):
    """``mla_merged_decode_attention``'s FIRST source over layer 1's
    gathered windows: every position of every row scored."""
    window = tuple(M.gather_window_paged(side[1], tables, WPAGES, side.shape[-1])
                   for side in (pool_c, pool_r))
    o, m, z = M.mla_window_attention_source(q_lat, q_rope, window, lens, SCALE)
    return o, m[..., 0], z[..., 0]


def both(q_lat, q_rope, pool_c, pool_r, tables, lens, pages_per_block=2):
    dtype = q_lat.dtype
    pool_c, pool_r = jnp.asarray(pool_c, dtype), jnp.asarray(pool_r, dtype)
    got = PA.latent_decode_attention_pallas(
        q_lat, q_rope, pool_c, pool_r, jnp.int32(1), tables, lens,
        scale=SCALE, wpages=WPAGES, interpret=True, pages_per_block=pages_per_block)
    return got, window_source(q_lat, q_rope, pool_c, pool_r, tables, lens)


class TestLatentDecodeKernelCorners:
    @pytest.mark.parametrize("pages_per_block", [1, 2, 3])
    @pytest.mark.parametrize("case", CASES)
    def test_matches_the_gathered_window_at_toy_widths(self, case, pages_per_block):
        args = make_case(case, "toy")[:6]
        got, want = both(*args, pages_per_block=pages_per_block)
        assert got[0].shape == want[0].shape and got[0].dtype == jnp.float32
        for name, g, w in zip("omz", got, want):
            # one pass over the window against a block, and a part of a
            # block, at a time: the same sums in another order
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
                err_msg=f"{case}: {name} diverged")
        empty = np.asarray(args[5]) == 0
        # a row that reads nothing stays finite at the floor
        assert (np.asarray(got[1])[empty] == np.float32(-1e29)).all()
        assert (np.asarray(got[2])[empty] == 0).all() and (np.asarray(got[0])[empty] == 0).all()

    @pytest.mark.parametrize("case", ["mixed", "partial-last-page", "inactive-row"])
    def test_the_cells_widths_bf16_operands_f32_accumulation(self, case):
        """The configuration's precision at its widths (512 | 64, pages of
        64, 16 heads): bf16 q, c and k_rope into the products, float32
        scores, statistics and accumulator."""
        got, want = both(*make_case(case, "kimi")[:6], pages_per_block=2)
        assert all(a.dtype == jnp.float32 for a in got)
        norm = lambda o, m, z: np.asarray(o / jnp.maximum(z[..., None], 1e-30))  # noqa: E731
        # p is rounded to bf16 against a running maximum here and against
        # the row's own there: agreement to bf16's 8 bits, not float32's
        np.testing.assert_allclose(norm(*got), norm(*want), atol=2e-2)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-5)

    def test_pages_are_visited_in_block_table_order(self):
        """The same rows through a table in pool order and through a
        shuffled one (other page ids, the pages' CONTENTS moved with them):
        the same result, bit for bit."""
        a = make_case("mixed", "toy", seed=3, shuffled=False)
        b = make_case("mixed", "toy", seed=3, shuffled=True)
        assert not np.array_equal(np.asarray(a[4]), np.asarray(b[4]))
        # move every page of a's pool to where b's table points
        pools = [np.zeros_like(a[2]), np.zeros_like(a[3])]
        for row_a, row_b in zip(np.asarray(a[4]), np.asarray(b[4])):
            for src, dst in zip(row_a, row_b):
                for moved, side in zip(pools, (a[2], a[3])):
                    moved[:, dst] = side[:, src]
        in_order, _ = both(*a[:6])
        shuffled, _ = both(a[0], a[1], pools[0], pools[1], b[4], a[5])
        for g, w in zip(shuffled, in_order):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("pages_per_block", [1, 2, 4])
    @pytest.mark.parametrize("case", ["mixed", "inactive-row", "partial-last-page"])
    def test_dead_pages_are_never_read(self, case, pages_per_block):
        """Every page no ACTIVE row's length reaches (the trash page, an
        inactive row's own pages, the tail of each table, the pages no row
        holds, the other layer's) is NaN on both sides of the pool; the
        result is finite and equal to the clean pool's."""
        q_lat, q_rope, pool_c, pool_r, tables, lens, live = make_case(case, "toy", seed=5)
        dirty_c, dirty_r = pool_c.copy(), pool_r.copy()
        dirty_c[:, ~live] = dirty_r[:, ~live] = np.nan
        dirty_c[0] = dirty_r[0] = np.nan  # another layer's pages
        got, _ = both(q_lat, q_rope, dirty_c, dirty_r, tables, lens, pages_per_block)
        clean, want = both(q_lat, q_rope, pool_c, pool_r, tables, lens, pages_per_block)
        for g, c, w in zip(got, clean, want):
            assert np.isfinite(np.asarray(g)).all()
            np.testing.assert_array_equal(np.asarray(g), np.asarray(c))
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize(
        "r,dr,page,dtype,ok",
        [
            (512, 64, 64, "bfloat16", True),  # Kimi-VL-A3B, DeepSeek-V2/V3
            (512, 64, 32, "bfloat16", True),  # a part of the page: one packed tile
            (128, 64, 16, "float32", True),
            (256, 128, 16, "bfloat16", True),  # a rope part of a whole lane tile
            (512, 32, 64, "bfloat16", True),  # four parts a page
            (512, 64, 16, "bfloat16", False),  # a part under a packed sublane tile
            (128, 64, 8, "float32", False),
            (32, 8, 8, "float32", False),  # tests/test_mla_moe.py's toy latent
            (192, 64, 64, "bfloat16", False),  # a latent that is not whole lane tiles
            (512, 48, 64, "bfloat16", False),  # a rope part that does not divide 128
            (512, 64, 32, "int8", False),  # a part under a tile of 32 packed rows
        ],
    )
    def test_shape_rule(self, r, dr, page, dtype, ok):
        assert PA.latent_decode_in_place_ok(r, dr, page, dtype) is ok

    def test_a_view_made_by_the_caller_is_read_as_it_lies(self):
        """The engine makes ``latent_rope_view`` once a dispatch and hands
        it down: the kernel's result is bit for bit that of the rope side as
        it lies.  Row r of a viewed page: PART j of the page in lane block
        j.  A rope part of a whole lane tile is its own view."""
        q_lat, q_rope, pool_c, pool_r, tables, lens, _ = make_case("mixed", "kimi")
        pool_c, pool_r = jnp.asarray(pool_c, jnp.bfloat16), jnp.asarray(pool_r, jnp.bfloat16)
        view = PA.latent_rope_view(pool_r)
        page, dr = pool_r.shape[3:]
        assert view.shape == (*pool_r.shape[:3], page // 2, 2 * dr)
        np.testing.assert_array_equal(
            np.asarray(view[1, 2, 0, 5], np.float32),
            np.concatenate([np.asarray(pool_r[1, 2, 0, 5], np.float32),
                            np.asarray(pool_r[1, 2, 0, 5 + page // 2], np.float32)]))
        kw = dict(scale=SCALE, wpages=WPAGES, interpret=True)
        got = PA.latent_decode_attention_pallas(
            q_lat, q_rope, pool_c, view, jnp.int32(1), tables, lens, **kw)
        want = PA.latent_decode_attention_pallas(
            q_lat, q_rope, pool_c, pool_r, jnp.int32(1), tables, lens, **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        wide = jnp.zeros((1, 3, 1, 16, 128), jnp.bfloat16)
        assert PA.latent_rope_view(wide) is wide

    @pytest.mark.parametrize("r,dr,page,dtype", [
        (32, 8, 8, "float32"),  # tests/test_mla_moe.py's toy latent
        (512, 64, 16, "bfloat16"),  # a part of the page under a packed sublane tile
        (512, 48, 64, "bfloat16"),  # a rope part that does not divide a lane tile
    ])
    def test_other_shapes_are_refused(self, r, dr, page, dtype):
        """Outside the shape rule there is no kernel, and a direct call
        says so while it is traced: nothing is built, nothing else runs."""
        pool_c, pool_r = jnp.zeros((1, 5, 1, page, r), dtype), jnp.zeros((1, 5, 1, page, dr), dtype)
        tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
        before = dict(PA.KERNEL_TRACES)
        with pytest.raises(PA.PallasShapeError, match="latent_decode_in_place_ok"):
            PA.latent_decode_attention_pallas(
                jnp.zeros((2, 4, r), dtype), jnp.zeros((2, 4, dr), dtype), pool_c, pool_r,
                jnp.int32(0), tables, jnp.asarray([page + 3, page], jnp.int32),
                scale=SCALE, wpages=2, interpret=True)
        assert dict(PA.KERNEL_TRACES) == before  # nothing was built

    def test_the_merged_read_is_the_xla_read(self):
        """Main cache and ring together, as ``decode_step_ring_paged`` calls
        either: the kernel's merged read against
        ``mla_merged_decode_attention`` over the gathered windows."""
        q_lat, q_rope, pool_c, pool_r, tables, lens, _ = make_case("mixed", "toy", seed=2)
        pool_c, pool_r = jnp.asarray(pool_c), jnp.asarray(pool_r)
        B, T = q_lat.shape[0], 4
        rng = np.random.default_rng(9)
        ring = tuple(jnp.asarray(rng.standard_normal((T, B, 1, w)), jnp.float32)
                     for w in (pool_c.shape[-1], pool_r.shape[-1]))
        t = jnp.int32(2)
        got = PA.merged_latent_decode_attention_pallas(
            q_lat[:, None], q_rope[:, None], pool_c, PA.latent_rope_view(pool_r), jnp.int32(1),
            tables, ring, lens, t, scale=SCALE, wpages=WPAGES, interpret=True)
        window = tuple(M.gather_window_paged(s[1], tables, WPAGES, s.shape[-1])
                       for s in (pool_c, pool_r))
        want = M.mla_merged_decode_attention(
            q_lat[:, None], q_rope[:, None], window, ring, lens, t, SCALE)
        assert got.shape == want.shape == (B, 1, *q_lat.shape[1:])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# the selector, and an engine that serves through the kernel
# --------------------------------------------------------------------------- #

# the toy stack of tests/test_mla_moe.py with a latent inside the kernel's
# rule (128 | 64 on pages of 16, float32); the ratios that matter there stay:
# rope on a part of the head, 8 experts with 2 a token, a dense layer first
IN_RULE = replace(TOY, name="toy-mla-moe-in-rule", kv_lora_rank=128, qk_rope_head_dim=64)


def in_rule_runtime(**kw):
    return FAMILY.runtime(page_size=16, **kw)


@pytest.fixture
def kernel_traces():
    before = dict(PA.KERNEL_TRACES)
    PA.latent_decode_attention_pallas.clear_cache()  # traced anew, counted anew
    return lambda: {k: n - before.get(k, 0) for k, n in PA.KERNEL_TRACES.items()
                    if n != before.get(k, 0)}


def test_a_latent_pool_in_the_rule_builds_the_latent_kernel_and_no_other(kernel_traces):
    engine = InferenceEngine(IN_RULE, in_rule_runtime(attention_impl="pallas_interpret"))
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "xla")
    # "auto" on this CPU, and "xla" anywhere: the reference path
    for impl in ("auto", "xla"):
        assert InferenceEngine(IN_RULE, in_rule_runtime(attention_impl=impl))._attn_impl == "xla"
    args, window, steps, sampled = engine._decode_args()
    jaxpr = str(jax.make_jaxpr(engine._decode_fn_paged(
        window // engine.runtime.page_size, steps, sampled))(*args, moe=engine._moe_zero))
    assert "latent_decode_attention" in jaxpr and "paged_decode_attention" not in jaxpr
    # traced for the unrolled dense layer (a Python index) and for the scan
    assert set(kernel_traces()) == {("latent_decode", "interpreted")}


def test_prefill_then_decode_through_the_kernel_agrees_with_the_reference(
        monkeypatch, kernel_traces):
    """(b) of tests/test_mla_moe.py with the decode read in the kernel:
    chunks of 16 under a prompt of 37, pages of 16, 21 generated tokens
    across five dispatches of four steps and two windows; every generated
    position's logits against the float32 reference's expanded forward.
    (A latent inside the kernel's rule under two implementations: builds of
    its own.)"""
    spy = Spy(monkeypatch)
    prompt = FAMILY.prompt_of(37)
    rt = in_rule_runtime(attention_impl="pallas_interpret")
    (out,), params, counters = FAMILY.serve((IN_RULE, rt), [(prompt, 21)])
    assert kernel_traces()["latent_decode", "interpreted"] >= 1
    assert counters["decode_pages_live"] > 0
    got = spy.of_request(prompt, out, 16)
    want = generated(FAMILY.reference_logits(params, IN_RULE, prompt + out), prompt, out)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < LOGIT_TOL
    # and the XLA read serves the same tokens from the same weights
    (xla_out,), _, _ = FAMILY.serve((IN_RULE, in_rule_runtime(attention_impl="xla")), [(prompt, 21)])
    assert out == xla_out


ONE_SLOT = dict(attention_impl="pallas_interpret", max_batch_size=1)


@pytest.fixture(scope="module")
def cold():
    """One slot under the kernel, built once: each case's second request served by an
    engine that never saw the first."""
    with FAMILY.standing((IN_RULE, in_rule_runtime(**ONE_SLOT))) as engine:
        yield engine


@pytest.mark.parametrize("reused", ["prefix", "slot"])
def test_a_reused_prefix_or_slot_through_the_kernel_gives_the_reference_logits(
        monkeypatch, cold, reused):
    """ONE slot, two requests in turn, so the second decodes where the
    first did.  "prefix": it shares 32 tokens (two latent pages) with the
    first and seeds its scratch from the cached pages; "slot": it shares
    nothing.  Either way its logits are the reference's, whatever the slot
    and its pages held before, and those of a cold engine.  (What a slot held
    before is the case: the engine that serves both is a build of its own.)"""
    shared = FAMILY.prompt_of(32, seed=1)
    first = shared + FAMILY.prompt_of(9, seed=2)
    second = shared + FAMILY.prompt_of(13, seed=3) if reused == "prefix" else FAMILY.prompt_of(21, seed=4)
    spy = Spy(monkeypatch)
    (_, out), params, counters = FAMILY.serve(
        (IN_RULE, in_rule_runtime(**ONE_SLOT)), [(first, 5), (second, 9)])
    assert counters["prefix_hits"] == (reused == "prefix")
    assert counters["prefix_reused_tokens"] == (32 if reused == "prefix" else 0)
    warm = spy.of_request(second, out, 16)
    want = generated(FAMILY.reference_logits(params, IN_RULE, second + out), second, out)
    assert np.abs(warm - want).max() < LOGIT_TOL
    served = cold.serve([(second, 9)])
    (cold_out,) = served.outs
    assert cold_out == out and served.added["prefix_hits"] == 0
    # the same programs on the same numbers but for the chunks skipped
    assert np.abs(warm - served.spy.of_request(second, cold_out, 16)).max() < 1e-6


# --------------------------------------------------------------------------- #
# who else runs the changed code: a pool of K and V pairs, with its kernel on
# --------------------------------------------------------------------------- #

# sha256 of str(jaxpr) of the paged decode dispatch and of a ragged program
# carrying one chunk of a two-row wave, with the paged decode kernel resolved
# by name, as the commit BEFORE the latent kernel traced them (0b6fef0;
# recorded there with tests/test_mla_moe.py's ``_programs``): a dense model
# at heads of 64 and of 128 (Mistral's and granite's bodies of the kernel)
# and a hybrid.  tests/test_mla_moe.py holds the same under "xla".  A PR that
# changes these programs on purpose records anew.
KV_PAIR_MODELS = {
    "dense-64": dict(d_model=256, n_heads=4, n_kv_heads=2),
    "dense-128": dict(d_model=512, n_heads=4, n_kv_heads=2),
    "hybrid": None,
}
# since PR 46 the programs end in the paged write's loop of window updates: with PR 45's scatter
# (tests/test_kv_write.py's reference) put back, each traced to the hash pinned before, letter for letter.
# Since PR 49 a pool of heads of 64 is STORED two positions a row (``model.positions_per_row``):
# "dense-64" and "hybrid" (float32 heads of 64 on pages of 32: ``[L, N, K, 16, 128]``) were
# recorded anew there on purpose (the kernel's operand is the pool itself, no reshape a dispatch,
# and the write's windows are groups of 8 stored rows); "dense-128", stored as declared, traces what it traced.
# Since PR 55 the kernel's rows follow one another in one copy pipeline (a cursor in SMEM, slots of
# a block by the slab's bytes, the columns' mask made once): all three recorded anew on purpose; with
# the parent's ``paged_decode_attention_pallas`` put back each traced to the hash pinned before
# (4e46cc9b.. / 116bac2e.., d1a95305.. / aa41a91b.., 8e005985.. / 65a836dc..), letter for letter.
TRACED_BEFORE = {
    "dense-64": {"decode": "0af28ebd1f14e386dea81dd69cd068322fb453ab974a6f90b3033da7ebf37493",
                 "ragged": "7bbbc5b7eca9d50da97a9e7fe8858c32a7bfc7347aa60b3c906bae85da328568"},
    "dense-128": {"decode": "7140b89da19cc8696d258dd6a68fc9230f697eb58de99158110dadba721cf0c0",
                  "ragged": "3f67339dc13eacb87705442a0baea64ce64fc4374d8a8aa50a3727438dc53cb6"},
    "hybrid": {"decode": "0d19b1ecc4055025e580834b08c627afb553790388b0af872666d8c6508a46e5",
               "ragged": "3fd80cce6121b667c3f881437ed7cd98f307fcf58a3a8accce7d0a58b9d3ff24"},
}


@pytest.mark.parametrize("kind", sorted(KV_PAIR_MODELS))
def test_a_pool_of_k_and_v_pairs_traces_the_kernel_it_traced_before(kind):
    """The selector, the decode step and the kernel module changed for a
    latent pool; a model without one builds the programs it built before,
    the paged decode kernel's own jaxpr inside them, letter for letter."""
    import hashlib

    from test_mla_moe import _programs

    from calfkit_tpu.inference.config import ModelConfig, preset

    config = (
        replace(preset("debug"), **KV_PAIR_MODELS[kind]) if KV_PAIR_MODELS[kind]
        else ModelConfig(
            name="toy-hybrid-64", vocab_size=128, d_model=512, n_layers=3, n_heads=8,
            n_kv_heads=2, d_ff=128, layer_types=("mamba", "mamba", "attention"),
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=128, mamba_n_groups=2,
            mamba_d_conv=4, mamba_chunk_size=8, dtype="float32",
            position_embedding="none", attention_multiplier=0.125, max_seq_len=256)
    )
    engine = InferenceEngine(config, FAMILY.runtime(
        prefix_cache=False, attention_impl="pallas", page_size=32, prefill_chunk=32))
    assert engine._attn_impl == "pallas"
    for name, jaxpr in _programs(engine).items():
        text = str(jaxpr)
        assert "paged_decode_attention" in text and "latent_decode_attention" not in text
        assert hashlib.sha256(text.encode()).hexdigest() == TRACED_BEFORE[kind][name], name
