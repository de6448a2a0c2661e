"""The Pallas kernel of a decode step's routed experts: the experts the
step's real rows hit, read in place out of the stack.

- The kernel (interpret mode on the CPU) against ``moe.experts_dense``, the
  form it stands in for, at the REHEARSAL shapes of the three configurations
  whose experts are held by share (``benchmarks/configs/*.json``'s
  ``rehearsal.model``), under ``valid`` masks with no, one, some and every
  held expert hit, pairs held elsewhere among them, at the first and the last
  layer of the stack, and with every row inactive.
- ``moe.moe_ffn`` under ``step_impl``: the same block and the same counters.
- The selector (``InferenceEngine._resolved_moe_step_impl``): platform, mesh
  size, share, rows, shapes.
- The yardstick: the kernel's scope path, as the benchmark's trace reduction
  reads it, is ``decode_loop/mlp/moe/experts``, where the dense form's
  products stand.
"""

from __future__ import annotations

from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import moe
from calfkit_tpu.inference import pallas_attention as PA
from calfkit_tpu.inference import pallas_moe as PM
from calfkit_tpu.inference.engine import InferenceEngine

CELLS = {
    "command-a-plus": "command-a-plus-05-2026.longdoc-closed",
    "qwen3-next": "qwen3-next-80b-a3b-instruct.history-closed",
    "ling": "ling-3.0-flash-vl.reason-closed",
}
LAYERS, ROWS = 3, 8
HITS = ("none-held", "one", "some", "all")
CASES = [(c, h, m) for c in CELLS for h in HITS for m in (0, LAYERS - 1)] + [
    (c, "all-inactive", 1) for c in CELLS]
TOL = 5e-6  # float32 sums of up to 64 x 32 products of numbers near 1 / 8, in another order


def rehearsal_model(cell: str):
    """The cell's toy ``ModelConfig`` (its file's ``rehearsal.model``), float32."""
    from benchmarks import manifest

    config = manifest.resolve_cell(manifest.load_manifest(), CELLS[cell]).config
    model, _ = manifest.load_architecture(config["architecture"]).model(config, True)
    return replace(model, dtype="float32")


def step_inputs(cell: str, hit: str):
    """(config, stack, h, chosen [T, k] among ALL the experts scored, weights,
    valid): ``hit`` decides which held experts the valid rows choose."""
    c = rehearsal_model(cell)
    E, k, first = c.n_routed_experts, c.n_experts_per_tok, c.expert_first
    assert c.expert_share and c.experts_scored > E
    key = jax.random.split(jax.random.key(53), 5)
    D, Fe = c.d_model, c.moe_d_ff
    stack = {
        "w_gate": jax.random.normal(key[0], (LAYERS, E, D, Fe), jnp.float32) / np.sqrt(D),
        "w_up": jax.random.normal(key[1], (LAYERS, E, D, Fe), jnp.float32) / np.sqrt(D),
        "w_down": jax.random.normal(key[2], (LAYERS, E, Fe, D), jnp.float32) / np.sqrt(Fe),
    }
    h = jax.random.normal(key[3], (ROWS, D), jnp.float32)
    weights = jax.random.uniform(key[4], (ROWS, k), jnp.float32, 0.05, 1.0)
    rng = np.random.default_rng(7)
    elsewhere = [e for e in range(c.experts_scored) if not first <= e < first + E]
    chosen = np.stack([rng.choice(elsewhere, size=k, replace=False) for _ in range(ROWS)])
    valid = np.asarray([True, False, True, True, False, True, True, False])
    if hit == "one":
        chosen[valid, 0] = first + E - 1
    elif hit == "some":  # half the held, by valid rows; an INVALID row chooses the others
        for t in np.flatnonzero(valid):
            chosen[t, :2] = first + rng.choice(E // 2, size=2, replace=False)
        chosen[~valid, 0] = first + E - 1
    elif hit in ("all", "all-inactive"):
        for n, t in enumerate(np.flatnonzero(valid)):
            chosen[t, :k] = first + (np.arange(k) + n * k) % E
        if hit == "all-inactive":
            valid = np.zeros((ROWS,), bool)
    return c, stack, h, jnp.asarray(chosen, jnp.int32), weights, jnp.asarray(valid)


def onehot_of(c, chosen):
    return chosen[..., None] == jnp.arange(c.n_routed_experts, dtype=jnp.int32) + c.expert_first


@pytest.mark.parametrize("cell,hit,m", CASES, ids=[f"{c}-{h}-layer{m}" for c, h, m in CASES])
def test_the_kernel_agrees_with_the_dense_form(cell, hit, m):
    """The step's sum to float32 rounding, with every expert that no valid
    row chose POISONED in the stack the kernel reads (it must not enter the
    sum: the kernel computes the hit alone) and the other layers poisoned
    whole (the layer is an index, not a slice)."""
    c, stack, h, chosen, weights, valid = step_inputs(cell, hit)
    onehot = onehot_of(c, chosen)
    real = onehot & valid[:, None, None]
    hit_mask = np.asarray(jnp.any(real, axis=(0, 1)))
    wanted = {"none-held": 0, "one": 1, "some": None, "all": c.n_routed_experts,
              "all-inactive": 0}[hit]
    assert wanted is None or hit_mask.sum() == wanted
    assert hit != "some" or 1 < hit_mask.sum() <= c.n_routed_experts // 2
    want = moe.experts_dense(h, real, weights, {n: a[m] for n, a in stack.items()})
    poison = np.full((LAYERS, c.n_routed_experts), np.nan, np.float32)
    poison[m, hit_mask] = 1.0
    poisoned = {n: a * poison[:, :, None, None] for n, a in stack.items()}
    got = moe.experts_step(h, onehot, weights, valid, poisoned, jnp.int32(m), True)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < TOL
    live = np.asarray(valid)
    assert not np.asarray(got)[~live].any()  # a row that is not valid is weighted zero
    if not hit_mask.any():
        assert not np.asarray(got).any()
    else:
        assert np.abs(np.asarray(got)[live]).max() > 1e-3


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_block_and_its_counters_do_not_change_with_the_step_s_form(cell):
    """``moe_ffn`` with the gate, the shared expert and the counters around the
    products: ``step_impl`` moves the output by rounding and no counter; rows
    past the kernel's limit keep the other forms' jaxpr."""
    c, _, h, _, _, valid = step_inputs(cell, "some")
    lp_all = moe.init_moe_params(c, jax.random.key(3), jnp.float32)
    m = c.n_moe_layers - 1
    lp = {n: a[m] for n, a in lp_all.items()}
    x = h[:, None, :]
    out = {}
    for impl in ("xla", "pallas_interpret"):
        out[impl] = moe.moe_ffn(
            x, lp, c, moe.moe_stats_init(c), valid[:, None], m, lp_all, step_impl=impl)
    (y0, s0), (y1, s1) = out["xla"], out["pallas_interpret"]
    live = np.asarray(valid)
    assert np.abs(np.asarray(y0 - y1))[live].max() < TOL
    for a, b in zip(s0, s1):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(s0[1]) > 0
    wide = jnp.zeros((moe._STEP_MAX_TOKENS + 8, 1, c.d_model), jnp.float32)
    texts = {impl: str(jax.make_jaxpr(
        lambda x, impl=impl: moe.moe_ffn(x, lp, c, None, None, m, lp_all, step_impl=impl)[0])(wide))
        for impl in ("xla", "pallas_interpret")}
    assert texts["xla"] == texts["pallas_interpret"] and "pallas_call" not in texts["xla"]


def test_the_hit_list_is_ascending_and_repeats_its_last():
    for hit, ids, n in [
        ([0, 1, 0, 1, 1, 0, 0, 0], [1, 3, 4, 4, 4, 4, 4, 4], 3),
        ([0] * 8, [0] * 8, 0),
        ([1] * 4, [0, 1, 2, 3], 4),
        ([0, 0, 0, 1], [3, 3, 3, 3], 1),
    ]:
        got_ids, got_n = PM.hit_experts_first(jnp.asarray(hit, bool))
        assert got_ids.tolist() == ids and got_n.tolist() == [n]
        assert got_ids.dtype == jnp.int32 and got_n.dtype == jnp.int32


def test_a_step_past_the_hit_names_the_block_before_it():
    """The weights' block index maps: ``(m, ids[i], ., tile)`` for a hit expert,
    and for a grid step past ``n_hit`` the LAST block of the last hit expert,
    whatever the tile's index, so that the pipeline starts no copy; the stack
    goes in whole (no slice of it anywhere in the jaxpr)."""
    E, D, Fe = 4, 128, 256
    tile = PM._width_tile(D, Fe, 4)
    assert tile == 256
    stack = {"w_gate": jnp.zeros((2, E, D, Fe)), "w_up": jnp.zeros((2, E, D, Fe)),
             "w_down": jnp.zeros((2, E, Fe, D))}
    jaxpr = jax.make_jaxpr(lambda *a: PM.moe_step_pallas.__wrapped__(*a, interpret=True))(
        jnp.zeros((8, D)), jnp.zeros((8, E)), jnp.zeros((E,), bool), stack, jnp.int32(1))
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in call.invars[5:]] == [(2, E, D, Fe), (2, E, D, Fe), (2, E, Fe, D)]
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("dynamic_slice", "gather", "sort")]
    grid = call.params["grid_mapping"].grid
    assert grid == (E, Fe // tile)


def test_a_width_tile_is_whole_lane_tiles_within_a_block():
    assert PM._width_tile(4096, 4096, 2) == 256  # command-a-plus: 2 MB
    assert PM._width_tile(2048, 512, 2) == 512  # Qwen3-Next: the whole width
    assert PM._width_tile(2560, 768, 2) == 384  # Ling: two tiles of 1.97 MB
    assert PM._width_tile(2048, 1408, 2) == 128  # 11 lane tiles: no wider divisor fits
    assert PM._width_tile(64, 32, 4) == 32  # a toy's width, interpreted: one tile


@pytest.mark.parametrize(
    "d_model,moe_d_ff,dtype,ok",
    [
        (4096, 4096, "bfloat16", True),
        (2048, 512, "bfloat16", True),
        (2560, 768, "bfloat16", True),
        (128, 128, "float32", True),
        (2048, 512, "float16", False),
        (2048, 96, "bfloat16", False),  # not whole lane tiles
        (64, 32, "float32", False),  # the toys' own widths
    ],
)
def test_the_rule_is_a_rule_of_the_experts_shape(d_model, moe_d_ff, dtype, ok):
    assert PM.moe_step_ok(d_model, moe_d_ff, dtype) is ok


def test_a_shape_outside_the_rule_is_refused_by_name():
    stack = {"w_gate": jnp.zeros((1, 2, 64, 32)), "w_up": jnp.zeros((1, 2, 64, 32)),
             "w_down": jnp.zeros((1, 2, 32, 64))}
    before = dict(PA.KERNEL_TRACES)
    with pytest.raises(PA.PallasShapeError, match="moe_step_ok"):
        PM.moe_step_pallas(jnp.zeros((8, 64)), jnp.zeros((8, 2)), jnp.zeros((2,), bool), stack, 0)
    assert dict(PA.KERNEL_TRACES) == before  # nothing was built


# --------------------------------------------------------------- the selector
def wide_model(cell: str = "qwen3-next", **changed):
    """The cell's rehearsal model with experts of one lane tile a side."""
    return replace(rehearsal_model(cell), d_model=128, moe_d_ff=128, **changed)


@pytest.fixture(scope="module")
def engine():
    """ONE engine: the selector reads the engine's config, runtime and mesh
    and the platform when it is ASKED, so a case only changes those."""
    from tests.arch_harness import GDN_MOE

    return InferenceEngine(wide_model(), GDN_MOE.runtime(window_buckets=(128,)))


@pytest.mark.parametrize(
    "platform,devices,changed,rows,impl,want",
    [
        pytest.param("tpu", 1, {}, 8, "auto", "pallas", id="tpu"),
        pytest.param("cpu", 1, {}, 8, "auto", "xla", id="cpu"),
        pytest.param("tpu", 2, {}, 8, "auto", "xla", id="tpu-two-devices"),
        pytest.param("tpu", 1, {}, 128, "auto", "pallas", id="tpu-128-rows"),
        pytest.param("tpu", 1, {}, 136, "auto", "xla", id="tpu-more-rows-than-the-kernel-s"),
        pytest.param("tpu", 1, {"n_experts_total": 0, "expert_first": 0}, 8, "auto", "xla",
                     id="tpu-experts-held-whole"),
        pytest.param("tpu", 1, {"moe_d_ff": 96}, 8, "auto", "xla", id="tpu-width-96"),
        pytest.param("tpu", 1, {"d_model": 64}, 8, "auto", "xla", id="tpu-hidden-64"),
        pytest.param("tpu", 1, {}, 8, "xla", "xla", id="tpu-xla-asked"),
        pytest.param("cpu", 1, {}, 8, "pallas_interpret", "pallas_interpret", id="cpu-interpret"),
        pytest.param("cpu", 1, {"moe_d_ff": 32}, 8, "pallas_interpret", "xla",
                     id="cpu-interpret-the-toy-s-own-width"),
        pytest.param("cpu", 1, {"n_experts_total": 0, "expert_first": 0}, 8, "pallas_interpret",
                     "xla", id="cpu-interpret-experts-held-whole"),
    ],
)
def test_the_step_kernel_is_selected_by_platform_share_and_shape(
    monkeypatch, engine, platform, devices, changed, rows, impl, want
):
    """``_resolved_moe_step_impl()`` answers from the platform, the mesh's
    size, whether the experts are held by share, the slots' rows and the
    experts' shape, under the ``attention_impl`` values that govern the paged
    decode read; nothing of it reads a model's name."""
    real = jax.devices()
    assert engine._moe_step_impl == "xla"  # "auto" on this process's CPU
    monkeypatch.setattr(engine, "config", replace(engine.config, **changed))
    monkeypatch.setattr(
        engine, "runtime", replace(engine.runtime, attention_impl=impl, max_batch_size=rows))
    monkeypatch.setattr(engine, "mesh", SimpleNamespace(size=devices))
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(platform=platform)] if not a else real)
    assert engine._resolved_moe_step_impl() == want


# ------------------------------------------------------------- the yardstick
def test_the_kernel_s_scope_path_is_where_the_dense_products_stand():
    """``benchmarks/readers/moe_expert_roofline.py`` sums a scope path if it
    holds ``decode_loop`` and ``moe``.  The kernel is called directly under
    ``moe`` and named ``experts``: its device time is read under
    ``decode_loop/mlp/moe/experts``, where the dense form's products stand,
    once an expert layer of the period, in the decode program and in a
    ragged one, whose chunk keeps the form ``dense_form`` gives it (an
    attention head of 128 on pages of 16 and a value head of 128: inside the
    decode read's and the delta step's rules too)."""
    from benchmarks.trace_reduce import scope_path
    from calfkit_tpu.inference.mamba import make_recurrent_state
    from tests.arch_harness import GDN_MOE
    from tests.test_ssm_step_kernel import _kernels

    toy = wide_model(attn_head_dim=128, gdn_d_v=128)
    config = replace(toy, n_layers=4, layer_types=toy.layer_types[:4])
    engine = InferenceEngine(config, GDN_MOE.runtime(
        window_buckets=(128,), attention_impl="pallas_interpret", page_size=16))
    assert (engine._attn_impl, engine._ssm_impl, engine._moe_step_impl) == (
        "pallas_interpret",) * 3
    rt = engine.runtime
    args, window, steps, sampled = engine._decode_args()
    rows, chunk = 2, rt.prefill_chunk
    scratch = jnp.zeros(
        (config.n_kv_layers, rows, config.n_kv_heads, 2 * chunk, config.head_dim), engine._k.dtype)
    wave = [scratch, scratch, jnp.zeros((rows, chunk), jnp.int32), jnp.int32(0)]
    wave_state = (engine._state, make_recurrent_state(config, rows), jnp.zeros((rows,), jnp.int32))
    zero = engine._moe_zero
    programs = {
        "decode": jax.make_jaxpr(
            lambda *a: engine._decode_fn_paged(window // rt.page_size, steps, sampled)(
                *a, moe=zero))(*args, engine._state),
        "ragged": jax.make_jaxpr(
            lambda *a: engine._ragged_jit(window, steps, sampled, chunk, rows)(
                *a, moe=zero, wmoe=zero))(*args, *wave, *wave_state),
    }
    for name, jaxpr in programs.items():
        paths = [scope_path(op) for kernel, op in _kernels(jaxpr.jaxpr) if kernel == "experts"]
        assert paths == ["decode_loop/mlp/moe/experts"] * 4, (name, paths)  # L L L A: one period
