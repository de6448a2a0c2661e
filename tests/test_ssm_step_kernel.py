"""The Pallas kernel of the Mamba-2 decode step's pass over the SSM state.

- The kernel (interpret mode on the CPU) against ``mamba.ssm_step_xla``,
  the XLA body it stands in for, at toy size and at granite-4.0-h-micro's
  head shape, on a stacked state of two layers.
- The selector (``InferenceEngine._resolved_ssm_impl``): platform, mesh
  size, state dtype, state shape, a model without Mamba layers.
- Who else runs the code: a dense model's programs are the same with and
  without this kernel's path.
- The yardstick: the kernel's scope path, as the benchmark's trace
  reduction reads it, ends in ``ssm``.
"""

from __future__ import annotations

from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import mamba as mm
from calfkit_tpu.inference import pallas_attention as PA
from calfkit_tpu.inference import pallas_ssm as PS
from calfkit_tpu.inference.config import ModelConfig
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import HYBRID_MAMBA

# (H, G, P, N): the toy of tests/test_hybrid_mamba.py (two groups, a chunk
# is a group), the published head shape (granite-4.0-h-micro: a chunk is two
# heads of one group), heads of whole chunks, heads of 8 in two groups
SHAPES = {
    "toy": (4, 2, 16, 16),
    "granite": (64, 1, 64, 128),
    "heads-of-256": (2, 1, 256, 128),
    "heads-of-8": (32, 2, 8, 128),
}
ROWS = 5  # no piece, chunk or slot count divides it
ACTIVE = {
    "every-row": None,
    "a-mask": np.asarray([True, False, True, True, False]),
    "all-frozen": np.zeros((ROWS,), bool),
}
TOL = 2e-5  # tests/test_hybrid_mamba.py's float32 tolerance (LOGIT_TOL)


def step_inputs(shape: str, layers: int = 2, rows: int = ROWS):
    H, G, P, N = SHAPES[shape]
    k = jax.random.split(jax.random.key(7), 5)
    E = H // G
    return (
        jax.random.normal(k[0], (layers, rows, H, P, N), jnp.float32),
        jnp.exp(-jax.random.uniform(k[1], (rows, G, E), jnp.float32, 0.001, 2.0)),
        0.1 * jax.random.normal(k[2], (rows, G, E, P), jnp.float32),
        jax.random.normal(k[3], (rows, G, N), jnp.float32),
        jax.random.normal(k[4], (rows, G, N), jnp.float32),
    )


@pytest.mark.parametrize("active", sorted(ACTIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_agrees_with_the_xla_body(shape, active):
    """``y`` and ``S'`` of layer 1 within the float32 tolerance; a row that
    is not active keeps its state bit for bit and reads ``y`` zero; the
    other layer's slice is bit-equal."""
    state, decay, dtx, Bm, Cm = step_inputs(shape)
    mask = ACTIVE[active]
    act = None if mask is None else jnp.asarray(mask)
    im = jnp.int32(1)
    want_y, want_s = mm.ssm_step_xla(state, im, decay, dtx, Bm, Cm, act)
    got_y, got_s = PS.ssm_step_pallas(state, im, decay, dtx, Bm, Cm, act, interpret=True)
    assert got_y.shape == want_y.shape and got_s.shape == state.shape
    live = np.ones((ROWS,), bool) if mask is None else mask
    assert np.abs(np.asarray(got_y - want_y))[live].max(initial=0.0) < TOL
    assert np.abs(np.asarray(got_s - want_s)).max() < TOL
    assert not np.asarray(got_y)[~live].any()
    assert np.array_equal(np.asarray(got_s)[1][~live], np.asarray(state)[1][~live])
    assert np.array_equal(np.asarray(got_s)[0], np.asarray(state)[0])
    if live.any():
        assert not np.array_equal(np.asarray(got_s)[1][live], np.asarray(state)[1][live])


def test_the_state_goes_out_where_it_came_in():
    """The jaxpr's ``pallas_call`` aliases the state operand (after three
    prefetched scalars) to the state result, and the layer index is one of
    those scalars: the stacked state is never sliced."""
    state, decay, dtx, Bm, Cm = step_inputs("granite", rows=2)
    jaxpr = jax.make_jaxpr(
        lambda *a: PS.ssm_step_pallas.__wrapped__(*a, None, interpret=True)
    )(state, jnp.int32(1), decay, dtx, Bm, Cm)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((3, 0),)
    assert call.invars[3].aval.shape == (2, 2, 64 * 64, 128)
    assert call.outvars[0].aval.shape == call.invars[3].aval.shape
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("dynamic_slice", "dynamic_update_slice")]


def test_a_shape_outside_the_rule_is_refused_by_name():
    state, decay, dtx, Bm, Cm = step_inputs("toy")
    before = dict(PA.KERNEL_TRACES)
    with pytest.raises(PA.PallasShapeError, match="ssm_step_in_place_ok"):
        PS.ssm_step_pallas(state, jnp.int32(0), decay, dtx, Bm, Cm, None)
    assert dict(PA.KERNEL_TRACES) == before  # nothing was built


@pytest.mark.parametrize(
    "heads,groups,d_head,d_state,dtype,ok",
    [
        (64, 1, 64, 128, "float32", True),  # granite-4.0-h-micro
        (32, 2, 8, 128, "float32", True),
        (2, 1, 256, 256, "float32", True),
        (64, 1, 64, 128, "bfloat16", False),  # a float32 pass or none
        (64, 1, 64, 64, "float32", False),  # half a lane tile
        (64, 1, 60, 128, "float32", False),  # not whole sublane tiles
        (16, 1, 24, 128, "float32", False),  # heads that straddle chunks
        (4, 2, 16, 128, "float32", False),  # a group of 32 lines: under a chunk
    ],
)
def test_the_rule_is_a_rule_of_the_state_s_shape(heads, groups, d_head, d_state, dtype, ok):
    assert PS.ssm_step_in_place_ok(heads, groups, d_head, d_state, dtype) is ok


# --------------------------------------------------------------- the selector
HYBRID = ModelConfig(
    name="toy-hybrid-tiles", vocab_size=128, d_model=256, n_layers=3, n_heads=4, n_kv_heads=2,
    d_ff=64, layer_types=("mamba", "mamba", "attention"),
    mamba_n_heads=32, mamba_d_head=8, mamba_d_state=128, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=8, dtype="float32", position_embedding="none", max_seq_len=1024,
)
DENSE = ModelConfig(
    name="toy-dense", vocab_size=128, d_model=256, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, dtype="float32", max_seq_len=1024,
)


# pages of 16 and chunks of 32: inside the paged decode read's rule at heads of 64
IN_RULE = dict(page_size=16, prefill_chunk=32, window_buckets=(128,))


@pytest.mark.parametrize(
    "platform,devices,config,impl,want",
    [
        pytest.param("tpu", 1, HYBRID, "auto", "pallas", id="tpu"),
        pytest.param("cpu", 1, HYBRID, "auto", "xla", id="cpu"),
        pytest.param("tpu", 2, HYBRID, "auto", "xla", id="tpu-two-devices"),
        pytest.param("tpu", 1, replace(HYBRID, state_dtype="bfloat16"), "auto", "xla",
                     id="tpu-bfloat16-state"),
        pytest.param("tpu", 1, replace(HYBRID, mamba_d_state=64), "auto", "xla",
                     id="tpu-d-state-64"),
        pytest.param("tpu", 1, replace(HYBRID, mamba_n_heads=4, mamba_d_head=32), "auto", "xla",
                     id="tpu-group-under-a-chunk"),
        pytest.param("tpu", 1, DENSE, "auto", "xla", id="tpu-dense-model"),
        pytest.param("tpu", 1, HYBRID, "xla", "xla", id="tpu-xla-asked"),
        # an explicit kernel request waives the platform test alone, and
        # names the ATTENTION kernel: a state outside the rule reads through XLA
        pytest.param("cpu", 1, HYBRID, "pallas_interpret", "pallas_interpret", id="cpu-interpret"),
        pytest.param("cpu", 1, replace(HYBRID, state_dtype="bfloat16"), "pallas_interpret", "xla",
                     id="cpu-interpret-bfloat16-state"),
        pytest.param("cpu", 1, DENSE, "pallas_interpret", "xla", id="cpu-interpret-dense-model"),
    ],
)
def test_the_ssm_step_is_selected_by_platform_and_shape(
    monkeypatch, platform, devices, config, impl, want
):
    """``_resolved_ssm_impl()`` answers from the platform, the mesh's size,
    the state's dtype and shape and whether the model has Mamba layers,
    under the ``attention_impl`` values that govern the paged decode read."""
    real = jax.devices()
    engine = InferenceEngine(config, HYBRID_MAMBA.runtime(**IN_RULE, attention_impl=impl))
    monkeypatch.setattr(engine, "mesh", SimpleNamespace(size=devices))
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(platform=platform)] if not a else real)
    assert engine._resolved_ssm_impl() == want


# --------------------------------------------------------- who else runs it
def _kernels(jaxpr, prefix: str = "jit(program)") -> list[tuple[str, str]]:
    """(kernel name, op name as the compiled program carries it) of every
    ``pallas_call`` under ``jaxpr``: an equation's name stack is relative
    to the jaxpr that holds it, so the stacks are joined on the way down
    (a ``jit`` adds its own part; JAX's ``while`` / ``body`` parts are left
    out, ``scope_path`` drops them anyway), the primitive last."""
    found = []
    for eqn in jaxpr.eqns:
        at = "/".join(p for p in (prefix, str(eqn.source_info.name_stack)) if p)
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], f"{at}/pallas_call:"))
        inner = f"{at}/jit({eqn.params['name']})" if eqn.primitive.name in ("pjit", "jit") else at
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernels(sub, inner)
    return found


def _programs(engine) -> dict:
    """The jaxprs of the paged decode dispatch and of a ragged program
    carrying one chunk of a two-row wave."""
    from calfkit_tpu.inference.mamba import make_recurrent_state

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    rows, chunk = 2, rt.prefill_chunk
    scratch = jnp.zeros(
        (cfg.n_kv_layers, rows, cfg.n_kv_heads, 2 * chunk, cfg.head_dim), engine._k.dtype)
    wave = [scratch, scratch, jnp.zeros((rows, chunk), jnp.int32), jnp.int32(0)]
    state = wave_state = ()
    if engine._recurrent:
        state = (engine._state,)
        wave_state = (engine._state, make_recurrent_state(cfg, rows), jnp.zeros((rows,), jnp.int32))
    return {
        "decode": jax.make_jaxpr(
            engine._decode_fn_paged(window // rt.page_size, steps, sampled))(*args, *state),
        "ragged": jax.make_jaxpr(
            engine._ragged_jit(window, steps, sampled, chunk, rows))(*args, *wave, *wave_state),
    }


def test_a_dense_model_s_programs_do_not_change_with_the_ssm_path():
    """A model without Mamba layers never traces ``mamba_step``: its decode
    and ragged jaxprs are the same, letter for letter, whichever SSM
    implementation the engine were told, and the only ``pallas_call`` in
    them is the attention read's."""
    texts = {}
    for ssm_impl in ("xla", "pallas_interpret"):
        engine = InferenceEngine(DENSE, HYBRID_MAMBA.runtime(**IN_RULE, attention_impl="pallas_interpret"))
        assert engine._ssm_impl == "xla"
        engine._ssm_impl = ssm_impl  # what no resolution gives a dense model
        programs = _programs(engine)
        texts[ssm_impl] = {name: str(jaxpr) for name, jaxpr in programs.items()}
        for jaxpr in programs.values():
            assert [name for name, _ in _kernels(jaxpr.jaxpr)] == ["paged_decode_attention"]
    assert texts["xla"] == texts["pallas_interpret"]


@pytest.mark.parametrize("impl,kernels", [
    ("pallas_interpret", ["ssm", "ssm", "paged_decode_attention"]), ("xla", [])])
def test_a_hybrid_model_s_programs_hold_the_kernel_once_a_mamba_layer(impl, kernels):
    """The same programs of a hybrid in the rule: one ``ssm`` kernel a
    Mamba layer of the period before the one attention read, under
    ``pallas_interpret``; none under ``xla``."""
    engine = InferenceEngine(HYBRID, HYBRID_MAMBA.runtime(**IN_RULE, attention_impl=impl))
    for jaxpr in _programs(engine).values():
        assert [name for name, _ in _kernels(jaxpr.jaxpr)] == kernels


# ------------------------------------------------------------- the yardstick
def test_the_kernel_s_scope_path_ends_in_ssm():
    """``benchmarks/readers/ssm_state_roofline.py`` sums a scope path only
    if it holds ``decode_loop`` and ``mamba`` and its LAST part is ``ssm``
    or ``conv``.  A ``pallas_call``'s name is one more part of the path: the
    kernel is called inside the ``ssm`` scope AND is named ``ssm``, so its
    device time is read where the XLA fusions' was."""
    from benchmarks.trace_reduce import scope_path

    engine = InferenceEngine(HYBRID, HYBRID_MAMBA.runtime(**IN_RULE, attention_impl="pallas_interpret"))
    paths = {
        program: {scope_path(op_name) for _, op_name in _kernels(jaxpr.jaxpr)}
        for program, jaxpr in _programs(engine).items()
    }
    want = {"decode_loop/mamba/ssm/ssm", "decode_loop/attention/paged_decode_attention"}
    assert paths == {"decode": want, "ragged": want}
    for path in want - {"decode_loop/attention/paged_decode_attention"}:  # the reader's own test
        assert {"decode_loop", "mamba"} <= set(path.split("/"))
        assert path.split("/")[-1] in ("ssm", "conv")
