"""The Pallas kernel of the gated delta rule's decode step over ``S``.

- The kernel (interpret mode on the CPU) against ``gdn.delta_step_xla``,
  the XLA body it stands in for, at toy size, with the decay by head (Gated
  DeltaNet) and by key channel (Kimi Delta Attention), on a stacked state
  of two layers.
- The selector (``InferenceEngine._resolved_ssm_impl``): platform, mesh
  size, state dtype, state shape, for a ``gdn`` and a ``kda`` model.
- The yardstick: the kernel's scope path, as the benchmark's trace
  reduction reads it, lies under ``decode_loop/gdn/state``.
"""

from __future__ import annotations

from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import gdn
from calfkit_tpu.inference import pallas_attention as PA
from calfkit_tpu.inference import pallas_gdn as PG
from calfkit_tpu.inference.config import preset
from calfkit_tpu.inference.engine import InferenceEngine
from tests.arch_harness import GDN_MOE

# (Hv, d_k, d_v): heads of one sublane tile, a piece of several heads and an
# odd head count (no unrolling divides it), a head of two lane tiles
SHAPES = {
    "toy": (2, 8, 128),
    "three-heads-of-16": (3, 16, 128),
    "wide-values": (2, 8, 256),
}
# every (decay, active) at toy size; the other shapes under a mask
CASES = [("toy", d, a) for d in ("by-head", "by-channel") for a in ("every-row", "a-mask", "all-frozen")] + [
    (s, d, "a-mask") for s in ("three-heads-of-16", "wide-values") for d in ("by-head", "by-channel")]
ROWS = 4
ACTIVE = {
    "every-row": None,
    "a-mask": np.asarray([True, False, True, True]),
    "all-frozen": np.zeros((ROWS,), bool),
}
TOL = 2e-6  # float32 rounding of sums of up to 16 products of numbers near 1


def step_inputs(shape: str, by_channel: bool, layers: int = 2, rows: int = ROWS):
    Hv, dk, dv = SHAPES[shape]
    key = jax.random.split(jax.random.key(11), 6)
    return (
        jax.random.normal(key[0], (layers, rows, Hv, dk, dv), jnp.float32),
        gdn._l2(jax.random.normal(key[1], (rows, Hv, dk), jnp.float32)) / np.sqrt(dk),
        gdn._l2(jax.random.normal(key[2], (rows, Hv, dk), jnp.float32)),
        jax.random.normal(key[3], (rows, Hv, dv), jnp.float32),
        jax.nn.sigmoid(jax.random.normal(key[4], (rows, Hv), jnp.float32)),
        -jax.random.uniform(
            key[5], (rows, Hv, dk) if by_channel else (rows, Hv), jnp.float32, 0.001, 3.0),
    )


@pytest.mark.parametrize("shape,decay,active", CASES, ids=["-".join(c) for c in CASES])
def test_the_kernel_agrees_with_the_xla_body(shape, decay, active):
    """``o`` and ``S'`` of layer 1 to float32 rounding; a row that is not
    active keeps its state bit for bit and reads ``o`` zero; the other
    layer's slice is bit-equal."""
    state, *heads = step_inputs(shape, decay == "by-channel")
    mask = ACTIVE[active]
    act = None if mask is None else jnp.asarray(mask)
    im = jnp.int32(1)
    want_o, want_s = gdn.delta_step_xla(state, im, *heads, act)
    got_o, got_s = PG.delta_step_pallas(state, im, *heads, act, interpret=True)
    assert got_o.shape == want_o.shape and got_s.shape == state.shape
    live = np.ones((ROWS,), bool) if mask is None else mask
    assert np.abs(np.asarray(got_o - want_o))[live].max(initial=0.0) < TOL
    assert np.abs(np.asarray(got_s - want_s)).max() < TOL
    assert not np.asarray(got_o)[~live].any()
    assert np.array_equal(np.asarray(got_s)[1][~live], np.asarray(state)[1][~live])
    assert np.array_equal(np.asarray(got_s)[0], np.asarray(state)[0])
    if live.any():
        assert not np.array_equal(np.asarray(got_s)[1][live], np.asarray(state)[1][live])


def test_a_decay_by_head_is_the_channel_form_with_equal_channels():
    """The wrapper's broadcast is bit-equal to the head's scale: ``g`` [B,
    Hv] and the same ``g`` repeated over the key channels give the same
    bits."""
    state, *heads, g = step_inputs("toy", False)
    by_head = PG.delta_step_pallas(state, jnp.int32(0), *heads, g, None, interpret=True)
    wide = jnp.broadcast_to(g[..., None], heads[0].shape)
    by_channel = PG.delta_step_pallas(state, jnp.int32(0), *heads, wide, None, interpret=True)
    for a, b in zip(by_head, by_channel):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_state_goes_out_where_it_came_in():
    """The jaxpr's ``pallas_call`` aliases the state operand (after three
    prefetched scalars) to the state result, and the layer index is one of
    those scalars: the stacked state is never sliced."""
    state, *heads = step_inputs("toy", True, rows=2)
    jaxpr = jax.make_jaxpr(
        lambda *a: PG.delta_step_pallas.__wrapped__(*a, None, interpret=True)
    )(state, jnp.int32(1), *heads)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["input_output_aliases"]) == ((3, 0),)
    assert call.invars[3].aval.shape == (2, 2, 2 * 8, 128)
    assert call.outvars[0].aval.shape == call.invars[3].aval.shape
    assert not [e for e in jaxpr.eqns if e.primitive.name in ("dynamic_slice", "dynamic_update_slice")]


@pytest.mark.parametrize("shape,dtype", [((2, 2, 2, 8, 8), "float32"), ((2, 2, 2, 8, 128), "bfloat16")],
                         ids=["d-v-of-8", "bfloat16-state"])
def test_a_shape_outside_the_rule_is_refused_by_name(shape, dtype):
    _, B, Hv, dk, dv = shape
    state = jnp.zeros(shape, dtype)
    rows = jnp.zeros((B, Hv, dk), jnp.float32)
    before = dict(PA.KERNEL_TRACES)
    with pytest.raises(PA.PallasShapeError, match="delta_step_in_place_ok"):
        PG.delta_step_pallas(
            state, jnp.int32(0), rows, rows, jnp.zeros((B, Hv, dv), jnp.float32),
            jnp.zeros((B, Hv), jnp.float32), jnp.zeros((B, Hv), jnp.float32), None)
    assert dict(PA.KERNEL_TRACES) == before  # nothing was built


@pytest.mark.parametrize(
    "heads,d_k,d_v,dtype,ok",
    [
        (32, 128, 128, "float32", True),  # both cells' state
        (4, 8, 128, "float32", True),
        (2, 256, 256, "float32", True),
        (3, 64, 128, "float32", True),
        (32, 128, 128, "bfloat16", False),  # a float32 pass or none
        (32, 128, 64, "float32", False),  # half a lane tile
        (32, 12, 128, "float32", False),  # not whole sublane tiles
        (4, 8, 8, "float32", False),  # the toy presets' own heads
    ],
)
def test_the_rule_is_a_rule_of_the_state_s_shape(heads, d_k, d_v, dtype, ok):
    assert PG.delta_step_in_place_ok(heads, d_k, d_v, dtype) is ok


# --------------------------------------------------------------- the selector
# the toy presets widened to a value head of one lane tile: inside the rule
MODELS = {
    "gdn": replace(preset("debug-gdn-moe"), gdn_d_v=128),
    "kda": replace(preset("debug-kda-mla-moe"), gdn_d_v=128),
}


@pytest.fixture(scope="module")
def engine():
    """ONE engine: the selector reads the engine's config, runtime and mesh
    and the platform when it is ASKED, so a case only changes those."""
    return InferenceEngine(MODELS["gdn"], GDN_MOE.runtime(window_buckets=(128,)))


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize(
    "platform,devices,changed,impl,want",
    [
        pytest.param("tpu", 1, {}, "auto", "pallas", id="tpu"),
        pytest.param("cpu", 1, {}, "auto", "xla", id="cpu"),
        pytest.param("tpu", 2, {}, "auto", "xla", id="tpu-two-devices"),
        pytest.param("tpu", 1, {"state_dtype": "bfloat16"}, "auto", "xla", id="tpu-bfloat16-state"),
        pytest.param("tpu", 1, {"gdn_d_v": 64}, "auto", "xla", id="tpu-d-v-64"),
        pytest.param("tpu", 1, {"gdn_d_k": 12}, "auto", "xla", id="tpu-d-k-12"),
        pytest.param("tpu", 1, {}, "xla", "xla", id="tpu-xla-asked"),
        # an explicit kernel request waives the platform test alone, and
        # names the ATTENTION kernel: a state outside the rule reads through XLA
        pytest.param("cpu", 1, {}, "pallas_interpret", "pallas_interpret", id="cpu-interpret"),
        pytest.param("cpu", 1, {"state_dtype": "bfloat16"}, "pallas_interpret", "xla",
                     id="cpu-interpret-bfloat16-state"),
        pytest.param("cpu", 1, {"gdn_d_v": 8}, "pallas_interpret", "xla",
                     id="cpu-interpret-the-toy-s-own-heads"),
    ],
)
def test_the_delta_step_is_selected_by_platform_and_shape(
    monkeypatch, engine, model, platform, devices, changed, impl, want
):
    """``_resolved_ssm_impl()`` answers from the platform, the mesh's size
    and the state's dtype and shape, under the ``attention_impl`` values
    that govern the paged decode read; nothing of it reads a model's name."""
    real = jax.devices()
    assert engine._ssm_impl == "xla"  # "auto" on this process's CPU
    monkeypatch.setattr(engine, "config", replace(MODELS[model], **changed))
    monkeypatch.setattr(engine, "runtime", replace(engine.runtime, attention_impl=impl))
    monkeypatch.setattr(engine, "mesh", SimpleNamespace(size=devices))
    monkeypatch.setattr(
        jax, "devices", lambda *a: [SimpleNamespace(platform=platform)] if not a else real)
    assert engine._resolved_ssm_impl() == want


# ------------------------------------------------------------- the yardstick
def test_the_kernel_s_scope_path_lies_under_gdn_state():
    """``benchmarks/readers/gdn_state_roofline.py`` sums a scope path if it
    holds ``decode_loop`` and, from ``gdn`` on, ``state`` or ``conv``.  The
    kernel is called inside the ``state`` scope and named ``state``: its
    device time is read where the XLA fusions' was, once a delta-rule layer
    of the period, in the decode program and in a ragged one (an attention
    head of 128 on pages of 16: inside the decode read's rule too)."""
    from benchmarks.trace_reduce import scope_path
    from tests.test_ssm_step_kernel import _kernels, _programs

    config = replace(MODELS["gdn"], attn_head_dim=128, n_layers=4,
                     layer_types=MODELS["gdn"].layer_types[:4])
    engine = InferenceEngine(config, GDN_MOE.runtime(
        window_buckets=(128,), attention_impl="pallas_interpret", page_size=16))
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas_interpret", "pallas_interpret")
    for jaxpr in _programs(engine).values():
        paths = [scope_path(op) for name, op in _kernels(jaxpr.jaxpr) if name == "state"]
        assert paths == ["decode_loop/gdn/state/state"] * 3  # L L L A: one period
        parts = paths[0].split("/")
        assert "decode_loop" in parts and {"state", "conv"} & set(parts[parts.index("gdn"):])
