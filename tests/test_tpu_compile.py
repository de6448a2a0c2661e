"""AOT compiles of the four Pallas kernels for a DESCRIBED v5e.

The TPU compiler is installed without a chip: it compiles for a topology
that is described, not attached (on-chip-measurement guide, section 2).
Interpret mode cannot see what the Mosaic lowering refuses — block shapes,
SMEM scalars, VMEM limits — so the four kernels the serving path can
select, the paged decode read in place (of K and V pairs, and of a latent
pool in the absorbed form), the Mamba-2 decode step's pass over the SSM
state and a window stack's chunk attention with its scores in VMEM, are
compiled here at the widths of every preset and benchmark
configuration that takes them, alone and inside the dispatch programs of
the benchmark's cells.  Nothing runs; a pass is a compile, never a chip run.

Rules this file keeps: the topology is described inside a module-scoped
fixture (never at import, in a skipif, in parametrize arguments or in
conftest.py) because only one process may load the TPU library and every
xdist worker imports every test file; shapes and shardings are built in
fixtures/tests; compiles happen in the test's own process; the persistent
compile cache is off around them (such a compile can be written to it but
not read back without a chip).
"""

from __future__ import annotations

import os

import pytest

PAGE = 64
# the paged decode read: (K, G, head_dim, B, W, pages, layers).  The
# benchmark's configurations at their own batch and widest window (the
# kernel is what "auto" selects for them on a chip), and the two presets
# chip_smoke.py serves
DECODE_PAGED_WIDTHS = {
    "mistral-7b-v0.3": (8, 4, 128, 32, 2048, 513, 32),
    "internlm2-1.8b": (8, 2, 128, 64, 4096, 897, 24),
    # heads of 64: stored two positions a row (model.positions_per_row)
    "granite-4.0-h-micro": (8, 4, 64, 64, 2048, 1281, 4),
    "tinyllama-1.1b": (4, 8, 64, 64, 1024, 257, 2),
    # a head of 256 (two lane tiles) with 8 query heads a KV head: the two
    # gated-attention layers of the cut (PR 33), first run through the kernel here
    "qwen3-next-80b-a3b-instruct": (2, 8, 256, 64, 4096, 4097, 2),
    "llama-3-8b": (8, 4, 128, 64, 1024, 257, 2),
    # 16 query heads a KV head at a head of 128: the ONE global layer of the cut
    # (PR 38); its three window layers take the kernel's window form (below)
    "command-a-plus-05-2026": (8, 16, 128, 32, 18432, 6145, 1),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("widths", sorted(DECODE_PAGED_WIDTHS))
def test_paged_decode_in_place_compiles_for_v5e(
    widths, one_chip, no_persistent_cache
):
    """The merged paged decode read as ``decode_step_ring_paged`` calls it,
    with the whole pool of the configuration as the kernel's HBM operand."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import pallas_attention as PA

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    from calfkit_tpu.inference.model import positions_per_row

    K, G, hd, rows, window, pages, layers = DECODE_PAGED_WIDTHS[widths]
    assert PA.paged_decode_in_place_ok(hd, PAGE, jnp.bfloat16)
    bf16, i32 = jnp.bfloat16, jnp.int32
    f = positions_per_row(hd, PAGE, bf16)  # the pool as make_page_pool stores it
    assert f == max(1, 128 // hd)
    pool = (shape((layers, pages, K, PAGE // f, f * hd), bf16),) * 2
    ring = (shape((8, rows, K, hd), bf16),) * 2
    before = PA.KERNEL_TRACES["paged_decode", "compiled"]
    compiled = jax.jit(
        lambda *a: PA.merged_paged_decode_attention_pallas(
            *a, wpages=window // PAGE
        )
    ).lower(
        shape((rows, 1, K * G, hd), bf16), *pool, shape((), i32),
        shape((rows, window // PAGE), i32), *ring, shape((rows,), i32),
        shape((), i32),
    ).compile()
    # the kernel is IN the program: compiled by Mosaic, not interpreted and
    # not replaced by an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()
    assert PA.KERNEL_TRACES["paged_decode", "compiled"] == before + 1


# the latent decode read: (H, r, dr, B, W, pages, layers), the benchmark's
# one latent configuration at its cell's batch and window
DECODE_LATENT_WIDTHS = {"kimi-vl-a3b-instruct": (16, 512, 64, 64, 4096, 4097, 7)}


def _latent_decode_args(shape, widths="kimi-vl-a3b-instruct"):
    """Abstract arguments of ``latent_decode_attention_pallas`` as the decode
    step calls it: the c side as it lies, the rope side's view."""
    import jax.numpy as jnp

    H, r, dr, rows, window, pages, layers = DECODE_LATENT_WIDTHS[widths]
    bf16, i32 = jnp.bfloat16, jnp.int32
    return (
        shape((rows, H, r), bf16), shape((rows, H, dr), bf16),
        shape((layers, pages, 1, PAGE, r), bf16),
        shape((layers, pages, 1, PAGE // 2, 2 * dr), bf16), shape((), i32),
        shape((rows, window // PAGE), i32), shape((rows,), i32),
    ), {"scale": (128 + dr) ** -0.5, "wpages": window // PAGE}


def test_latent_decode_in_place_compiles_for_v5e(one_chip, no_persistent_cache):
    """The merged latent decode read as ``decode_step_ring_paged`` calls it
    for Kimi-VL-A3B's cell, both sides of the configuration's whole pool as
    the kernel's HBM operands: the rope side through its view, the c side,
    1.88 GB, as it lies (nothing of its size is made)."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import pallas_attention as PA

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    H, r, dr, rows, window, pages, layers = DECODE_LATENT_WIDTHS["kimi-vl-a3b-instruct"]
    assert PA.latent_decode_in_place_ok(r, dr, PAGE, jnp.bfloat16)
    (q_lat, q_rope, c, rope, layer, tables, lens), static = _latent_decode_args(shape)
    ring = (shape((8, rows, 1, r), jnp.bfloat16), shape((8, rows, 1, dr), jnp.bfloat16))
    before = PA.KERNEL_TRACES["latent_decode", "compiled"]
    compiled = jax.jit(
        lambda ql, qr, c, rope, layer, tables, rc, rr, lens, t:
        PA.merged_latent_decode_attention_pallas(
            ql[:, None], qr[:, None], c, rope, layer, tables, (rc, rr), lens, t, **static)
    ).lower(q_lat, q_rope, c, rope, layer, tables, *ring, lens, shape((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "latent_decode_attention" in hlo
    assert PA.KERNEL_TRACES["latent_decode", "compiled"] == before + 1
    assert compiled.memory_analysis().temp_size_in_bytes < c.size  # half the c side's bytes


def _computations(hlo: str) -> dict[str, list[str]]:
    """Compiled HLO text → computation name → its instruction lines; the
    entry computation under ``"ENTRY"``."""
    import re

    out: dict[str, list[str]] = {}
    name = None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def _made_in_loops(hlo: str, shapes: tuple[str, ...]) -> dict[str, list[str]]:
    """Loop-body computation → the operations in it whose RESULT is an array
    of one of ``shapes`` (``"bf16[64,2048,1408]"``), plumbing left out: what a
    ``dynamic-slice`` of a stack handed to a kernel of the compiler's own
    compiles to, a layer-sized fusion written once and read again.  A loop's
    body is what a ``while`` names as its ``body`` and what that calls
    (a fusion's own computation is no buffer: its result in the caller is)."""
    import re

    computations = _computations(hlo)
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))
    grew = True
    while grew:  # ... and the computations those call, fusions left out
        called = {
            name
            for body in bodies
            for line in computations.get(body, ())
            if " fusion(" not in line
            for name in re.findall(r"\b(?:to_apply|calls|body|condition)=%?([\w.\-]+)", line)
        }
        grew = not called <= bodies
        bodies |= called
    assert bodies and bodies <= set(computations), bodies
    pattern = re.compile(
        r"= (?:%s)\S* (\w[\w\-]*)\(" % "|".join(re.escape(shape) for shape in shapes))
    plumbing = ("bitcast", "parameter", "get-tuple-element")
    made = {
        name: [m.group(1) for m in map(pattern.search, computations[name])
               if m and m.group(1) not in plumbing]
        for name in bodies
    }
    return {name: ops for name, ops in made.items() if ops}


def _expert_stack_copies(hlo: str, config) -> list[str]:
    """The ``copy`` operations whose result is an array of a whole expert
    stack's shape (``[layers, E, D, Fe]`` or ``[layers, E, Fe, D]``) or of one
    layer's: a change of layout hoisted out of a dispatch's loops, which is
    what the dense products spelled rows first compiled to from 128 rows on
    (``moe.py``; PR 45)."""
    import re

    E, D, F = config.n_routed_experts, config.d_model, config.moe_d_ff
    stacks = re.compile(rf"= bf16\[(?:\d+,)?{E},(?:{D},{F}|{F},{D})\]\S* copy\(")
    return [line.strip()[:160] for line in hlo.splitlines() if stacks.search(line)]


# The five expert configurations' full-size programs' temporaries at PR 44, the parent of
# PR 45 (which respelled the dense products): the slow tests below on that tree, bytes
_TEMPORARIES_AT_PR_44 = {
    "kimi": {"decode": 1_282_386_432, "ragged x2": 1_198_626_816, "ragged x1": 1_195_691_520},
    "qwen3-next": {"decode": 543_565_312, "ragged x1": 553_725_952, "ragged x2": 669_483_520,
                   "ragged x4": 1_391_255_552},
    "command-a-plus": {"decode": 1_149_901_312, "ragged 6144": 1_158_707_200,
                       "ragged 16384": 1_158_739_456},
    "ling": {"decode": 641_782_272, "ragged x1": 657_845_760, "ragged x2": 1_251_969_024,
             "ragged x4": 2_493_971_968},
    # the decode program held 1,691,838,976 with its products GROUPED; dense since PR 45 it
    # holds 162,304 bytes more (the pool's layout copies are its temporaries, not the products)
    "lfm2": {"decode": 1_692_001_280, "ragged x1": 2_523_130_880, "ragged x4": 2_528_257_024},
}


def _no_larger_than_at_pr_44(cell: str, report: dict) -> None:
    want = _TEMPORARIES_AT_PR_44[cell]
    assert set(report) == set(want), (report, want)
    assert all(report[name] <= want[name] for name in want), (cell, report, want)


def test_decode_dispatch_of_narrow_heads_gathers_no_window_on_v5e(
    one_chip, no_persistent_cache
):
    """The decode DISPATCH program (``_decode_fn_paged``, 8 steps) of a toy
    hybrid at heads of 64, compiled for the described v5e: the kernel is in
    it, no ``gather_window`` scope is, and the pool, STORED two positions a
    row, is the kernel's operand as it lies: no array of a side's size is
    copied, reshaped or transposed anywhere in the program."""
    import jax

    from calfkit_tpu.inference.config import ModelConfig, RuntimeConfig
    from calfkit_tpu.inference.engine import InferenceEngine

    config = ModelConfig(
        name="toy-hybrid-64", vocab_size=128, d_model=512, n_layers=3, n_heads=8,
        n_kv_heads=2, d_ff=128, layer_types=("mamba", "mamba", "attention"),
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
        mamba_d_conv=4, mamba_chunk_size=8, dtype="bfloat16",
        position_embedding="none", attention_multiplier=0.125, max_seq_len=256,
    )
    page, steps = 32, 8
    engine = InferenceEngine(config, RuntimeConfig(
        max_batch_size=4, max_seq_len=256, kv_layout="paged", page_size=page,
        chunked_prefill=True, prefill_chunk=32, window_buckets=(256,),
        compilation_cache=False, decode_steps_per_dispatch=steps,
        attention_impl="pallas",
    ))
    assert config.head_dim == 64
    args, window, _, sampled = engine._decode_args()
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (*args, engine._state),
    )
    hlo = jax.jit(
        engine._decode_fn_paged(window // page, steps, sampled),
        donate_argnums=(1, 2, 14),
    ).lower(*abstract).compile().as_text()
    assert "tpu_custom_call" in hlo and "paged_decode_attention" in hlo
    assert "gather_window" not in hlo
    assert engine._k.shape[3:] == (page // 2, 128)  # stored two positions a row
    copies, scatters = _whole_side_copies(hlo, engine._k, ("copy", "copy-start", "reshape", "transpose"))
    assert not copies and not scatters, (copies, scatters)


# a benchmark cell's configuration -> (layers kept, experts held): the depth cut
# (granite: one period of its stack, nine Mamba-2 layers around one attention
# layer; Kimi: the dense layer and two expert layers; Qwen3-Next: one period;
# None: the cell's own depth, command-a-plus's W W W G and LFM2's twelve with
# their three attention layers) and, where a test has no use for GBs of
# experts, a few of them (the gate keeps its outputs)
CELL_CUTS = {
    "mistral-7b-v0.3-int8": (2, None),
    "granite-4.0-h-micro": (10, None),
    "command-a-plus-05-2026": (None, 2),
    "kimi-vl-a3b-instruct": (3, 8),
    "qwen3-next-80b-a3b-instruct": (4, 8),
    "lfm2-8b-a1b": (None, 8),
}


@pytest.fixture(scope="module")
def cell_engine():
    """name -> the engine of a benchmark configuration at its published
    WIDTHS and its cell's runtime (its pools as the cell holds them, by cache
    kind where the model has window layers), cut by ``CELL_CUTS``; the kernel
    asked for by name, as "auto" resolves it on a chip (this process sees a
    CPU)."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    built = {}

    def build(name):
        if name not in built:
            built.clear()  # one engine at a time: their pools and weights are GBs of this host
            here = os.path.dirname(manifest.__file__)
            with open(os.path.join(here, "configs", name + ".json")) as f:
                described = json.load(f)
            arch = manifest.load_architecture(
                described.get("architecture", "dense-gqa"), here)
            config, runtime = arch.model(described, False)
            layers, held = CELL_CUTS[name]
            if layers is not None:
                config = replace(
                    config, n_layers=layers,
                    **({"layer_types": config.layer_types[:layers]}
                       if config.layer_types else {}),
                )
            if held is not None:
                config = replace(config, n_routed_experts=held)
            built[name] = InferenceEngine(config, replace(
                runtime, compilation_cache=False, attention_impl="pallas"))
        return built[name]

    return build


def _dispatch_programs(engine, sharding):
    """{"decode": (jitted fn, abstract args), "ragged": ...}: the paged
    decode dispatch, and the ragged program that carries one chunk of a
    two-row wave beside it, at the engine's widest window."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.mamba import make_recurrent_state

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    rows, chunk = 2, rt.prefill_chunk
    scratch = jax.ShapeDtypeStruct(
        (cfg.n_kv_layers, rows, cfg.n_kv_heads, 2 * chunk, cfg.head_dim), engine._k.dtype)
    wave = [scratch, scratch, jax.ShapeDtypeStruct((rows, chunk), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)]
    state = wave_state = ()
    if engine._recurrent:
        state = (engine._state,)
        wave_state = (
            engine._state, jax.eval_shape(lambda: make_recurrent_state(cfg, rows)),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
        )
    return {
        "decode": (engine._decode_jit(window, steps, sampled), abstract((*args, *state))),
        "ragged": (
            engine._ragged_jit(window, steps, sampled, chunk, rows),
            abstract((*args, *wave, *wave_state)),
        ),
    }


@pytest.mark.parametrize(
    "cell,program,ssm_kernels",
    [
        ("mistral-7b-v0.3-int8", "decode", 0),
        ("mistral-7b-v0.3-int8", "ragged", 0),
        ("granite-4.0-h-micro", "decode", 9),
        ("granite-4.0-h-micro", "ragged", 9),
    ],
)
def test_cell_dispatch_program_holds_its_kernels_on_v5e(
    cell, program, ssm_kernels, cell_engine, one_chip, no_persistent_cache
):
    """A dispatch program of a benchmark cell, compiled for the described
    v5e: ONE paged decode read of the decode loop, no window gathered
    there, and, where the model has them, one SSM step kernel a Mamba layer
    of the period (nine in granite's), each under the ``ssm`` scope; the
    chunk that rides along in a ragged program reads and scans through XLA.
    The state goes out where it came in, and the program's temporaries stay
    under TWO layers' state: no copy of the stacked state is made."""
    engine = cell_engine(cell)
    assert engine._ssm_impl == ("pallas" if ssm_kernels else "xla")
    fn, args = _dispatch_programs(engine, one_chip)[program]
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 1 + ssm_kernels
    assert sum("paged_decode_attention" in line for line in kernels) == 1
    assert sum("/mamba/ssm/jit(ssm_step_pallas)/ssm/pallas_call" in line
               for line in kernels) == ssm_kernels
    assert "gather_window" not in hlo
    if program == "ragged":
        assert "chunk_loop/" in hlo and "decode_loop/" in hlo
    if ssm_kernels:
        memory = compiled.memory_analysis()
        state_bytes = engine.config.recurrent_state_bytes(engine.runtime.max_batch_size)
        assert memory.alias_size_in_bytes >= state_bytes
        if program == "decode":
            assert memory.temp_size_in_bytes < 2 * state_bytes // engine.config.n_mamba_layers


def _whole_side_copies(hlo: str, side, kinds=("copy", "copy-start")) -> tuple[list[str], list[str]]:
    """(copies, scatters) of a compiled program over an array of one pool
    side's SIZE, in whatever shape and layout (the scatter's operand had the
    page offset above the KV heads; through PR 47 the kernel's view of a
    head of 64 folded two positions a row): the ``copy`` / ``copy-start``
    operations outside fusions (or the ``kinds`` asked for), and every
    ``scatter``."""
    import re
    from math import prod

    made = re.compile(r"= (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(")
    copies, scatters = [], []
    for name, lines in _computations(hlo).items():
        for line in lines:
            m = made.search(line)
            if not m or prod(map(int, m.group(2).split(","))) != side.size:
                continue
            if m.group(3) in kinds and "fused_computation" not in name:
                copies.append(line.strip()[:120])
            elif m.group(3) == "scatter":
                scatters.append(line.strip()[:120])
    return copies, scatters


# The whole-side copies a dispatch program still makes of a pool side.  A K/V
# side of heads of 64 is STORED two positions a row since PR 49
# (model.positions_per_row): the kernel and the write's loop take it as it lies,
# and the six copies a dispatch PR 46 counted in granite's and LFM2's programs
# are gone.  What stays is a LATENT pool's rope side, 64 wide and stored as
# declared: two copies into the kernel's view (latent_rope_view, another
# arrangement), one into the layout the write's loop runs on and one back
# (ROADMAP S10, the part left open).
_WHOLE_SIDE_COPIES_LEFT = {
    "granite-4.0-h-micro": 0, "lfm2-8b-a1b": 0, "kimi-vl-a3b-instruct": 4}
# LFM2's decode program held two row-major copies of a 0.83 GB side through
# PR 47 (1.675 GB of temporaries at the cell's full size; 0.85 at this cut)
_LFM2_DECODE_TEMPORARIES_GB = 0.2


def _finalize_program(engine, sharding, rows=2):
    """(jitted fn, abstract args, abstract keyword args) of the wave's
    landing, ``jit_finalize``: a two-row wave of two chunks goes into the
    pool page by page (``write_prefill_pages``)."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.mamba import make_recurrent_state

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)

    rt, cfg = engine.runtime, engine.config
    bucket = 2 * rt.prefill_chunk
    i32, f32 = jnp.int32, jnp.float32
    S = jax.ShapeDtypeStruct
    scratch = S((cfg.n_kv_layers, rows, cfg.cache_heads, bucket, cfg.head_dim), engine._k.dtype)
    args = [
        engine._k, engine._v, scratch, scratch, engine._last, engine._lens,
        S((rows,), i32), S((rows,), i32), S((rows, rt.prefill_chunk, cfg.vocab_size), f32),
        engine._slot_keys, engine._temp, engine._top_k, engine._top_p,
        S((rows,), jnp.uint32), S((rows,), f32), S((rows,), i32), S((rows,), f32),
        engine._tables, S((rows, rt.pages_per_seq()), i32), S((rows, bucket // rt.page_size), i32),
    ]
    kw = {}
    if engine._recurrent:
        kw = {"state": engine._state,
              "wstate": jax.eval_shape(lambda: make_recurrent_state(cfg, rows))}
    return engine._finalize_jit(bucket, rows, False), abstract(args), abstract(kw)


def test_a_pool_of_whole_lane_tiles_lowers_to_the_program_it_was(cell_engine, one_chip, monkeypatch):
    """The decode dispatch of a cell whose heads are whole lane tiles
    (Mistral's, 128) is outside the stored form's reach: ``positions_per_row``
    returns 1, the pool is the declared one, and the program lowers to the
    same text with the rule consulted as with a rule that can only say 1."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M

    engine = cell_engine("mistral-7b-v0.3-int8")
    cfg, page = engine.config, engine.runtime.page_size
    assert M.positions_per_row(cfg.head_dim, page, engine._k.dtype) == 1
    assert engine._k.shape[3:] == (page, cfg.head_dim)

    def lowered():
        jax.clear_caches()  # trace anew: the rule is consulted while the kernel's call is traced
        args, window, steps, sampled = engine._decode_args()
        abstract = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
        return engine._decode_jit(window, steps, sampled).lower(*abstract(args)).as_text()

    with_the_rule = lowered()
    consulted = []
    monkeypatch.setattr(M, "positions_per_row", lambda *a: consulted.append(a) or 1)
    assert lowered() == with_the_rule
    assert (cfg.head_dim, page, jnp.dtype(engine._k.dtype)) in [
        (w, p, jnp.dtype(d)) for w, p, d in consulted]


@pytest.mark.parametrize(
    "cell,program",
    [
        ("mistral-7b-v0.3-int8", "decode"),
        ("mistral-7b-v0.3-int8", "ragged"),
        ("command-a-plus-05-2026", "decode"),
        ("kimi-vl-a3b-instruct", "decode"),
        ("qwen3-next-80b-a3b-instruct", "decode"),
        ("granite-4.0-h-micro", "decode"),
        ("granite-4.0-h-micro", "ragged"),
        ("granite-4.0-h-micro", "finalize"),
        ("lfm2-8b-a1b", "decode"),
        ("lfm2-8b-a1b", "ragged"),
        ("lfm2-8b-a1b", "finalize"),
    ],
)
def test_cell_dispatch_program_writes_its_tokens_in_place_on_v5e(
    cell, program, cell_engine, one_chip, no_persistent_cache
):
    """A dispatch program of a benchmark cell, compiled for the described
    v5e, ends in ``consolidate_ring_paged``'s loop of window updates on the
    donated pool: NO scatter over a pool side, the pools go out where they
    came in, and no side whose STORED rows are 128 numbers wide or wider
    (heads of 128 and 256, Kimi's 512-wide ``c`` side, both kinds of
    command-a-plus's pools and, since PR 49, the heads of 64 of granite and
    LFM2, stored two positions a row) is copied whole: through PR 45 the
    scatter cost each such side a copy into a layout with the page offset
    above the KV heads and one back, every dispatch, and through PR 47 a
    side of heads of 64 kept six copies a dispatch (PERF.md section 6, PRs
    46 and 49).  Kimi's rope side, stored as declared, is still held another
    way by the device than by the loop and the kernel: not more copies than
    ``_WHOLE_SIDE_COPIES_LEFT`` records.  The wave's landing (``finalize``:
    ``write_prefill_pages``, a page-granular set) copies no side either."""
    import jax

    engine = cell_engine(cell)
    if program == "finalize":
        fn, args, kw = _finalize_program(engine, one_chip)
        compiled = fn.lower(*args, **kw).compile()
    elif program == "ragged":
        fn, args = _dispatch_programs(engine, one_chip)[program]
        compiled = fn.lower(*args).compile()
    else:
        def abstract(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

        args, window, steps, sampled = engine._decode_args()
        carried = {"state": engine._state} if engine._recurrent else {}
        if engine._moe_zero is not None:
            carried["moe"] = engine._moe_zero
        compiled = engine._decode_jit(window, steps, sampled).lower(
            *abstract(args), **abstract(carried)).compile()
    hlo = compiled.as_text()
    assert ("/kv_write/" in hlo) if program == "finalize" else ("/kv_write/while/body" in hlo)
    sides = jax.tree.leaves((engine._k, engine._v))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(side.nbytes for side in sides)
    left = 0
    for side in {side.shape: side for side in sides}.values():
        copies, scatters = _whole_side_copies(hlo, side)
        # the landing's page-granular set IS a scatter, in place on the donated side
        assert program == "finalize" or not scatters, scatters
        if side.shape[-1] >= 128:
            assert not copies, (side.shape, copies)
        left += len(copies)
    assert left <= _WHOLE_SIDE_COPIES_LEFT.get(cell, 0), (cell, left)
    if (cell, program) == ("lfm2-8b-a1b", "decode"):
        assert memory.temp_size_in_bytes < _LFM2_DECODE_TEMPORARIES_GB * 1e9
    print("whole-side copies left:", cell, program, left,
          "temporaries GB:", round(memory.temp_size_in_bytes / 1e9, 3))


# the write ALONE at a pool's full shape: (L, N, K, page, head, rows, T, table entries)
WRITE_ALONE_SHAPES = {
    "lfm2-8b-a1b": (3, 4225, 8, 64, 64, 128, 8, 33),
    "granite-4.0-h-micro": (4, 1281, 8, 64, 64, 64, 8, 32),
    "heads-of-32": (3, 4225, 8, 64, 32, 128, 8, 33),
    "mistral-7b-v0.3-int8": (32, 513, 8, 64, 128, 32, 8, 32),
    "command-a-plus-05-2026-window": (3, 2113, 8, 64, 128, 32, 4, 66),
}


@pytest.mark.parametrize("shape", sorted(WRITE_ALONE_SHAPES))
def test_the_write_alone_keeps_a_stored_side_in_place_on_v5e(shape, one_chip, no_persistent_cache):
    """``consolidate_ring_paged`` alone on a donated pool at a cell's FULL
    pool shape (every attention layer: the cut engines above keep one of
    granite's four), compiled for the described v5e: no operation makes an
    array of a side's size but the loop's own updates in place, and the
    program's temporaries are a window's, not a side's.  At heads of 64 and
    32 that holds for windows of whole groups of 8 stored rows
    (``model._write_windows``): a window of 5 rows made the compiler relay
    the side into a layout of its own and back (PERF.md section 6, PR 49)."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, N, K, page, hd, B, T, entries = WRITE_ALONE_SHAPES[shape]
    f = M.positions_per_row(hd, page, jnp.bfloat16)
    assert f == 128 // min(hd, 128)
    pool = (S((L, N, K, page // f, f * hd), jnp.bfloat16),) * 2
    ring = (S((L, T, B, K, hd), jnp.bfloat16),) * 2
    compiled = jax.jit(M.consolidate_ring_paged, donate_argnums=0).lower(
        pool, ring, S((B, entries), jnp.int32), S((B,), jnp.int32), S((B,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    copies, scatters = _whole_side_copies(hlo, pool[0], ("copy", "copy-start", "transpose", "reshape"))
    assert not copies and not scatters, (copies, scatters)
    assert "/kv_write/while/body" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("shape", ["lfm2-8b-a1b", "granite-4.0-h-micro", "heads-of-32"])
def test_the_landing_alone_sets_its_pages_in_a_stored_side_on_v5e(shape, one_chip, no_persistent_cache):
    """``write_prefill_pages`` alone (what ``jit_finalize`` does to the pool)
    on a donated pool at a cell's FULL pool shape, a wave of four rows of
    1,024 tokens, compiled for the described v5e: the wave's scratch is
    packed into stored rows, never the pool, so nothing copies, reshapes or
    transposes an array of a side's size, the pools go out where they came in
    and the program's temporaries are the wave's, not a side's (through PR 47
    each side of heads of 64 was copied in and out around the set: 1.66 GB of
    temporaries at LFM2's shape, 19 ms a landing in its cell).  What sets the
    pages is ONE scatter a side over the PAGE axis, in place on the donated
    side in its stored layout."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import model as M

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    L, N, K, page, hd, _, _, _ = WRITE_ALONE_SHAPES[shape]
    f = M.positions_per_row(hd, page, jnp.bfloat16)
    rows, tokens = 4, 1024
    pool = (S((L, N, K, page // f, f * hd), jnp.bfloat16),) * 2
    scratch = (S((L, rows, K, tokens, hd), jnp.bfloat16),) * 2
    compiled = jax.jit(M.write_prefill_pages, donate_argnums=0).lower(
        pool, scratch, S((rows, tokens // page), jnp.int32)).compile()
    copies, scatters = _whole_side_copies(
        compiled.as_text(), pool[0], ("copy", "copy-start", "transpose", "reshape"))
    assert not copies, copies
    assert len(scatters) == 2 and all("{4,3,2,1,0" in line for line in scatters), scatters
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * L * N * K * page * hd * 2
    assert memory.temp_size_in_bytes < 4e6


@pytest.mark.parametrize("ssm_impl", ["xla", "pallas"])
def test_mamba_decode_step_updates_the_state_in_place_on_v5e(
    ssm_impl, one_chip, no_persistent_cache
):
    """One Mamba-2 layer's decode step at granite-4.0-h-micro's widths and
    the benchmark cell's 64 rows, on a stacked state of three layers: the
    program's temporaries stay under ONE layer's state (134 MB), so the
    update is written in place and no copy of the state is made.  With 36
    layers a copy is 4.8 GB, and the cell's 16 GB chip has no room for it.
    With the kernel the compiled step holds ONE ``tpu_custom_call``, under
    the ``ssm`` scope, and no fusion or copy of the state's size at all:
    the state is touched by the kernel alone."""
    import re

    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import mamba as mm
    from calfkit_tpu.inference.config import preset

    config = preset("granite-4.0-h-micro")
    rows, layers = 64, 3

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    leaves = abstract(jax.eval_shape(
        lambda k: jax.tree.map(
            lambda a: a[0], mm.init_mamba_params(config, k, jnp.bfloat16)),
        jax.random.key(0),
    ))
    state = abstract(jax.eval_shape(
        lambda: tuple(s[:layers] for s in mm.make_recurrent_state(config, rows))
    ))
    compiled = jax.jit(
        lambda h, lp, st, im, active: mm.mamba_step(h, lp, st, im, active, config, ssm_impl),
        donate_argnums=(2,),
    ).lower(
        jax.ShapeDtypeStruct((rows, 1, config.d_model), jnp.bfloat16, sharding=one_chip),
        leaves, state,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows,), jnp.bool_, sharding=one_chip),
    ).compile()
    memory = compiled.memory_analysis()
    one_layer = config.recurrent_state_bytes(rows) // config.n_mamba_layers
    assert memory.alias_size_in_bytes >= layers * one_layer  # the state goes out where it came in
    assert memory.temp_size_in_bytes < one_layer
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == (ssm_impl == "pallas")
    # an operation that makes a layer's slice or the whole state (bitcasts,
    # parameters and tuple plumbing make nothing)
    H, P, N = config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state
    state_sized = re.compile(
        rf"= f32\[(?:{layers},)?{rows},(?:{H},{P}|{H * P}),{N}\]\S* (\w[\w\-]*)\(")
    made = [m.group(1) for m in map(state_sized.search, hlo.splitlines())
            if m and m.group(1) not in ("bitcast", "parameter", "get-tuple-element")]
    if ssm_impl == "pallas":
        # the kernel's result is a tuple: the whole stacked state and y
        assert "ssm/jit(ssm_step_pallas)/ssm/pallas_call" in kernels[0]
        assert f"(f32[{layers},{rows},{H * P},{N}]" in kernels[0]
        assert made == [], made
    else:
        assert "copy" not in made and "fusion" in made, made


def _paged_decode_entry(shape):
    import jax.numpy as jnp

    from calfkit_tpu.inference import pallas_attention as PA

    K, G, hd, rows, window, pages, layers = DECODE_PAGED_WIDTHS["mistral-7b-v0.3"]
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = (
        shape((rows, K, G, hd), bf16),
        *(shape((layers, pages, K, PAGE, hd), bf16),) * 2, shape((), i32),
        shape((rows, window // PAGE), i32), shape((rows,), i32),
    )
    return (PA.paged_decode_attention_pallas,
            lambda *a: PA.paged_decode_attention_pallas(*a, wpages=window // PAGE), args)


def _ssm_step_entry(shape):
    import jax.numpy as jnp

    from calfkit_tpu.inference import pallas_ssm as PS

    layers, rows, H, P, N = 3, 64, 64, 64, 128  # granite-4.0-h-micro's state
    f32 = jnp.float32
    args = (
        shape((layers, rows, H, P, N), f32), shape((), jnp.int32), shape((rows, 1, H), f32),
        shape((rows, 1, H, P), f32), shape((rows, 1, N), f32), shape((rows, 1, N), f32),
        shape((rows,), jnp.bool_),
    )
    return PS.ssm_step_pallas, lambda *a: PS.ssm_step_pallas(*a), args


# the delta-rule cells' state: 6 layers of 32 heads of 128 x 128, (slots, a decay a key channel)
DELTA_STEP_STATES = {"ling-3.0-flash-vl": (128, True), "qwen3-next-80b-a3b-instruct": (64, False)}


def _delta_step_args(shape, rows=128, by_channel=True):
    import jax.numpy as jnp

    layers, H, dk, dv = 6, 32, 128, 128
    f32 = jnp.float32
    return (
        shape((layers, rows, H, dk, dv), f32), shape((), jnp.int32),
        shape((rows, H, dk), f32), shape((rows, H, dk), f32), shape((rows, H, dv), f32),
        shape((rows, H), f32), shape((rows, H, dk) if by_channel else (rows, H), f32),
        shape((rows,), jnp.bool_),
    )


def _delta_step_entry(shape):
    from calfkit_tpu.inference import pallas_gdn as PG

    return PG.delta_step_pallas, lambda *a: PG.delta_step_pallas(*a), _delta_step_args(shape)


@pytest.mark.parametrize("cell", sorted(DELTA_STEP_STATES))
def test_delta_step_compiles_for_v5e(cell, one_chip, no_persistent_cache):
    """The delta step kernel alone at both delta-rule cells' state, the decay
    by key channel and by head: ONE kernel, the whole stacked state goes out
    where it came in, and nothing of a layer's size is made beside it."""
    import jax

    from calfkit_tpu.inference import pallas_attention as PA
    from calfkit_tpu.inference import pallas_gdn as PG

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    rows, by_channel = DELTA_STEP_STATES[cell]
    args = _delta_step_args(shape, rows, by_channel)
    before = PA.KERNEL_TRACES["delta_step", "compiled"]
    PG.delta_step_pallas.clear_cache()
    compiled = jax.jit(
        lambda *a: PG.delta_step_pallas(*a), donate_argnums=0).lower(*args).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "jit(delta_step_pallas)/state/pallas_call" in hlo
    assert PA.KERNEL_TRACES["delta_step", "compiled"] == before + 1
    memory = compiled.memory_analysis()
    state_bytes = 6 * rows * 32 * 128 * 128 * 4
    assert memory.alias_size_in_bytes == state_bytes
    assert memory.temp_size_in_bytes < state_bytes // (6 * rows)  # under ONE row of a layer


# the three shapes held by SHARE (experts held, hidden, expert width) with their cells'
# slots: command-a-plus's, Qwen3-Next's, Ling's
MOE_STEP_SHAPES = {
    "command-a-plus-05-2026": ((16, 4096, 4096), 32),
    "qwen3-next-80b-a3b-instruct": ((128, 2048, 512), 64),
    "ling-3.0-flash-vl": ((64, 2560, 768), 128),
}


def _moe_step_args(shape, cell="ling-3.0-flash-vl", layers=3):
    import jax.numpy as jnp

    (E, D, F), rows = MOE_STEP_SHAPES[cell]
    bf16 = jnp.bfloat16
    stack = {"w_gate": shape((layers, E, D, F), bf16), "w_up": shape((layers, E, D, F), bf16),
             "w_down": shape((layers, E, F, D), bf16)}
    return (shape((rows, D), bf16), shape((rows, E), jnp.float32), shape((E,), jnp.bool_),
            stack, shape((), jnp.int32))


def _moe_step_entry(shape):
    from calfkit_tpu.inference import pallas_moe as PM

    return PM.moe_step_pallas, lambda *a: PM.moe_step_pallas(*a), _moe_step_args(shape)


@pytest.mark.parametrize("cell", sorted(MOE_STEP_SHAPES))
def test_moe_step_compiles_for_v5e(cell, one_chip, no_persistent_cache):
    """The expert step kernel at the three shapes held by share, nested as a
    decode dispatch nests it (a scan over the stack's layers inside a loop
    over steps, the layer a traced index): ONE kernel, under the caller's
    scope and named ``experts``; the stack is read where it lies: no array
    of a stack's or of a layer's experts' shape among the temporaries or made
    in a loop, and the temporaries are under ONE expert's matrices."""
    import re

    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import pallas_attention as PA
    from calfkit_tpu.inference import pallas_moe as PM

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    (E, D, F), rows = MOE_STEP_SHAPES[cell]
    Lm, steps = 3, 4
    h, gates, hit, stack, _ = _moe_step_args(shape, cell, Lm)

    def dispatch(stack, x, gates, hit):
        def layer(x, m):
            with jax.named_scope("moe"):
                return x + PM.moe_step_pallas(x, gates, hit, stack, m), None

        def step(_, x):
            return lax.scan(layer, x, jnp.arange(Lm, dtype=jnp.int32))[0]

        return lax.fori_loop(0, steps, step, x)

    before = PA.KERNEL_TRACES["moe_step", "compiled"]
    PM.moe_step_pallas.clear_cache()
    compiled = jax.jit(dispatch).lower(stack, h, gates, hit).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "moe/jit(moe_step_pallas)/experts/pallas_call" in hlo
    assert PA.KERNEL_TRACES["moe_step", "compiled"] == before + 1
    a_stack = re.compile(
        rf"= bf16\[(?:{Lm},)?{E},(?:{D},{F}|{F},{D})\]\S* (copy|transpose|fusion)\(")
    assert not a_stack.search(hlo)
    assert not _made_in_loops(hlo, (f"bf16[{E},{D},{F}]", f"bf16[{E},{F},{D}]"))
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * D * F * 2  # ONE expert's matrices


def _latent_decode_entry(shape):
    from calfkit_tpu.inference import pallas_attention as PA

    args, static = _latent_decode_args(shape)
    return (PA.latent_decode_attention_pallas,
            lambda *a: PA.latent_decode_attention_pallas(*a, **static), args)


# a window stack's chunk attention at the command-a-plus cell's own widths: 8 KV heads
# of 16 query heads of 128, a chunk of 2,048 against the widest scratch, one row a wave
CHUNK_ATTENTION_WIDTHS = dict(K=8, G=16, hd=128, chunk=2048, scratch=18432, window=4096)


def _chunk_attention_args(shape, rows=1):
    import jax.numpy as jnp

    w = CHUNK_ATTENTION_WIDTHS
    bf16, i32 = jnp.bfloat16, jnp.int32
    return (
        shape((rows, w["chunk"], w["K"] * w["G"], w["hd"]), bf16),
        *(shape((rows, w["K"], w["scratch"], w["hd"]), bf16),) * 2,
        shape((rows,), i32), shape((rows,), i32),
    )


def _chunk_attention_entry(shape):
    from calfkit_tpu.inference import pallas_attention as PA

    window = CHUNK_ATTENTION_WIDTHS["window"]
    return (PA.chunk_attention_pallas,
            lambda *a: PA.chunk_attention_pallas(*a, window=window), _chunk_attention_args(shape))


@pytest.mark.parametrize("window", [CHUNK_ATTENTION_WIDTHS["window"], 0])
def test_chunk_attention_compiles_for_v5e(window, one_chip, no_persistent_cache):
    """The chunk attention kernel alone, in its two static forms (a window
    layer's lower bound, a global layer's none): a tile of 128 positions x 16
    heads against key blocks of 1,024, 8 MB of scores that never leave VMEM;
    the only float32 the program makes outside the kernel is none at all."""
    import jax

    from calfkit_tpu.inference import pallas_attention as PA

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    before = PA.KERNEL_TRACES["chunk_attention", "compiled"]
    PA.chunk_attention_pallas.clear_cache()
    compiled = jax.jit(
        lambda *a: PA.chunk_attention_pallas(*a, window=window)
    ).lower(*_chunk_attention_args(shape)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1 and "chunk_attention" in hlo
    assert PA.KERNEL_TRACES["chunk_attention", "compiled"] == before + 1
    w = CHUNK_ATTENTION_WIDTHS
    assert PA.chunk_attention_tiles(w["chunk"], w["scratch"]) == (128, 1024)
    # the key axis: every block of the scratch, or the six a window and a tile can touch
    assert PA.chunk_attention_key_steps(w["chunk"], w["scratch"], window, 128, 1024) == (
        6 if window else 18)
    # q and o regrouped around the kernel (64 MB each at most), never a score buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2048 * 128 * 128 * 2 + 2 ** 20
    assert " f32[" not in hlo.replace("f32[]", "")


@pytest.mark.parametrize(
    "kernel",
    ["paged_decode", "ssm_step", "latent_decode", "chunk_attention", "delta_step", "moe_step"])
def test_kernel_bytes_do_not_depend_on_the_caller(
    kernel, one_chip, no_persistent_cache, monkeypatch
):
    """The serialized kernel is hashed into the persistent cache's key, and
    a jitted entry point is traced once a process, from whichever program
    calls it first.  Under the compile-cache rule its bytes are the same
    from a shallow and from a deep, differently scoped call stack."""
    import jax

    from calfkit_tpu.inference.compile_cache import enable_compile_cache

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    entry, f, args = {"paged_decode": _paged_decode_entry, "ssm_step": _ssm_step_entry,
                      "latent_decode": _latent_decode_entry,
                      "chunk_attention": _chunk_attention_entry,
                      "delta_step": _delta_step_entry,
                      "moe_step": _moe_step_entry}[kernel](shape)

    def deep(*a, depth=4):
        if depth:
            return deep(*a, depth=depth - 1)
        with jax.named_scope("another_program"):
            return f(*a)

    f.__name__ = f.__qualname__ = deep.__name__ = deep.__qualname__ = "f"  # one module name for both

    def lowered(fn):
        entry.clear_cache()  # trace it from HERE
        return jax.jit(fn).lower(*args).as_text()

    option = "jax_traceback_in_locations_limit"
    before = getattr(jax.config, option)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent")  # no update
    try:
        enable_compile_cache()  # with ten frames in, the two differ
        assert "tpu_custom_call" in lowered(f)
        assert lowered(f) == lowered(deep)
    finally:
        jax.config.update(option, before)


def test_entry_point_list_is_complete():
    """The kernel modules' entry points are the eight compiled above (a
    merged read calls the plain one), ONE ``pallas_call`` a kernel body,
    and no other module of the package makes one: a kernel added without a
    compile of its own fails here."""
    import glob
    import inspect

    from calfkit_tpu.inference import pallas_attention as PA
    from calfkit_tpu.inference import pallas_gdn as PG
    from calfkit_tpu.inference import pallas_moe as PM
    from calfkit_tpu.inference import pallas_ssm as PS

    def entries(module):
        return {name for name, fn in vars(module).items()
                if name.endswith("_pallas") and callable(fn)}

    assert entries(PA) == {
        "paged_decode_attention_pallas", "merged_paged_decode_attention_pallas",
        "latent_decode_attention_pallas", "merged_latent_decode_attention_pallas",
        "chunk_attention_pallas",
    }
    assert entries(PS) == {"ssm_step_pallas"}
    assert entries(PG) == {"delta_step_pallas"}
    assert entries(PM) == {"moe_step_pallas"}
    assert inspect.getsource(PA).count("pl.pallas_call(") == 3
    assert inspect.getsource(PS).count("pl.pallas_call(") == 1
    assert inspect.getsource(PG).count("pl.pallas_call(") == 1
    assert inspect.getsource(PM).count("pl.pallas_call(") == 1
    with_kernels = sorted(
        os.path.basename(path)
        for path in glob.glob(os.path.join(os.path.dirname(PA.__file__), "*.py"))
        if "pl.pallas_call(" in open(path).read()
    )
    assert with_kernels == [
        "pallas_attention.py", "pallas_gdn.py", "pallas_moe.py", "pallas_ssm.py"]


# ---------------------------------------------------------------------------
# A latent pool and routed experts (kimi-vl-a3b-instruct): the latent decode
# read in place in the decode step, the TPU compiler's own ragged-dot kernel
# for a chunk's grouped expert products
# ---------------------------------------------------------------------------


@pytest.mark.slow  # 2.7 GB of weights and two whole-program compiles on every core: 50 s that
# starve the timing-bound engine tests of a tier-1 run's other workers; the offline lane runs it
def test_latent_expert_cell_dispatch_programs_compile_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch and the ragged program of the cell's configuration
    at its published WIDTHS and runtime (3 of its 7 layers: the dense one and
    TWO expert layers, all 64 experts: a stack from which a layer can be
    sliced), with the kernel resolved as "auto"
    resolves it on a chip, compiled for the described v5e: the decode step
    reads the latent through the latent decode kernel under
    ``mla/attention`` (two calls: the unrolled dense layer and the expert
    layers' scan), gathers NO window and slices no layer out of the pool
    there, and multiplies every expert; the rope side's view is made once a
    dispatch, in the entry computation, and nothing of the c side's size is
    made in a loop's body; the chunk that rides along groups its tokens when
    it is wider than ``moe.dense_form``'s limit for this shape, 1,536 (two rows of 1,024: three
    ``ragged-dot`` kernels an expert layer; one row takes the dense form, as
    the decode step does: the chip's readings in ``moe.py``), and since PR 34
    those kernels take the STACK of the expert layers, flattened, so that NO
    operation of a loop's body makes an array of a layer's experts (the
    parent's program held three such fusions, 1.1 GB a layer written and read
    again) and the program's temporaries did not grow; the two parts of the
    pool go out where they came in."""
    import json
    import re
    from dataclasses import replace

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", "kimi-vl-a3b-instruct.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert InferenceEngine(
        replace(config, n_layers=3), replace(runtime, compilation_cache=False)
    )._attn_impl == "xla"  # "auto" on this process's CPU: the reference path
    engine = InferenceEngine(
        replace(config, n_layers=3),
        replace(runtime, compilation_cache=False, attention_impl="pallas"))
    assert engine._attn_impl == "pallas" and engine._ssm_impl == "xla"

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    def own_kernels(hlo):
        return [line for line in hlo.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line
                and "latent_decode_attention" in line]

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    assert window == 4096 == rt.max_seq_len
    rows, chunk = 2, rt.prefill_chunk
    scratch = [jax.ShapeDtypeStruct((cfg.n_kv_layers, rows, 1, 2 * chunk, width), engine._k.dtype)
               for width in cfg.cache_dims]
    wave = [*scratch, jax.ShapeDtypeStruct((rows, chunk), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)]
    zero = engine._moe_zero
    decode = engine._decode_jit(window, steps, sampled).lower(
        *abstract(args), moe=abstract(zero)).compile()
    hlo = decode.as_text()
    kernels = own_kernels(hlo)
    assert len(kernels) == 2 and all("/mla/attention/" in line for line in kernels), kernels
    assert all("decode_loop/" in line for line in kernels)
    assert "gather_window" not in hlo and "/mlp/moe/experts" in hlo
    assert not _expert_stack_copies(hlo, cfg) and "ragged-dot" not in hlo
    # the rope side's view: once a dispatch, in the entry computation; the c
    # side: no operation of a loop's body makes an array of its size or of
    # ONE LAYER's (the XLA read's dynamic-slice copy)
    L, N, _, page, r = engine._k.shape
    dr = engine._v.shape[-1]
    view = re.compile(rf"= bf16\[{L},{N},1,{page // 2},{2 * dr}\]\S* (\w[\w\-]*)\(")
    c_side = re.compile(rf"= bf16\[(?:{L},)?{N},1,{page},{r}\]\S* (\w[\w\-]*)\(")
    plumbing = ("bitcast", "parameter", "get-tuple-element")
    for pattern, in_entry in ((view, 1), (c_side, None)):
        made = {
            name: [m.group(1) for m in map(pattern.search, lines)
                   if m and m.group(1) not in plumbing]
            for name, lines in _computations(hlo).items()
        }
        entry = made.pop("ENTRY")
        if in_entry is not None:
            assert len(entry) == in_entry, entry
        assert not any(
            ops for name, ops in made.items() if "while" in name or "body" in name), made
    pool_bytes = engine._k.nbytes + engine._v.nbytes
    assert decode.memory_analysis().alias_size_in_bytes >= pool_bytes
    ragged = engine._ragged_jit(window, steps, sampled, chunk, rows).lower(
        *abstract((*args, *wave)), moe=abstract(zero), wmoe=abstract(zero),
        true_lens=abstract(jax.ShapeDtypeStruct((rows,), jnp.int32))).compile()
    hlo = ragged.as_text()
    # the compiler's kernel carries its own op name, "ragged-dot-none", and NOT
    # the scope path it was called under: a device trace shows it unscoped
    # (PERF.md section 7), so it is told here by its operands: all 64 experts
    grouped = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line and "= bf16[" in line
               and "ragged-dot-none" in line]
    assert len(grouped) == 3  # gate, up, down in the body of the expert layers' scan
    # their operand: the two layers' stack, flattened, never a slice of it
    assert all("bf16[128,2048,1408]" in line or "bf16[128,1408,2048]" in line for line in grouped)
    assert not _made_in_loops(hlo, ("bf16[64,2048,1408]", "bf16[64,1408,2048]"))
    assert not _expert_stack_copies(hlo, cfg)
    assert "decode_loop/" in hlo and "chunk_loop/" in hlo
    assert len(own_kernels(hlo)) == 2
    # the chunk still reads its rows' windows through XLA (the reference
    # path, S > 1); the decode steps beside it gather nothing
    assert not any("decode_loop/" in line and "gather_window" in line
                   for line in hlo.splitlines())
    one = [jax.ShapeDtypeStruct((a.shape[0], 1, *a.shape[2:]), a.dtype) for a in scratch]
    narrow = engine._ragged_jit(window, steps, sampled, chunk, 1).lower(
        *abstract((*args, *one, jax.ShapeDtypeStruct((1, chunk), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32))),
        moe=abstract(zero), wmoe=abstract(zero),
        true_lens=abstract(jax.ShapeDtypeStruct((1,), jnp.int32))).compile()
    assert "ragged-dot" not in narrow.as_text()  # 1,024 tokens: the dense form
    assert not _expert_stack_copies(narrow.as_text(), cfg)
    assert not _made_in_loops(narrow.as_text(), ("bf16[64,2048,1408]", "bf16[64,1408,2048]"))
    report = {"decode": decode.memory_analysis().temp_size_in_bytes,
              "ragged x2": ragged.memory_analysis().temp_size_in_bytes,
              "ragged x1": narrow.memory_analysis().temp_size_in_bytes}
    _no_larger_than_at_pr_44("kimi", report)
    print("temporaries, bytes:", report)


# The dense expert products' SPELLING (PR 45): what PR 44's "20 lines" showed, kept
# ---------------------------------------------------------------------------

# (experts held, hidden, expert width): Kimi's, Qwen3-Next's, command-a-plus's,
# Ling's and LFM2's, the five shapes a cell holds
HELD_EXPERT_SHAPES = [
    (64, 2048, 1408), (128, 2048, 512), (16, 4096, 4096), (64, 2560, 768), (32, 2048, 1792)]


@pytest.mark.parametrize("shape", HELD_EXPERT_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_the_dense_products_nested_in_a_dispatch_copy_no_stack_on_v5e(
        shape, one_chip, no_persistent_cache):
    """The dense form as a decode dispatch nests it: a scan over the expert
    layers inside a loop over steps, at 128 rows (a whole lane tile of rows:
    from there on, at ANY of the five shapes, the TPU compiler takes the
    rows-first einsum as ``jnp.einsum`` lowers it, the experts by the rows,
    the experts its input and the rows its kernel, and wants the experts
    with the hidden size minor; at 64 or 96 rows it does not).  Spelled ``td,edf->etf`` the stack is invariant in the outer
    loop, so that change of layout is hoisted out of both loops as a COPY OF
    THE WHOLE STACK (PRs 40 and 44 met it as 7.4 and 6.4 GB of temporaries
    and sent two shapes' every product to the grouped form).  Spelled weights
    first (``edf,td->etf``), as ``moe.experts_dense`` is, the experts are
    read where they lie: nothing of a stack's size among the temporaries,
    and nothing of a layer's made in a loop.  So the spelling cannot be
    tidied back."""
    import re

    import jax
    import jax.numpy as jnp
    from jax import lax

    from calfkit_tpu.inference import moe
    from tests.arch_harness import experts_dense_rows_first

    E, D, F = shape
    Lm, T, k, steps = 3, 128, 4, 8

    def struct(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def dispatch(dense):
        def run(stack, x, chosen, weights):
            onehot = chosen[..., None] == jnp.arange(E, dtype=jnp.int32)

            def layer(x, m):
                lp = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False), stack)
                return x + dense(x, onehot, weights, lp), None

            def step(_, x):
                return lax.scan(layer, x, jnp.arange(Lm, dtype=jnp.int32))[0]

            return lax.fori_loop(0, steps, step, x)
        return run

    stack = {"w_gate": struct((Lm, E, D, F)), "w_up": struct((Lm, E, D, F)),
             "w_down": struct((Lm, E, F, D))}
    args = (stack, struct((T, D)), struct((T, k), jnp.int32), struct((T, k), jnp.float32))
    a_stack = re.compile(rf"= bf16\[{Lm},{E},(?:{D},{F}|{F},{D})\]\S* (copy|transpose|fusion)\(")
    one_stack = Lm * E * D * F * 2
    old = jax.jit(dispatch(experts_dense_rows_first)).lower(*args).compile()
    assert a_stack.search(old.as_text())
    assert old.memory_analysis().temp_size_in_bytes >= one_stack
    new = jax.jit(dispatch(moe.experts_dense)).lower(*args).compile()
    hlo = new.as_text()
    assert not a_stack.search(hlo)
    assert not _made_in_loops(hlo, (f"bf16[{E},{D},{F}]", f"bf16[{E},{F},{D}]"))
    assert new.memory_analysis().temp_size_in_bytes < one_stack // Lm // 8  # far under ONE layer


# Gated DeltaNet beside gated attention, the experts held by share
# (qwen3-next-80b-a3b-instruct): the paged decode read of a head of 256 in
# the two attention layers, the state's pass as XLA in place, the expert
# stacks read where they lie
# ---------------------------------------------------------------------------


def _gdn_cell_engine(held: int | None = None):
    """The engine of the cell's configuration at its published WIDTHS, its 8
    layers (TWO periods: a scan that could copy a period's weights) and its
    runtime; ``held`` experts of the 512 where the test has no use for all
    128 (the gate keeps its 512 outputs)."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", "qwen3-next-80b-a3b-instruct.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert config.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    if held is not None:
        config = replace(config, n_routed_experts=held)
    assert InferenceEngine(
        replace(config, n_layers=4, layer_types=config.layer_types[:4], n_routed_experts=8),
        replace(runtime, compilation_cache=False, max_batch_size=2, num_kv_pages=129),
    )._attn_impl == "xla"  # "auto" on this process's CPU: the reference path
    engine = InferenceEngine(
        config, replace(runtime, compilation_cache=False, attention_impl="pallas"))
    # the described v5e holds a float32 delta-rule state of whole tiles
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas", "pallas")
    return engine


def _delta_step_kernels(hlo: str) -> list[str]:
    """The delta step kernel's calls (``pallas_gdn.py``), each under
    ``decode_loop/.../gdn/state/``: where ``gdn_state_roofline`` reads."""
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "/jit(delta_step_pallas)/state/pallas_call" in line]
    assert all("decode_loop/" in k and "/gdn/state/" in k for k in kernels), kernels
    return kernels


def _under_moe_experts(hlo: str) -> bool:
    """Does a decode step's expert product stand under ``.../mlp/moe/experts``:
    the dense form's fusions, or the step kernel (a jit of its own, which
    ``trace_reduce.scope_path`` leaves out of the path)?"""
    import re

    return bool(re.search(r"/mlp/moe/(?:jit\(moe_step_pallas\)/)?experts", hlo))


def _moe_step_kernels(hlo: str, engine, a_loop: int) -> list[str]:
    """The expert step kernel's calls (``pallas_moe.py``) in a program of
    ``engine``: ``a_loop`` of them (the expert layers the decode loop's
    bodies unroll) where the engine resolved the kernel, each under
    ``decode_loop/.../mlp/moe/`` and named ``experts``, where
    ``moe_expert_roofline`` reads; none where it did not."""
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "/jit(moe_step_pallas)/experts/pallas_call" in line]
    assert all("decode_loop/" in k and "/mlp/moe/" in k for k in kernels), kernels
    assert len(kernels) == (a_loop if engine._moe_step_impl == "pallas" else 0), kernels
    return kernels


def _gdn_decode_checks(engine, compiled):
    """One paged decode read (the period's one attention layer, in the
    scan's body) under ``attention``; no window gathered; the state's pass
    the delta step kernel, once a DeltaNet layer of the period, under
    ``gdn/state``; the stacked state and the pool go out where they came in;
    the routed experts of each of the period's four layers the step kernel
    (PR 53: experts held by share), under ``mlp/moe``;
    NO copy of an expert stack, of a layer of it, or of the stacked state
    (the temporaries are under one layer's state: since PR 46 the dispatch's
    tokens go into the pool in place, and the layout copy of the pool that
    stood around the consolidation scatter is gone)."""
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    reads = [k for k in kernels if "paged_decode_attention" in k]
    experts = _moe_step_kernels(hlo, engine, 4)
    assert engine._moe_step_impl == "pallas" and "ragged-dot" not in hlo
    assert len(reads) == 1 and len(_delta_step_kernels(hlo)) == 3 == len(kernels) - 5, kernels
    assert "/attention/" in reads[0] and "decode_loop/" in reads[0]
    assert "gather_window" not in hlo and "/gdn/state/" in hlo and _under_moe_experts(hlo)
    cfg, rt = engine.config, engine.runtime
    assert not _expert_stack_copies(hlo, cfg)
    memory = compiled.memory_analysis()
    state_bytes = cfg.recurrent_state_bytes(rt.max_batch_size)
    pool_bytes = engine._k.nbytes + engine._v.nbytes
    assert memory.alias_size_in_bytes >= state_bytes + pool_bytes
    assert memory.temp_size_in_bytes < state_bytes // cfg.n_recurrent_layers
    return memory


def test_gdn_expert_cell_decode_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch of the new cell at its published widths, 8 layers
    and 64 slots (8 of the 128 held experts: the products' shapes but not
    6 GB of them), compiled for the described v5e."""
    import jax

    engine = _gdn_cell_engine(held=8)

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    args, window, steps, sampled = engine._decode_args()
    assert window == 4096 == engine.runtime.max_seq_len
    compiled = engine._decode_jit(window, steps, sampled).lower(
        *abstract(args), state=abstract(engine._state), moe=abstract(engine._moe_zero)).compile()
    memory = _gdn_decode_checks(engine, compiled)
    print("temporaries, bytes: decode (8 held experts)", memory.temp_size_in_bytes)


@pytest.mark.slow  # 7.3 GB of weights and four whole-program compiles on every core (2 min): the
# offline lane runs it, as it runs the latent cell's; PERF.md section 6, PR 33 has its readings
def test_gdn_expert_cell_dispatch_programs_compile_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch and the ragged programs (a wave of 1, 2 and 4
    rows of 1,024) of the new cell at its FULL size: all 128 held experts of
    8 layers.  No period's weights are copied: a one-row chunk in the dense
    form copied the whole of ``w_gate`` and ``w_up`` into another layout
    (2 x 2.15 GB a dispatch), which is why chunks of this shape take the
    grouped form, whose three products take the stacks FLATTENED to
    ``[8 x 128, ., .]`` and the layer as the one run of groups that is not
    empty (PR 34): no operation of a loop's body makes an array of a layer's
    experts, where the parent's ``chunk_loop`` held three such fusions (0.81 GB
    a layer written and read again: 13% of the cell's busy time), and the
    ragged programs' temporaries did not grow; the chunk's delta rule solves
    its triangular systems; and arguments and temporaries together leave the
    16 GB chip 3 GB of room."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.mamba import make_recurrent_state

    engine = _gdn_cell_engine()

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    zero = engine._moe_zero
    decode = engine._decode_jit(window, steps, sampled).lower(
        *abstract(args), state=abstract(engine._state), moe=abstract(zero)).compile()
    report = {"decode": _gdn_decode_checks(engine, decode).temp_size_in_bytes}
    chunk = rt.prefill_chunk
    assert not moe.dense_form(chunk, cfg) and moe.dense_form(rt.max_batch_size, cfg)
    for rows in (1, 2, 4):
        scratch = [jax.ShapeDtypeStruct(
            (cfg.n_kv_layers, rows, cfg.cache_heads, 2 * chunk, width), engine._k.dtype)
            for width in cfg.cache_dims]
        wave = [*scratch, jax.ShapeDtypeStruct((rows, chunk), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)]
        ragged = engine._ragged_jit(window, steps, sampled, chunk, rows).lower(
            *abstract((*args, *wave)), state=abstract(engine._state),
            wstate=abstract(jax.eval_shape(lambda: make_recurrent_state(cfg, rows))),
            true_lens=abstract(jax.ShapeDtypeStruct((rows,), jnp.int32)),
            moe=abstract(zero), wmoe=abstract(zero)).compile()
        hlo = ragged.as_text()
        assert "decode_loop/" in hlo and "chunk_loop/" in hlo and "ragged-dot" in hlo
        assert "chunk_loop/" in hlo and "/gdn/state/" in hlo
        assert not _expert_stack_copies(hlo, cfg)
        assert not _made_in_loops(hlo, ("bf16[128,2048,512]", "bf16[128,512,2048]"))
        memory = ragged.memory_analysis()
        report[f"ragged x{rows}"] = memory.temp_size_in_bytes
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.0e9
    _no_larger_than_at_pr_44("qwen3-next", report)
    print("temporaries, bytes:", report)


# Window layers beside global ones, pages by cache kind (command-a-plus-05-2026):
# the decode read's WINDOW form over a ring of pages a row, and the cell's
# dispatch programs with a chunk's attention in the chunk kernel (PR 39)
# ---------------------------------------------------------------------------


def test_paged_decode_window_form_compiles_for_v5e(one_chip, no_persistent_cache):
    """The kernel's window form as a window layer's decode read calls it at
    the cell's widths: 3 layers' pool of every slot's ring (66 pages a row),
    the fourth scalar array, the walk around the ring."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference import pallas_attention as PA

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    K, G, hd, rows, ring, layers = 8, 16, 128, 32, 66, 3
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = (shape((layers, rows * ring + 1, K, PAGE, hd), bf16),) * 2
    before = PA.KERNEL_TRACES["paged_decode", "compiled"]
    compiled = jax.jit(
        lambda q, k, v, layer, tables, lens, starts: PA.paged_decode_attention_pallas(
            q, k, v, layer, tables, lens, wpages=ring, window_starts=starts)
    ).lower(
        shape((rows, K, G, hd), bf16), *pool, shape((), i32), shape((rows, ring), i32),
        shape((rows,), i32), shape((rows,), i32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert PA.KERNEL_TRACES["paged_decode", "compiled"] == before + 1


def _window_cell_engine(held: int | None = None, name: str = "command-a-plus-05-2026",
                        ring: int = 66):
    """The engine of a window stack's cell at its published WIDTHS, its
    layers (whole periods W W W G) and its runtime; ``held`` experts of those
    the file holds where the test has no use for gigabytes of them (the gate
    keeps its outputs)."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", f"{name}.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert config.layer_period == ("window", "window", "window", "attention")
    if held is not None:
        config = replace(config, n_routed_experts=held,
                         n_experts_total=config.experts_scored)
    engine = InferenceEngine(
        config, replace(runtime, compilation_cache=False, attention_impl="pallas"))
    assert engine._attn_impl == engine._chunk_attn_impl == "pallas" and engine._ring_pages == ring
    return engine


def _window_cell_programs(engine, one_chip, buckets):
    import re

    import jax
    import jax.numpy as jnp

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    assert window == 18432 == rt.max_seq_len
    zero = engine._moe_zero
    decode = engine._decode_jit(window, steps, sampled).lower(
        *abstract(args), moe=abstract(zero)).compile()
    hlo = decode.as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    # the experts of the period's four layers through the step kernel, where they are held
    # by share (PR 53; a test that holds FEWER than the file's makes a share of any)
    experts = _moe_step_kernels(hlo, engine, 4)
    kernels = [k for k in kernels if k not in experts]
    # the period's four decode reads in the scan's body: three of the window form, one global
    assert len(kernels) == 4 and all("paged_decode_attention" in k for k in kernels)
    assert sum("/attention/window/" in k for k in kernels) == 3
    assert sum("/attention/global/" in k for k in kernels) == 1
    assert "gather_window" not in hlo and _under_moe_experts(hlo)
    assert not _expert_stack_copies(hlo, cfg) and "ragged-dot" not in hlo
    pools = sum(a.nbytes for a in jax.tree.leaves((engine._k, engine._v)))
    assert decode.memory_analysis().alias_size_in_bytes >= pools
    report = {"decode": decode.memory_analysis().temp_size_in_bytes}
    chunk = rt.prefill_chunk
    for bucket in buckets:
        scratch = [jax.ShapeDtypeStruct((cfg.n_kv_layers, 1, cfg.cache_heads, bucket, w),
                                        jnp.bfloat16) for w in cfg.cache_dims]
        wave = [*scratch, jax.ShapeDtypeStruct((1, chunk), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)]
        ragged = engine._ragged_jit(window, steps, sampled, chunk, 1).lower(
            *abstract((*args, *wave)), true_lens=abstract(jax.ShapeDtypeStruct((1,), jnp.int32)),
            moe=abstract(zero), wmoe=abstract(zero)).compile()
        text = ragged.as_text()
        assert "decode_loop/" in text and "chunk_loop/" in text and "ragged-dot" in text
        assert not _expert_stack_copies(text, cfg)
        assert all(re.search(rf'chunk_loop/[^"]*attention/{kind}/', text)
                   for kind in ("window", "global"))
        # the chunk's attention is the kernel, under the scopes swa_device_pct reads: the
        # period's three window layers and its global one, beside the decode steps' reads
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        chunk_calls = [c for c in calls if "chunk_attention" in c]
        assert len(chunk_calls) == 4 < len(calls), (len(chunk_calls), len(calls))
        assert sum(bool(re.search(r"chunk_loop/[^\"]*attention/window/", c)) for c in chunk_calls) == 3
        assert sum(bool(re.search(r"chunk_loop/[^\"]*attention/global/", c)) for c in chunk_calls) == 1
        # and no key block's scores cross HBM: the loop made [1, 8, 16, 2048, 512] float32
        assert not re.search(rf"f32\[1,{cfg.n_kv_heads},\d+,{chunk},\d+\]", text)
        memory = ragged.memory_analysis()
        report[f"ragged {bucket}"] = memory.temp_size_in_bytes
        # no scores of 128 heads over a context, nor over a key block
        assert memory.temp_size_in_bytes < 2.5e9
        # what the program holds at once: arguments (weights, pools, scratch), outputs that
        # alias none of them (a chunk's logits), temporaries
        held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
                - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
        assert held < 16e9, (bucket, held)
        yield_text = text
    return report, yield_text


def test_mellum_cell_dispatch_programs_compile_for_v5e(one_chip, no_persistent_cache):
    """Mellum 2's cell at its published widths, 8 layers (two periods in the
    scan) and 64 slots, 2 of the 64 experts held: the decode dispatch (a ring
    of 18 pages a slot, the rotation by kind outside the scan) and the ragged
    programs of the narrowest and the widest bucket, whose chunk of 2,048 is
    LONGER than the window of 1,024: the period's four decode reads and four
    chunk kernels under both kinds' scopes, both kinds' rope tables, the pools
    out where they came in."""
    engine = _window_cell_engine(held=2, name="mellum2-12b-a2.5b-instruct", ring=18)
    assert engine.config.sliding_window < engine.runtime.prefill_chunk
    report, text = _window_cell_programs(engine, one_chip, (2048, 16384))
    assert "/rope/window/" in text and "/rope/global/" in text
    print("temporaries, bytes (2 held experts):", report)


@pytest.mark.slow  # 7.6 GB of weights and three whole-program compiles on every core: the offline
# lane runs it, as it runs the other expert cells'; PERF.md section 4 has its readings
def test_mellum_cell_programs_at_full_size_fit_the_chip(one_chip, no_persistent_cache):
    """The same at the cell's FULL size, all 64 experts of 12.4 MB in 8
    layers: no stack is copied into another layout, and arguments and
    temporaries together fit the 16 GB chip."""
    engine = _window_cell_engine(name="mellum2-12b-a2.5b-instruct", ring=18)
    report, text = _window_cell_programs(engine, one_chip, (2048, 16384))
    assert not _made_in_loops(text, ("bf16[64,2304,896]",))
    print("temporaries, bytes:", report)


def test_window_cell_dispatch_programs_compile_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch and the widest bucket's ragged program of the new
    cell at its published widths, 4 layers and 32 slots (2 of the 16 held
    experts: the products' shapes but not 6 GB of them), compiled for the
    described v5e: four kernels in the decode loop, the pools go out where
    they came in, the ragged program's temporaries under 2.5 GB."""
    engine = _window_cell_engine(held=2)
    report, _ = _window_cell_programs(engine, one_chip, (16384,))
    print("temporaries, bytes (2 held experts):", report)


@pytest.mark.slow  # 9.5 GB of weights and three whole-program compiles on every core: the offline
# lane runs it, as it runs the other expert cells'; PERF.md section 6, PR 38 has its readings
def test_window_cell_programs_at_full_size_copy_no_expert_stack(one_chip, no_persistent_cache):
    """The same at the cell's FULL size, all 16 held experts of 100.7 MB in
    4 layers, the narrowest and the widest bucket: no operation of a loop's
    body makes an array of a layer's experts and no stack is copied into
    another layout (PR 33's finding at Qwen3-Next's shape: a dense chunk can
    make the compiler copy whole expert stacks; every chunk here is grouped),
    and arguments and temporaries together fit the 16 GB chip."""
    engine = _window_cell_engine()
    report, text = _window_cell_programs(engine, one_chip, (6144, 16384))
    assert not _made_in_loops(text, ("bf16[16,4096,4096]",))
    _no_larger_than_at_pr_44("command-a-plus", report)
    print("temporaries, bytes:", report)


# Kimi Delta Attention beside latent attention, a dense leading layer, the
# experts chosen by group and held by share (ling-3.0-flash-vl): the latent
# decode read in the period's ONE latent layer, the channel-decay state's pass
# as XLA in place, the two-level chunk form, the expert stacks read where they lie
# ---------------------------------------------------------------------------


def _kda_cell_engine(held: int | None = None, slots: int | None = None):
    """The engine of the cell's configuration at its published WIDTHS, its 7
    layers and its runtime; ``held`` experts of the 512 and ``slots`` where
    the test has no use for all 64 and all 128."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", "ling-3.0-flash-vl.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert config.layer_types == ("kda",) * 6 + ("attention",)
    assert config.stack_plan == (1, ("kda",) * 5 + ("attention",))
    if held is not None:
        config = replace(config, n_routed_experts=held)
    if slots is not None:
        runtime = replace(runtime, max_batch_size=slots, num_kv_pages=slots * 64 + 1)
    engine = InferenceEngine(
        config, replace(runtime, compilation_cache=False, attention_impl="pallas"))
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas", "pallas")
    return engine


def _kda_programs(engine, one_chip, rows_of_waves):
    """The decode dispatch and the ragged programs (a wave of each of
    ``rows_of_waves`` rows of one chunk; a pair ``(rows, chunk)`` is a wave of
    a bucket NARROWER than ``prefill_chunk``, whose chunk is its bucket),
    compiled for the described v5e -> {name: compiled}."""
    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.mamba import make_recurrent_state

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    zero = engine._moe_zero
    out = {"decode": engine._decode_jit(window, steps, sampled).lower(
        *abstract(args), state=abstract(engine._state), moe=abstract(zero)).compile()}
    for wave_of in rows_of_waves:
        if isinstance(wave_of, tuple):
            (rows, chunk), name = wave_of, "ragged {}x{}".format(*wave_of)
            bucket = chunk
        else:
            rows, chunk, name = wave_of, rt.prefill_chunk, f"ragged x{wave_of}"
            bucket = 2 * chunk
        scratch = [jax.ShapeDtypeStruct(
            (cfg.n_kv_layers, rows, cfg.cache_heads, bucket, width), engine._k.dtype)
            for width in cfg.cache_dims]
        wave = [*scratch, jax.ShapeDtypeStruct((rows, chunk), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32)]
        out[name] = engine._ragged_jit(window, steps, sampled, chunk, rows).lower(
            *abstract((*args, *wave)), state=abstract(engine._state),
            wstate=abstract(jax.eval_shape(lambda: make_recurrent_state(cfg, rows))),
            true_lens=abstract(jax.ShapeDtypeStruct((rows,), jnp.int32)),
            moe=abstract(zero), wmoe=abstract(zero)).compile()
    return out


def _kda_checks(engine, name, compiled):
    """The latent decode read of the one latent layer, under
    ``decode_loop/.../mla/attention``; no window gathered in the decode
    loop; the state's pass the delta step kernel, once a delta-rule layer
    (the leading dense layer's and the scan's five), under ``gdn/state``, and
    the decay's under ``gdn/decay``; the groups under ``moe/router/groups``;
    NO copy of an expert stack; the stacked state and the pool go out where
    they came in."""
    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line and "pallas_call" in line]
    reads = [k for k in kernels if "latent_decode" in k]
    # the experts through the step kernel (PR 53): one in the head's unrolled layers, the
    # scan's period of four; no decode step's product is the compiler's ragged-dot any more
    experts = _moe_step_kernels(hlo, engine, len(kernels) - 7)
    assert engine._moe_step_impl == "pallas" and len(experts) >= 4
    assert len(reads) == 1 and len(_delta_step_kernels(hlo)) == 6, kernels
    assert "/mla/" in reads[0] and "decode_loop/" in reads[0]
    for scope in ("/gdn/state/", "/gdn/decay/", "/moe/router/groups", "/mla/kv_latent"):
        assert scope in hlo, scope
    assert _under_moe_experts(hlo)
    cfg, rt = engine.config, engine.runtime
    assert not _expert_stack_copies(hlo, cfg), name
    memory = compiled.memory_analysis()
    state_bytes = cfg.recurrent_state_bytes(rt.max_batch_size)
    pool_bytes = engine._k.nbytes + engine._v.nbytes
    assert memory.alias_size_in_bytes >= state_bytes + pool_bytes
    if name.startswith("ragged"):  # the chunk: grouped experts, the two-level delta rule
        assert "chunk_loop/" in hlo and "ragged-dot" in hlo and "chunk_loop/" in hlo
    else:
        assert "ragged-dot" not in hlo
    return memory


def test_kda_expert_cell_decode_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch of the new cell at its published widths and 7
    layers, 8 of the 64 held experts and 16 of the 128 slots (the products'
    shapes but not 4.5 GB of experts and 1.7 GB of state), compiled for the
    described v5e (the ragged programs: the full-size test below)."""
    engine = _kda_cell_engine(held=8, slots=16)
    for name, compiled in _kda_programs(engine, one_chip, ()).items():
        memory = _kda_checks(engine, name, compiled)
        print("temporaries, bytes:", name, memory.temp_size_in_bytes)


@pytest.mark.slow  # 5.6 GB of weights and four whole-program compiles on every core: the offline
# lane runs it, as it runs the other expert cells'; PERF.md section 6, PR 40 has its readings
def test_kda_expert_cell_programs_at_full_size_fit_the_chip(one_chip, no_persistent_cache):
    """The decode dispatch and the ragged programs (a wave of 1, 2 and 4 rows
    of 1,024) of the new cell at its FULL size: all 64 held experts of 6
    layers, 128 slots.  No expert stack is copied, the decode step's
    temporaries stay under two layers' state, and arguments and temporaries
    together leave the 16 GB chip 3 GB of room."""
    from calfkit_tpu.inference import moe

    engine = _kda_cell_engine()
    cfg, rt = engine.config, engine.runtime
    # a chunk's products are grouped; a decode step's take the step kernel (PR 53: its group
    # gate sends a step ~27 of the 64 held experts, which the kernel reads alone; through PR 52
    # they were grouped too, ``moe._DENSE_TO_THE_CROSSING``'s row of 0, PRs 40 and 45) and
    # what the row left to an engine WITHOUT the kernel is the dense form, the reference
    assert not moe.dense_form(rt.prefill_chunk, cfg) and moe.dense_form(rt.max_batch_size, cfg)
    assert engine._moe_step_impl == "pallas"
    report = {}
    for name, compiled in _kda_programs(engine, one_chip, (1, 2, 4)).items():
        memory = _kda_checks(engine, name, compiled)
        report[name] = (memory.temp_size_in_bytes, memory.argument_size_in_bytes)
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.0e9, (name, report)
    per_layer = cfg.recurrent_state_bytes(rt.max_batch_size) // cfg.n_recurrent_layers
    assert report["decode"][0] < 2 * per_layer + engine._k.nbytes + engine._v.nbytes, report
    _no_larger_than_at_pr_44("ling", {name: temp for name, (temp, _) in report.items()})
    print("temporaries and arguments, bytes:", report)


# ---------------------------------------------------------------------------
# a gated short convolution beside rotary GQA attention with normed heads, two
# dense layers, then 32 bias-routed experts (lfm2-8b-a1b): the paged decode
# read at granite's 32 / 8 heads of 64, the conv tail read and rewritten in
# place under ``shortconv/conv`` (no matrix state, no kernel of its own), the
# expert stacks read where they lie: by the DENSE form in the decode steps
# (since PR 45: 128 rows, every expert hit every step) and by the grouped form
# in the chunks, which are a bucket of 1,024 a row here and so always wide
# ---------------------------------------------------------------------------


def _lfm2_cell_engine(held: int | None = None, slots: int | None = None):
    """The engine of the cell's configuration at its published WIDTHS, its 12
    layers and its runtime; ``held`` experts of the 32 and ``slots`` where the
    test has no use for 7 GB of experts and all 128 (the products take the
    form the cell's shape takes: it has no row in ``moe``'s table, so the
    default every shape has)."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", "lfm2-8b-a1b.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert config.layer_types == ("conv", "conv", "attention", "conv") * 3
    assert config.stack_plan == (4, ("conv", "conv", "attention", "conv"))
    assert (config.n_routed_experts, config.d_model, config.moe_d_ff) not in (
        moe._DENSE_TO_THE_CROSSING)
    if held is not None:
        config = replace(config, n_routed_experts=held)
    if slots is not None:
        runtime = replace(runtime, max_batch_size=slots, num_kv_pages=slots * 33 + 1)
    engine = InferenceEngine(
        config, replace(runtime, compilation_cache=False, attention_impl="pallas"))
    # the state's pass is XLA whatever is asked: there is no matrix state to pass over
    assert (engine._attn_impl, engine._ssm_impl) == ("pallas", "xla")
    return engine, described


def _lfm2_checks(engine, name, compiled):
    """ONE paged decode read a dispatch loop's attention layer kind (the head
    unrolls one, the scan's period holds one), under ``decode_loop/.../attention``,
    and no other kernel; the mixer under ``shortconv`` with its three scopes,
    in a ragged program under ``chunk_loop`` too; the decode steps' expert
    products under ``decode_loop/.../moe/experts`` (the dense form: the decode
    program holds NO ``ragged-dot``), a chunk's the compiler's ``ragged-dot``
    kernel where it is wider than ``moe.dense_form``'s limit and none where it
    is not; NO copy of an expert stack, and no array of a layer's experts made
    in a loop; the tails and the pool go out where they came in."""
    import re

    from calfkit_tpu.inference import moe

    hlo = compiled.as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line and "pallas_call" in line]
    assert len(kernels) == 2 and all("paged_decode" in k and "decode_loop/" in k
                                     for k in kernels), kernels
    for scope in ("/shortconv/in_proj", "/shortconv/conv", "/shortconv/out_proj", "/qk_norm",
                  "/mlp/moe/router", "/mlp/moe/experts", "/mlp/moe/combine"):
        assert scope in hlo, scope
    assert re.search(r'decode_loop/[^"]*/mlp/moe/experts', hlo), name
    cfg, rt = engine.config, engine.runtime
    assert moe.dense_form(rt.max_batch_size, cfg)
    E, D, F = cfg.n_routed_experts, cfg.d_model, cfg.moe_d_ff
    assert not _expert_stack_copies(hlo, cfg), name
    assert not _made_in_loops(hlo, (f"bf16[{E},{D},{F}]", f"bf16[{E},{F},{D}]")), name
    memory = compiled.memory_analysis()
    tails = cfg.recurrent_state_bytes(rt.max_batch_size)
    assert memory.alias_size_in_bytes >= tails + engine._k.nbytes + engine._v.nbytes
    if name.startswith("ragged"):
        assert "chunk_loop/" in hlo and "/shortconv/" in hlo.split("chunk_loop/", 1)[1]
        rows, _, chunk = name.split()[1].partition("x")  # "x4": 4 rows of prefill_chunk
        rows, chunk = (int(rows), int(chunk)) if rows else (int(chunk), rt.prefill_chunk)
        assert ("ragged-dot" in hlo) == (not moe.dense_form(rows * chunk, cfg)), name
    else:
        assert "ragged-dot" not in hlo, name
    return memory


def test_shortconv_expert_cell_decode_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """The decode dispatch of the new cell at its published widths and 12
    layers, 8 of the 32 experts and 16 of the 128 slots (the products' shapes
    and form but not 7 GB of experts), and two ragged programs beside it: a
    wave of one row of 1,024 (the cell's narrowest: grouped) and one of two rows
    of a bucket of 256 (no key of the cell: a ``prefill_chunk`` of 512 or less
    would make it; 512 tokens, dense), compiled for the described v5e (the
    temporaries at the cell's 128 rows: the full-size test below)."""
    engine, _ = _lfm2_cell_engine(held=8, slots=16)
    for name, compiled in _kda_programs(engine, one_chip, (1, (2, 256))).items():
        memory = _lfm2_checks(engine, name, compiled)
        print("temporaries, bytes:", name, memory.temp_size_in_bytes)


@pytest.mark.slow  # 7.9 GB of weights and three whole-program compiles on every core (2 min): the
# offline lane runs it, as it runs the other expert cells'; PERF.md section 6, PRs 44-45 have its readings
def test_shortconv_expert_cell_programs_at_full_size_fit_the_chip(one_chip, no_persistent_cache):
    """The decode dispatch and the ragged programs (a wave of 1 and 4 rows of
    1,024) of the new cell at its FULL size: all 32 experts of 10 layers, 128
    slots.  The decode steps' products are DENSE (PR 45) and no expert stack is
    copied: spelled ``td,edf->etf`` the same program copied both stacks whole
    (6.38 GB of temporaries on 9.58 GB of arguments: no room), spelled weights
    first it holds LESS than the grouped program PR 44 ran (1.69 GB); the
    temporaries stay under what ``hbm`` states, and arguments and temporaries
    together leave the 16 GB chip 3 GB of room."""
    from calfkit_tpu.inference import moe

    engine, described = _lfm2_cell_engine()
    cfg, rt = engine.config, engine.runtime
    assert not moe.dense_form(rt.prefill_chunk, cfg) and moe.dense_form(rt.max_batch_size, cfg)
    stated = described["hbm"]["temporaries_bytes"]
    report = {}
    for name, compiled in _kda_programs(engine, one_chip, (1, 4)).items():
        memory = _lfm2_checks(engine, name, compiled)
        report[name] = (memory.temp_size_in_bytes, memory.argument_size_in_bytes)
        assert memory.temp_size_in_bytes < stated["decode" if name == "decode" else "ragged"], (
            name, report)
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 13.0e9, (name, report)
    _no_larger_than_at_pr_44("lfm2", {name: temp for name, (temp, _) in report.items()})
    print("temporaries and arguments, bytes:", report)


def _eva_cell_engine(layers: int = 2, slots: int = 4):
    """An engine of the EvaByte cell's widths, runtime and kernels on the CPU,
    with ``layers`` of its 8 layers (ONE scan over them: the body is traced
    once whatever their number) and ``slots`` of its 16 slots (the kernels'
    grid is the rows: the body is the same), so that the test holds 2 GB and
    not 12."""
    import json
    from dataclasses import replace

    from benchmarks import manifest
    from calfkit_tpu.inference.engine import InferenceEngine

    here = os.path.dirname(manifest.__file__)
    with open(os.path.join(here, "configs", "evabyte.json")) as f:
        described = json.load(f)
    arch = manifest.load_architecture(described["architecture"], here)
    config, runtime = arch.model(described, False)
    assert config.layer_period == ("eva",) and config.n_layers == 8
    assert (config.d_model, config.n_heads, config.head_dim, config.d_ff) == (4096, 32, 128, 11008)
    config = replace(config, n_layers=layers, layer_types=("eva",) * layers)
    engine = InferenceEngine(config, replace(
        runtime, max_batch_size=slots, compilation_cache=False, attention_impl="pallas"))
    assert engine._attn_impl == engine._chunk_attn_impl == "pallas"
    assert (engine._ring_pages, engine._pages_per_seq) == (34, 28)
    return engine


def test_eva_cell_dispatch_programs_compile_for_v5e(one_chip, no_persistent_cache):
    """EvaByte's cell at its published widths (2 of its 8 layers, 4 of its 16
    slots): the decode dispatch holds the paged decode kernel TWICE a layer,
    its window form over the ring under ``eva/attention/window`` and its
    global form over the summary pages under ``eva/attention/summary``,
    gathers no window, and gives both pools out where they came in; no pool
    side is copied for the pooling's reads (a loop of window reads) or its
    writes; the ragged program holds the chunk kernel under
    ``chunk_loop/.../eva/attention`` over the summaries with the chunk's keys
    behind them, and no scores of a chunk cross HBM."""
    import re

    import jax
    import jax.numpy as jnp

    from calfkit_tpu.inference.eva import make_scratch

    engine = _eva_cell_engine()
    rt, cfg = engine.runtime, engine.config

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)

    args, window, steps, sampled = engine._decode_args()
    assert window == 28672 == rt.max_seq_len and steps == 8
    decode = engine._decode_jit(window, steps, sampled).lower(*abstract(args)).compile()
    hlo = decode.as_text()
    kernels = [line for line in hlo.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2 and all("paged_decode_attention" in k for k in kernels), kernels
    assert sum("/eva/attention/window/" in k for k in kernels) == 1
    assert sum("/eva/attention/summary/" in k for k in kernels) == 1
    assert "gather_window" not in hlo
    pools = sum(a.nbytes for a in jax.tree.leaves((engine._k, engine._v)))
    side = max(a.nbytes for a in jax.tree.leaves((engine._k, engine._v)))
    memory = decode.memory_analysis()
    assert memory.alias_size_in_bytes >= pools
    # nothing the size of a pool side is made: the tail's and the pooling's reads
    # of the ring and the summaries' write are windows of the stored pool
    assert memory.temp_size_in_bytes < side / 2, (memory.temp_size_in_bytes, side)
    report = {"decode": memory.temp_size_in_bytes}
    chunk = rt.prefill_chunk
    scratch = make_scratch(cfg, 1, rt.max_seq_len, jnp.bfloat16)
    wave = [*scratch, jax.ShapeDtypeStruct((1, chunk), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)]
    ragged = engine._ragged_jit(window, steps, sampled, chunk, 1).lower(
        *abstract((*args, *wave))).compile()
    text = ragged.as_text()
    assert "decode_loop/" in text and "chunk_loop/" in text
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    chunk_calls = [c for c in calls if "chunk_attention" in c]
    assert len(chunk_calls) == 1 and len(calls) == 3, (len(chunk_calls), len(calls))
    assert re.search(r'chunk_loop/[^"]*eva/attention/', chunk_calls[0])
    assert re.search(r'chunk_loop/[^"]*eva/pool/', text)
    # no scores of 32 heads over a key block in HBM (the loop made [1, 32, 1, 2048, 512] float32)
    assert not re.search(rf"f32\[1,{cfg.n_heads},\d+,{chunk},\d+\]", text)
    memory = ragged.memory_analysis()
    report["ragged"] = memory.temp_size_in_bytes
    assert memory.temp_size_in_bytes < 2.5e9
    print("temporaries, bytes (2 layers, 4 slots):", report)
