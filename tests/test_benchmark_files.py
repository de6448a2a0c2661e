"""The benchmark's files, held in tier-1 (PERF.md section 7 has waited for
this since PR 26): every cell of BENCHMARK.json resolves, every
architecture file loads behind the seven functions, and the counts a
roofline share is computed from are what a hand reckons from the sizes."""

from __future__ import annotations

import json
import os

import pytest

from benchmarks import manifest as M

MANIFEST = M.load_manifest(M.ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ARCHITECTURES = sorted(
    f[:-3] for f in os.listdir(os.path.join(M.HERE, "architectures")) if f.endswith(".py"))


def config_file(name: str) -> dict:
    with open(os.path.join(M.HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_resolves(cell_name):
    cell = M.resolve_cell(MANIFEST, cell_name, M.ROOT)
    reported = {m.name for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer and all(m.moves in reported and callable(m.read) for m in cell.per_layer)
    assert cell.params and cell.traffic["loop"] in ("open", "closed")
    described, runtime = cell.arch.model(cell.config, False)
    assert described.vocab_size == cell.config["vocab_size"]
    assert runtime.max_batch_size <= cell.config["worker"]["max_workers"]  # lanes never cap the batch


@pytest.mark.parametrize("name", ARCHITECTURES)
def test_every_architecture_file_loads_with_the_seven_functions(name):
    module = M.load_architecture(name)
    assert all(callable(getattr(module, f)) for f in M.ARCHITECTURE)
    assert len(M.ARCHITECTURE) == 7


def test_every_configuration_of_the_manifest_names_an_architecture_that_is_there():
    for entry in MANIFEST["configs"]:
        with open(os.path.join(M.ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config.get("architecture", M.DEFAULT_ARCHITECTURE) in ARCHITECTURES
        assert config["reduced"] == entry["reduced"] and config["source"] == entry["source"]


def test_mistral_s_weight_bytes_are_what_they_were():
    arch = M.load_architecture("dense-gqa")
    assert arch.weight_bytes(config_file("mistral-7b-v0.3-int8")) == 7_113_539_584


def test_granite_s_counts_are_what_a_hand_reckons():
    arch = M.load_architecture("granite-hybrid")
    config = config_file("granite-4.0-h-micro")
    assert config["reduced"] == [] and config["num_hidden_layers"] == 40
    assert config["vocab_size"] == 100352 and config["layer_types"].count("mamba") == 36
    weights = arch.weight_bytes(config)
    assert f"{weights / 1e9:.3g}" == "6.38"
    assert arch.state_bytes_per_token(config) == 4 * 2 * 8 * 64 * 2 == 8192
    # one decode step over 64 rows of 400 tokens, by hand: the weights, 64
    # rows of SSM state (36 layers x 64 heads x 64 x 128 float32) and conv
    # state (36 x 4352 x 3 bfloat16) read AND written, the KV of 4 layers
    ssm = 36 * 64 * 64 * 128 * 4
    conv = 36 * 4352 * 3 * 2
    by_hand = 6.38e9 + 2 * 64 * (ssm + conv) + 64 * 400 * 8192
    step = arch.decode_step(config, 64, 400, 1)
    assert abs(step["bytes"] - by_hand) / by_hand < 0.01
    assert 16.0e9 < step["bytes"] < 16.7e9  # about 16.3 GB, 60% of it recurrent state
    assert arch.recurrent_state_bytes(config, 64) == 64 * (ssm + conv) \
        == config["hbm"]["recurrent_state_bytes"]
    assert arch.recurrent_state_step(config, 64)["bytes"] == 2 * 64 * (ssm + conv)
    assert config["hbm"]["weights_bytes"] == weights == 2 * config["parameters"]
    # a prefill chunk's FLOPs: 2 x matrices x tokens dominates, attention in 4 layers only
    chunk = arch.prefill_chunk(config, 4, 512, 0, 1)
    assert 2 * 3.19e9 * 2048 < chunk["flops"] < 1.1 * 2 * 3.19e9 * 2048


def test_the_program_s_description_of_granite_is_the_file_s():
    from calfkit_tpu.inference.config import preset

    arch = M.load_architecture("granite-hybrid")
    described, runtime = arch.model(config_file("granite-4.0-h-micro"), False)
    want = preset("granite-4.0-h-micro")
    for key in ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
                "layer_types", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size", "position_embedding",
                "attention_multiplier", "embedding_multiplier", "residual_multiplier",
                "logits_scaling", "tie_embeddings", "norm_eps", "state_dtype", "dtype"):
        assert getattr(described, key) == getattr(want, key), key
    assert described.param_count == 3_191_396_096
    assert (runtime.max_batch_size, runtime.kv_layout, runtime.chunked_prefill) == (64, "paged", True)
    assert runtime.pool_pages() == 64 * 20 + 1 and not runtime.prefix_cache


def test_the_new_readers_read_nothing_where_there_is_nothing_to_read():
    """On a program or an architecture without the Mamba scopes and
    counts (the parent commit, the dense cell) they return None and do
    not raise."""
    from types import SimpleNamespace

    ssm_pct = M.load_reader("ssm_device_pct")
    roofline = M.load_reader("ssm_state_roofline")
    dense = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {"decode_loop/mlp": 1.5, "(unscoped)": 0.5}},
        trace_counters={"decode_tokens": 100, "decode_dispatches": 5, "short_dispatches": 0},
        arch=M.load_architecture("dense-gqa"), config={}, chips=1,
        runtime=SimpleNamespace(decode_steps_per_dispatch=8), peaks={})
    assert ssm_pct(dense) is None and roofline(dense) is None
    assert ssm_pct(SimpleNamespace(trace_reduced=None)) is None
    hybrid = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {
            "decode_loop/mamba/ssm": 0.6, "decode_loop/mamba/conv": 0.1,
            "chunk_loop/mamba/ssm": 0.2, "decode_loop/mlp": 0.9}},
        trace_counters={"decode_tokens": 64 * 40, "decode_dispatches": 5, "short_dispatches": 0},
        arch=M.load_architecture("granite-hybrid"), config=config_file("granite-4.0-h-micro"),
        chips=1, runtime=SimpleNamespace(decode_steps_per_dispatch=8),
        peaks=M.load_peaks("TPU v5 lite"))
    assert ssm_pct(hybrid) == pytest.approx(45.0)
    # 40 steps x 2 x 64 rows x 76.4 MB at 819 GB/s = 0.478 s over 0.7 s measured
    assert roofline(hybrid) == pytest.approx(100 * 40 * 2 * 4892000256 / 819e9 / 0.7)
