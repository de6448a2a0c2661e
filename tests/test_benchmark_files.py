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


# ------------------------------------------- kimi-vl-a3b-instruct (PR 31)
KIMI, KIMI_CELL = "kimi-vl-a3b-instruct", "kimi-vl-a3b-instruct.history-closed"


def test_kimi_s_counts_are_what_a_hand_reckons():
    arch = M.load_architecture("deepseek-mla-moe")
    config = config_file(KIMI)
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["num_hidden_layers"], config["published"]["num_hidden_layers"]) == (7, 27)
    assert (config["n_routed_experts"], config["num_experts_per_tok"],
            config["n_shared_experts"], config["vocab_size"]) == (64, 6, 2, 163840)
    weights = arch.weight_bytes(config)
    assert f"{weights / 1e9:.3g}" == "8.53"
    assert config["hbm"]["weights_bytes"] == weights == 2 * config["parameters"]
    assert arch.state_bytes_per_token(config) == 7 * 576 * 2 == 8064 \
        == config["hbm"]["kv_bytes_per_token"]
    # one decode step over 64 rows of 1,300 tokens, by hand (bfloat16):
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048  # 13.76M a layer
    expert = 3 * 2048 * 1408  # 8.65M
    outside = 7 * attention + 3 * 2048 * 11264 + 6 * (2048 * 64 + 2 * expert) + 2048 * 163840
    hit = 64 * (1 - (1 - 6 / 64) ** 64)  # 63.9 of 64 under even routing
    by_hand = 2 * (outside + 6 * hit * expert) + 64 * 1300 * 8064
    assert 8.4e9 < by_hand < 8.6e9  # the 8.5 GB of the issue
    step = arch.decode_step(config, 64, 1300, 1)
    assert abs(step["bytes"] - by_hand) / by_hand < 0.01
    assert 0.76 < 2 * 6 * hit * expert / step["bytes"] < 0.80  # the experts: 78% of a step's bytes
    assert arch.experts_hit(config, 64) == pytest.approx(63.9, abs=0.05)
    assert arch.experts_hit(config, 8) == pytest.approx(35, abs=1)  # not 64
    few = arch.decode_step(config, 8, 1300, 1)
    assert few["bytes"] < 0.6 * step["bytes"]
    # a chunk multiplies the tokens ROUTED, never every expert: 2 x the
    # 6 + 2 experts and the rest a token, far under 2 x every parameter
    chunk = arch.prefill_chunk(config, 4, 512, 0, 1)
    active = outside + 6 * 6 * expert
    assert 2 * active * 2048 < chunk["flops"] < 1.15 * 2 * active * 2048
    assert chunk["flops"] < 0.5 * 2 * (weights / 2) * 2048


def test_the_program_s_description_of_kimi_is_the_file_s():
    from dataclasses import replace

    from calfkit_tpu.inference.config import preset

    arch = M.load_architecture("deepseek-mla-moe")
    config = config_file(KIMI)
    described, runtime = arch.model(config, False)
    want = replace(preset(KIMI), n_layers=7, max_seq_len=4096)
    fields = {f: getattr(described, f) for f in want.__dataclass_fields__}
    assert type(want)(**{**fields, "name": want.name}) == want  # every field of the program's
    assert (described.agreement_margin, described.agreement_new_tokens, described.routing_tie) == (
        config["agreement"]["margin"], config["agreement"]["new_tokens"],
        config["agreement"]["routing_tie"])  # and, beside them, what forward_top2 reads
    assert described.param_count == config["parameters"] == 4_263_151_488
    assert preset(KIMI).param_count == config["published_parameters"]
    assert described.kv_norm_eps == 1e-6 != described.norm_eps
    assert (runtime.max_batch_size, runtime.kv_layout, runtime.chunked_prefill,
            runtime.prefix_cache, runtime.max_prefill_wave) == (64, "paged", True, True, 4)
    assert runtime.pool_pages() == 64 * 64 + 1 == config["hbm"]["pool_pages"] + 1
    assert config["hbm"]["pool_bytes"] == 4097 * 64 * 8064
    toy, toy_runtime = arch.model(config, True)
    assert toy.moe and toy.latent and toy_runtime.max_batch_size == 8


def test_kimi_s_reference_lists_every_choice_of_experts_within_the_tie():
    """``_routings``: a token whose k-th expert leads the next by more than
    the tie has ONE routing; one in doubt on each side of the line has two;
    one inside and two outside three; and a crowd is given up (its top k
    alone is kept)."""
    import numpy as np

    arch = M.load_architecture("deepseek-mla-moe")
    scores = np.asarray([
        [0.9, 0.8, 0.5, 0.4, 0.3, 0.2, 0.1, 0.0],      # clear: {0, 1}
        [0.9, 0.8, 0.799, 0.4, 0.3, 0.2, 0.1, 0.0],    # 1 or 2 beside 0
        [0.9, 0.8, 0.799, 0.798, 0.3, 0.2, 0.1, 0.0],  # 1, 2 or 3 beside 0
        [0.8] * 8,                                     # all in doubt: 28 ways
    ], np.float32)
    parent, chosen, first, crowded = arch._routings(scores, 2, 0.004)
    sets = [{tuple(np.flatnonzero(c)) for c, p in zip(chosen, parent) if p == n} for n in range(4)]
    assert sets[0] == {(0, 1)} and sets[1] == {(0, 1), (0, 2)}
    assert sets[2] == {(0, 1), (0, 2), (0, 3)}
    assert crowded.tolist() == [False, False, False, True] and len(sets[3]) == 1
    assert (chosen.sum(-1) == 2).all()
    assert [int(first[parent == n].sum()) for n in range(4)] == [1, 1, 1, 1]
    assert {tuple(np.flatnonzero(c)) for c in chosen[first & (parent == 1)]} == {(0, 1)}


def _greedy(params, toy, prompts, n, route=None):
    """``n`` tokens the PROGRAM's full forward serves after each of
    ``prompts``, greedily (float32, toy size), under another gate if
    ``route`` is one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from calfkit_tpu.inference import model as program
    from calfkit_tpu.inference import moe

    S = max(len(p) for p in prompts) + n
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    right = moe.route
    if route is not None:
        moe.route = lambda h, lp, c: route(right, h, lp, c)
    try:
        forward = jax.jit(lambda tokens: program.forward(
            params, toy, tokens, pos, program.make_empty_cache(toy, 1, S),
            jnp.full((1,), S, jnp.int32))[0])
        outs = []
        for prompt in prompts:
            seq = list(prompt)
            for _ in range(n):  # causal: the padding after a position moves nothing before it
                tokens = np.zeros((1, S), np.int32)
                tokens[0, :len(seq)] = seq
                seq.append(int(np.argmax(np.asarray(forward(jnp.asarray(tokens)))[0, len(seq) - 1])))
            outs.append(seq[len(prompt):])
        return outs
    finally:
        moe.route = right


def test_kimi_s_reference_follows_a_near_tie_and_catches_a_wrong_gate():
    """The rule of the architecture file at toy size, float32 on both sides.
    A program whose gate sees scores off by LESS than the tie (what a
    bfloat16 stream does to a float32 gate) serves tokens the reference
    accepts at every position it decides, some of them under another
    routing than its own; the margin rule alone (tie 0) fails the same
    tokens.  A program that leaves the bias out of the choice serves
    tokens that no admitted routing gives, and fails."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import agreement
    from calfkit_tpu.inference.config import RuntimeConfig
    from calfkit_tpu.inference.sharding import make_mesh

    arch = M.load_architecture("deepseek-mla-moe")
    config = config_file(KIMI)
    toy, _ = arch.model(config, True)
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=24, routing_tie=0.02,
                              agreement_margin=0.25)
    params = arch.params(toy, RuntimeConfig(), make_mesh(tp=1, dp=1, devices=jax.devices()[:1]), 5)
    bias = np.asarray(params["layers"]["moe"]["router_bias"])
    assert 0.01 < np.abs(bias).mean() < 0.05 and np.abs(bias).max() <= 0.05  # NOT zero
    # 8 experts' scores lie eight times further apart than 64 experts': a bias that is to
    # move a choice as often as it does at the real size is that much larger
    params["layers"]["moe"]["router_bias"] = params["layers"]["moe"]["router_bias"] * 8.0
    assert 0.9 < float(jnp.std(params["embed"])) < 1.1  # the token's own row at unit scale

    def jitter(right, h, lp, c):  # scores off by up to 0.009: under half the tie either way
        noise = jax.random.uniform(jax.random.key(0), lp["router_bias"].shape, jnp.float32,
                                   -0.009, 0.009)
        chosen, _ = right(h, {**lp, "router_bias": lp["router_bias"] + noise}, c)
        scores = jax.nn.sigmoid(h @ lp["router"])
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        return chosen, w / w.sum(-1, keepdims=True) * c.routed_scaling_factor

    def no_bias(right, h, lp, c):
        return right(h, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, c)

    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (9, 14, 20, 27)]

    def check(route, described):
        outs = _greedy(params, toy, prompts, 24, route)
        return agreement(arch.forward_top2, params, described, prompts, outs,
                         described.agreement_margin, 8)

    near = check(jitter, toy)
    assert near["ok"] and near["compared"] >= 24, near
    alone = check(jitter, dataclasses.replace(toy, routing_tie=0.0))
    assert alone["compared"] > alone["equal"], alone  # the margin rule alone fails the same tokens
    wrong = check(no_bias, toy)
    assert not wrong["ok"] and wrong["compared"] - wrong["equal"] >= 3, wrong
    right = check(None, toy)
    assert right["ok"] and right["compared"] >= near["compared"] - 8, right


def test_kimi_s_readers_read_what_their_files_say_and_nothing_elsewhere():
    """On a program or an architecture without the scopes and counters
    (the parent commit, the dense and hybrid cells) the four new readers
    return None and do not raise; on a made-up traced run they read what a
    hand reckons."""
    from types import SimpleNamespace

    read = {n: M.load_reader(n) for n in (
        "moe_device_pct", "moe_expert_roofline", "mla_cache_roofline", "moe_expert_load_ratio")}
    parent = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {"decode_loop/mlp": 1.5, "(unscoped)": 0.5}},
        trace_counters={"decode_tokens": 100, "decode_dispatches": 5, "short_dispatches": 0,
                        "decode_pages_live": 900.0},
        counters={"window": {"decode_tokens": 100}},
        arch=M.load_architecture("dense-gqa"), config={}, chips=1, model_config=SimpleNamespace(),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64), peaks={})
    assert all(reader(parent) is None for reader in read.values())
    untraced = SimpleNamespace(trace_reduced=None, trace_counters=None, counters={}, arch=None)
    assert all(reader(untraced) is None for reader in read.values())
    config = config_file(KIMI)
    steps, rows = 40, 64
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {
            "decode_loop/mlp/moe/experts": 0.7, "decode_loop/mlp/moe/router": 0.1,
            "chunk_loop/mlp/moe/experts": 0.2, "decode_loop/mla/attention": 0.2,
            "decode_loop/mla/gather_window": 0.25, "decode_loop/mla/absorb": 0.05,
            "decode_loop/mla/q_proj": 0.1, "chunk_loop/mla/attention": 0.3}},
        trace_counters={"decode_tokens": rows * steps, "decode_dispatches": 5,
                        "short_dispatches": 0, "moe_experts_hit": 63 * 6 * steps,
                        "decode_pages_live": rows * steps * 21.0},
        counters={"window": {"moe_expert_tokens_max": 300, "moe_expert_tokens_mean": 200.0}},
        arch=M.load_architecture("deepseek-mla-moe"), config=config, chips=1,
        model_config=SimpleNamespace(n_moe_layers=6, n_layers=7),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64),
        peaks=M.load_peaks("TPU v5 lite"))
    assert read["moe_device_pct"](run) == pytest.approx(50.0)
    assert read["moe_expert_load_ratio"](run) == pytest.approx(1.5)
    expert = 3 * 2048 * 1408 * 2  # bytes
    layer_step = (63 * expert + 2 * expert + 2048 * 64 * 2) / 819e9
    assert read["moe_expert_roofline"](run) == pytest.approx(100 * layer_step * 6 * steps / 0.8)
    tokens = rows * steps * 21 * 64
    assert read["mla_cache_roofline"](run) == pytest.approx(100 * 7 * tokens * 576 * 2 / 819e9 / 0.5)
    assert read["moe_expert_roofline"](run) < 100 and read["mla_cache_roofline"](run) < 100


def test_the_manifest_carries_kimi_s_cell_and_its_four_metrics():
    cell = M.resolve_cell(MANIFEST, KIMI_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 64}
    assert {m.name for m in cell.end_to_end} == {"tpot_p95_ms", "out_tok_s_per_chip", "setup_s"}
    own = {m["name"]: m for m in MANIFEST["per_layer"]  # those that came with the cell
           if m.get("workloads", [None])[0] == KIMI_CELL}
    assert set(own) == {"moe_device_pct", "moe_expert_roofline", "mla_cache_roofline",
                        "moe_expert_load_ratio"}
    assert {own[n]["moves"] for n in own} == {"tpot_p95_ms", "out_tok_s_per_chip"}
    assert own["moe_expert_load_ratio"]["source"] == "program_counter"
    registered = {m.name for m in cell.per_layer}
    assert set(own) <= registered and "ssm_state_roofline" not in registered
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    law = cell.traffic["prompt_tokens"]
    assert (law["median"], law["sigma"], law["min"], law["max"]) == (1024, 0.6, 256, 3072)
    assert cell.traffic["output_tokens"]["values"] == [128, 256, 512]


# --------------------------------- qwen3-next-80b-a3b-instruct (PR 33)
QWEN, QWEN_CELL = "qwen3-next-80b-a3b-instruct", "qwen3-next-80b-a3b-instruct.history-closed"


def test_qwen_s_counts_are_what_a_hand_reckons():
    """The cut's bytes as ISSUE 33 reckons them, and the counts of THIS
    chip's share: the held experts hit, the state read and written, the
    live K and V."""
    arch = M.load_architecture("qwen3-next-gdn-moe")
    config = config_file(QWEN)
    assert arch.weight_bytes(config) == 2 * config["parameters"] == 7_334_502_656
    assert arch.weight_bytes(config) == config["hbm"]["weights_bytes"]
    expert = 3 * 2048 * 512
    assert config["hbm"]["experts_bytes"] == 8 * 128 * expert * 2 == 6_442_450_944
    assert arch.state_bytes_per_token(config) == config["hbm"]["kv_bytes_per_token"] == 4096
    assert arch.recurrent_state_bytes(config, 1) == 12_877_824
    assert arch.recurrent_state_bytes(config, 64) == config["hbm"]["recurrent_state_bytes"]
    step = arch.recurrent_state_step(config, 56)
    assert step["bytes"] == 2 * 56 * 12_877_824 and step["flops"] == 8 * 56 * 6 * 32 * 128 * 128
    assert arch.experts_hit(config, 64) == pytest.approx(128 * (1 - (1 - 10 / 512) ** 64))
    assert 91 < arch.experts_hit(config, 64) < 93
    layer = arch.expert_layer_step(config, 64, 92.0)
    gate = 2048 * 512 + 2048
    assert layer["bytes"] == (92 * expert + expert + gate) * 2
    assert layer["flops"] == 2 * 64 * (2.5 * expert + expert + gate)  # 10 x 128 / 512 lie here
    whole = arch.decode_step(config, 64, 1400)
    assert whole["bytes"] < arch.weight_bytes(config) + 2 * 64 * 12_877_824 + 4096 * 64 * 1400
    assert whole["bytes"] > 0.6 * arch.weight_bytes(config)  # 92 of 128 experts, no embedding
    chunk = arch.prefill_chunk(config, 4, 1024, 0)
    assert chunk["flops"] > 2 * 4096 * 8 * 3.5 * expert and chunk["bytes"] > 0


def test_the_program_s_description_of_qwen_is_the_file_s():
    arch = M.load_architecture("qwen3-next-gdn-moe")
    config = config_file(QWEN)
    described, runtime = arch.model(config, False)
    assert described.param_count == config["parameters"] == 3_667_251_328
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    assert "4 chips share a layer" in config["deployment"]
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (described.n_routed_experts, described.experts_scored, described.expert_first,
            described.n_experts_per_tok) == (128, 512, 0, 10)
    assert described.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    assert (described.head_dim, described.rotary_dim, described.n_heads, described.n_kv_heads) == (
        256, 64, 16, 2)
    assert described.state_dtype == "float32" and described.dtype == "bfloat16"
    assert described.recurrent_state_bytes(64) == config["hbm"]["recurrent_state_bytes"]
    assert (runtime.max_batch_size, runtime.max_seq_len, runtime.prefill_chunk,
            runtime.max_prefill_wave, runtime.prefix_cache) == (64, 4096, 1024, 4, False)
    assert runtime.pool_pages() * 64 * 4096 == 4097 * config["hbm"]["page_bytes"]
    from calfkit_tpu.inference.config import preset

    published = preset("qwen3-next-80b-a3b-instruct")
    assert published.param_count == config["published_parameters"]
    toy, _ = arch.model(config, True)
    assert toy.expert_share and toy.layer_types == described.layer_types


def test_qwen_s_reference_opens_no_branch_for_a_tie_among_absent_experts():
    """``routing_tie.routings`` with the share (the rule's one helper file,
    which ``qwen3-next-gdn-moe.py`` imports): a doubt between two experts of
    which one is held here has two routings; the same doubt between two
    experts that are both held elsewhere has one."""
    import numpy as np

    from benchmarks import routing_tie

    logits = np.asarray([
        [0.9, 0.8, 0.799, 0.4, 0.3, 0.2, 0.1, 0.0],    # 1 or 2 beside 0: both held (0-3)
        [0.1, 0.0, 0.2, 0.3, 0.9, 0.8, 0.799, 0.4],    # 5 or 6 beside 4: all absent
        [0.8, 0.0, 0.2, 0.3, 0.9, 0.1, 0.799, 0.4],    # 0 (held) or 6 (absent) beside 4
    ], np.float32)
    parent, chosen, first, crowded = routing_tie.routings(logits, 2, 0.004, (0, 4))
    sets = [{tuple(np.flatnonzero(c)) for c, p in zip(chosen, parent) if p == n} for n in range(3)]
    assert sets == [{(0, 1), (0, 2)}, {(4, 5)}, {(0, 4), (4, 6)}]
    assert not crowded.any() and [int(first[parent == n].sum()) for n in range(3)] == [1, 1, 1]


@pytest.mark.parametrize("fault", ["jitter_inside_the_tie", "renormalised_over_the_held",
                                   "shared_gate_left_out", "none"])
def test_qwen_s_reference_follows_a_near_tie_and_catches_a_wrong_layer(fault):
    """The rule of the architecture file at toy size, float32 on both sides,
    through DeltaNet and attention layers alike.  A program whose gate sees
    logits off by LESS than the tie serves tokens the reference accepts at
    every position it decides; a program that renormalises the weights over
    the held experts, or leaves the shared expert's gate out, serves tokens
    that no admitted routing gives, and fails."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import agreement
    from calfkit_tpu.inference import model as program
    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.config import RuntimeConfig
    from calfkit_tpu.inference.mamba import make_recurrent_state
    from calfkit_tpu.inference.sharding import make_mesh

    arch = M.load_architecture("qwen3-next-gdn-moe")
    toy, _ = arch.model(config_file(QWEN), True)
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=24, routing_tie=0.05,
                              agreement_margin=0.25)
    params = arch.params(toy, RuntimeConfig(), make_mesh(tp=1, dp=1, devices=jax.devices()[:1]), 5)
    assert 0.9 < float(jnp.std(params["embed"])) < 1.1  # the token's own row at unit scale
    assert 0.02 < float(jnp.abs(params["final_norm"]).mean()) < 0.1  # w of (1 + w), NOT zero
    right, right_ffn = moe.route, program.moe_ffn

    def jitter(h, lp, c):  # logits off by up to 0.02: under half the tie either way
        noise = jax.random.uniform(jax.random.key(0), (c.experts_scored,), jnp.float32, -0.02, 0.02)
        logits = h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        _, chosen = jax.lax.top_k(logits + noise, c.n_experts_per_tok)
        w = jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, axis=-1)
        return chosen.astype(jnp.int32), w / w.sum(-1, keepdims=True)

    def over_held(h, lp, c):
        chosen, w = right(h, lp, c)
        held = (chosen >= c.expert_first) & (chosen < c.expert_first + c.n_routed_experts)
        kept = jnp.where(held, w, 0.0)
        return chosen, kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-20)

    if fault == "jitter_inside_the_tie":
        moe.route = jitter
    elif fault == "renormalised_over_the_held":
        moe.route = over_held
    elif fault == "shared_gate_left_out":
        program.moe_ffn = lambda h, lp, *a, **kw: right_ffn(
            h, {n: w for n, w in lp.items() if n != "shared_gate"}, *a, **kw)
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (9, 14, 20, 27)]
    S = 27 + 24
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    try:
        forward = jax.jit(lambda tokens: program.forward(
            params, toy, tokens, pos, program.make_empty_cache(toy, 1, S),
            jnp.full((1,), S, jnp.int32), state=make_recurrent_state(toy, 1))[0])
        outs = []
        for prompt in prompts:
            seq = list(prompt)
            for _ in range(24):  # causal: the padding after a position moves nothing before it
                tokens = np.zeros((1, S), np.int32)
                tokens[0, :len(seq)] = seq
                seq.append(int(np.argmax(np.asarray(forward(jnp.asarray(tokens)))[0, len(seq) - 1])))
            outs.append(seq[len(prompt):])
    finally:
        moe.route, program.moe_ffn = right, right_ffn
    result = agreement(arch.forward_top2, params, toy, prompts, outs, toy.agreement_margin, 8)
    if fault in ("none", "jitter_inside_the_tie"):
        assert result["ok"] and result["compared"] >= 24, result
    else:
        assert not result["ok"] and result["compared"] - result["equal"] >= 3, result


def test_the_moe_readers_take_qwen_s_architecture_as_they_stand():
    """The three ``moe_*`` readers read the new cell through
    ``expert_layer_step`` and the counters' names, unedited; the share's
    least time counts the HELD experts hit and stays under 100%."""
    from types import SimpleNamespace

    read = {n: M.load_reader(n) for n in (
        "moe_device_pct", "moe_expert_roofline", "moe_expert_load_ratio", "ssm_state_roofline",
        "mla_cache_roofline")}
    steps, rows = 40, 56
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {
            "decode_loop/mlp/moe/experts": 0.5, "decode_loop/mlp/moe/combine": 0.3,
            "chunk_loop/mlp/moe/experts": 0.2, "decode_loop/gdn/state": 0.3},
            "own_by_op": {"(unscoped) ragged-dot-none": 0.2}},
        trace_counters={"decode_tokens": rows * steps, "decode_dispatches": 5,
                        "short_dispatches": 0, "moe_experts_hit": 85 * 8 * steps,
                        "decode_pages_live": rows * steps * 21.0},
        counters={"window": {"moe_expert_tokens_max": 300, "moe_expert_tokens_mean": 100.0}},
        arch=M.load_architecture("qwen3-next-gdn-moe"), config=config_file(QWEN), chips=1,
        model_config=SimpleNamespace(n_moe_layers=8, n_layers=8),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64),
        peaks=M.load_peaks("TPU v5 lite"))
    assert read["moe_device_pct"](run) == pytest.approx(60.0)
    assert read["moe_expert_load_ratio"](run) == pytest.approx(3.0)
    expert = 3 * 2048 * 512 * 2
    layer_step = (85 * expert + expert + (2048 * 512 + 2048) * 2) / 819e9
    assert read["moe_expert_roofline"](run) == pytest.approx(100 * layer_step * 8 * steps / 0.8)
    assert read["moe_expert_roofline"](run) < 100
    # granite's and Kimi's own readers find nothing of theirs in this cell
    assert read["ssm_state_roofline"](run) is None and read["mla_cache_roofline"](run) is None


def test_the_manifest_carries_qwen_s_cell_and_its_two_metrics():
    cell = M.resolve_cell(MANIFEST, QWEN_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 64}
    assert cell.traffic_name == "history-closed"
    assert {m.name for m in cell.end_to_end} == {"tpot_p95_ms", "out_tok_s_per_chip", "setup_s"}
    # told by the FIRST cell of their list: later cells are appended to it (PR 40's)
    own = {m["name"]: m for m in MANIFEST["per_layer"]
           if m.get("workloads", [None])[0] == QWEN_CELL}
    assert set(own) == {"gdn_device_pct", "gdn_state_roofline"}
    assert {own[n]["moves"] for n in own} == {"tpot_p95_ms"}
    registered = {m.name for m in cell.per_layer}
    assert {"gdn_device_pct", "gdn_state_roofline", "moe_device_pct", "moe_expert_roofline",
            "moe_expert_load_ratio", "dispatch_roofline", "hbm_peak_gb", "batch_occupancy_pct",
            "empty_slot_queued_pct", "kv_pages_peak_pct"} <= registered
    assert not {"ssm_state_roofline", "ssm_device_pct", "mla_cache_roofline"} & registered
    names = [m["name"] for m in MANIFEST["per_layer"]]  # appended in PR 33; PR 36's four came after
    at = names.index("gdn_device_pct")
    assert names[at:at + 2] == ["gdn_device_pct", "gdn_state_roofline"] and at == 16
    assert MANIFEST["workloads"][3]["name"] == QWEN_CELL and len(MANIFEST["workloads"]) >= 4
    entry = MANIFEST["configs"][3]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0


# ------------------------------------------------ command-a-plus-05-2026 (PR 38)
COHERE, COHERE_CELL = "command-a-plus-05-2026", "command-a-plus-05-2026.longdoc-closed"


def test_command_a_plus_s_counts_are_what_a_hand_reckons():
    """The cut's bytes as ISSUE 38 reckons them, and the counts of THIS
    chip's share: a window layer's keys and values count min(context, W)
    tokens whatever the program reads."""
    arch = M.load_architecture("cohere2-moe-swa")
    config = config_file(COHERE)
    expert = 3 * 4096 * 4096
    attn = 2 * 4096 * 128 * 128 + 2 * 4096 * 8 * 128
    assert attn == 142_606_336 and expert == 50_331_648
    hbm = config["hbm"]
    assert arch.weight_bytes(config) == 2 * config["parameters"] == hbm["weights_bytes"]
    assert round(hbm["weights_bytes"] / 1e9, 2) == 9.47
    assert hbm["experts_bytes"] == 4 * 16 * expert * 2 == 6_442_450_944
    assert hbm["shared_experts_bytes"] == 4 * 4 * expert * 2 and hbm["attention_bytes"] == 4 * attn * 2
    assert hbm["embedding_bytes"] == 32768 * 4096 * 2
    assert arch.state_bytes_per_token(config) == hbm["kv_bytes_per_token_per_layer"] == 4096
    assert hbm["window_ring_pages_per_slot"] == 66 and hbm["window_pool_pages"] == 32 * 66
    assert hbm["window_pool_bytes"] == 3 * (32 * 66 + 1) * 64 * 4096
    assert hbm["global_pool_bytes"] == config["runtime"]["num_kv_pages"] * 64 * 4096
    assert hbm["one_pool_for_every_layer_bytes"] > 16e9 - hbm["weights_bytes"]  # what does not fit
    assert arch.experts_hit(config, 32) == pytest.approx(16 * (1 - (1 - 8 / 128) ** 32))
    assert 13.9 < arch.experts_hit(config, 32) < 14.1
    layer = arch.expert_layer_step(config, 32, 14.0)
    gate = 4096 * 128
    assert layer["bytes"] == (14 * expert + 4 * expert + gate) * 2
    assert layer["flops"] == 2 * 32 * (1.0 * expert + 4 * expert + gate)  # 8 x 16 / 128 lie here
    ring = arch.window_layers_step(config, 32, 3 * 32 * 4096.0)
    assert ring["bytes"] == 3 * 32 * 4096 * 4096 and ring["flops"] == 4 * 128 * 128 * 3 * 32 * 4096
    short, long = (arch.decode_step(config, 32, n) for n in (4096, 12000))
    # past the window only the ONE global layer's keys and values grow
    assert long["bytes"] - short["bytes"] == 32 * (12000 - 4096) * 4096
    assert long["flops"] - short["flops"] == 4 * 128 * 128 * 32 * (12000 - 4096)
    early, late = (arch.prefill_chunk(config, 1, 2048, at) for at in (4096, 12288))
    assert late["flops"] - early["flops"] == 4 * 128 * 128 * 2048 * (12288 - 4096)
    assert 3.0e9 < early["flops"] / 2048 < 5.5e9  # a prompt token: ~3.2 GFLOP of products + attention


def test_the_program_s_description_of_command_a_plus_is_the_file_s():
    arch = M.load_architecture("cohere2-moe-swa")
    config = config_file(COHERE)
    described, runtime = arch.model(config, False)
    assert described.param_count == config["parameters"] == 4_733_292_544
    assert config["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                   "vocab_size": 262144}
    assert "8 chips share a layer" in config["deployment"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert len(config["layer_types"]) == 32  # kept whole, as published
    assert (described.n_routed_experts, described.experts_scored, described.expert_first,
            described.n_experts_per_tok, described.n_shared_experts) == (16, 128, 0, 8, 4)
    assert described.layer_types == ("window", "window", "window", "attention")
    assert (described.head_dim, described.rotary_dim, described.n_heads, described.n_kv_heads,
            described.sliding_window, described.moe_d_ff) == (128, 128, 128, 8, 4096, 4096)
    assert (described.norm, described.parallel_block, described.position_embedding,
            described.shared_expert_combine, described.scoring_func, described.topk_method,
            described.tie_embeddings) == (
        "layer", True, "rope_window", "average", "sigmoid", "greedy", True)
    assert (runtime.max_batch_size, runtime.max_seq_len, runtime.prefill_chunk,
            runtime.max_prefill_wave, runtime.prefix_cache, runtime.window_buckets) == (
        32, 18432, 2048, 1, False, (18432,))
    assert described.window_ring_pages(runtime.page_size, runtime.decode_steps_per_dispatch) == 66
    from calfkit_tpu.inference.config import preset

    published = preset("command-a-plus-05-2026")
    assert published.param_count == config["published_parameters"]
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "moe_d_ff", "sliding_window",
                  "n_experts_per_tok", "n_shared_experts", "experts_scored", "rope_theta",
                  "norm_eps"):
        assert getattr(described, field) == getattr(published, field), field
    toy, toy_runtime = arch.model(config, True)
    assert toy.expert_share and toy.layer_types == described.layer_types
    assert toy.sliding_window * config["rehearsal"]["scale"] == described.sliding_window
    with pytest.raises(ValueError, match="use_parallel_block"):
        arch.model({**config, "use_parallel_block": False}, False)


def test_the_catalog_s_numbers_are_the_file_s():
    """Every number of the published config under its own key, but the three
    cuts (the driver holds the file to the catalog the same way)."""
    config = config_file(COHERE)
    published = {
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
        "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000,
        "num_attention_heads": 128, "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_shared_experts": 4, "prefix_dense_intermediate_size": 16384, "rope_theta": 50000,
        "rotary_pct": 1, "sliding_window": 4096, "first_k_dense_replace": 0,
    }
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        4, 16, 32768)


def test_the_window_readers_read_what_their_files_say_and_nothing_elsewhere():
    from types import SimpleNamespace

    read = {n: M.load_reader(n) for n in (
        "swa_device_pct", "swa_cache_roofline", "kv_pages_given_back_pct", "moe_device_pct",
        "moe_expert_roofline")}
    arch, config = M.load_architecture("cohere2-moe-swa"), config_file(COHERE)
    steps, rows = 40, 10
    window_tokens = 3 * rows * 4096 * steps
    counters = {"decode_tokens": rows * steps, "decode_dispatches": 5, "short_dispatches": 0,
                "moe_experts_hit": 9 * 4 * steps, "decode_window_tokens_read": window_tokens,
                "decode_global_tokens_read": rows * 9000 * steps}
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": {
            "decode_loop/attention/window": 0.1, "chunk_loop/attention/window": 0.5,
            "decode_loop/attention/global": 0.2, "decode_loop/mlp/moe/experts": 0.4,
            "chunk_loop/window/attention": 9.0}, "own_by_op": {}},
        trace_counters=counters, counters={"window": counters}, arch=arch, config=config, chips=1,
        model_config=arch.model(config, False)[0],
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64),
        peaks=M.load_peaks("TPU v5 lite"))
    assert read["swa_device_pct"](run) == pytest.approx(30.0)
    least = window_tokens * 4096 / 819e9  # bytes bound: 16 query heads a KV head at one query
    assert read["swa_cache_roofline"](run) == pytest.approx(100 * least / 0.1)
    assert 0 < read["swa_cache_roofline"](run) < 100
    # 4 layers x 9,000 tokens against 9,000 + 3 x 4,096
    assert read["kv_pages_given_back_pct"](run) == pytest.approx(
        100 * (4 * 9000 - 9000 - 3 * 4096) / (4 * 9000))
    assert read["moe_device_pct"](run) == pytest.approx(20.0)
    assert 0 < read["moe_expert_roofline"](run) < 100
    # a program without the scope or the counters (the parent, every other cell)
    older = SimpleNamespace(**{**vars(run), "trace_counters": {"decode_tokens": 400},
                               "counters": {"window": {"decode_tokens": 400}},
                               "trace_reduced": {"busy_s": 2.0, "by_scope": {
                                   "decode_loop/attention": 0.4}, "own_by_op": {}}})
    assert all(read[n](older) is None for n in (
        "swa_device_pct", "swa_cache_roofline", "kv_pages_given_back_pct"))
    untraced = SimpleNamespace(**{**vars(run), "trace_reduced": None, "trace_counters": None})
    assert read["swa_device_pct"](untraced) is None and read["swa_cache_roofline"](untraced) is None


def test_the_chunk_attention_reader_reads_its_share_and_nothing_without_its_sources():
    """PR 39's one metric: its file, its reader and its ``workloads`` list load; on a
    made-up run it reads the needed pairs' least time over the seconds under the two
    chunk scopes; nothing without the counters or the scopes (the parent's side, every
    other cell); under 100 where the measured time is above the least."""
    from types import SimpleNamespace

    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "chunk_attn_roofline")
    assert entry == {"name": "chunk_attn_roofline", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels", "moves": "tpot_p95_ms",
                     # (the second window stack's cell joined the list in PR 47)
                     "workloads": [COHERE_CELL, "mellum2-12b-a2.5b-instruct.mixed-lengths-closed"]}
    cell = M.resolve_cell(MANIFEST, COHERE_CELL, M.ROOT)
    metric = next(m for m in cell.per_layer if m.name == "chunk_attn_roofline")
    assert metric.layer == "kernels" and callable(metric.read)
    read, config = M.load_reader("chunk_attn_roofline"), config_file(COHERE)
    # a chunk of 2,048 at offset 8,192: three window layers at 4,096 keys a query, the
    # global layer at 8,193 .. 10,240
    pairs_w = 3 * 2048 * 4096
    pairs_g = sum(range(8193, 10241))
    counters = {"chunk_attn_pairs_window": 10 * pairs_w, "chunk_attn_pairs_global": 10 * pairs_g,
                "decode_tokens": 400}
    by_scope = {"chunk_loop/attention/window": 0.17, "chunk_loop/attention/global": 0.10,
                "decode_loop/attention/window": 5.0, "chunk_loop/mlp/moe/experts": 3.0,
                "prefill/attention/window": 7.0}
    run = SimpleNamespace(
        trace_reduced={"busy_s": 8.0, "by_scope": by_scope, "own_by_op": {}},
        trace_counters=counters, config=config, chips=1, peaks=M.load_peaks("TPU v5 lite"))
    least = 4 * 128 * 128 * 10 * (pairs_w + pairs_g) / 197e12
    assert read(run) == pytest.approx(100 * least / 0.27)
    assert 0 < read(run) < 100
    slower = SimpleNamespace(**{**vars(run), "trace_reduced": {
        **run.trace_reduced, "by_scope": {**by_scope, "chunk_loop/attention/window": 1.7}}})
    assert 0 < read(slower) < read(run)
    for missing in (
        {"trace_counters": {"decode_tokens": 400}},  # the parent: no such counter
        {"trace_counters": {**counters, "chunk_attn_pairs_window": 0, "chunk_attn_pairs_global": 0}},
        {"trace_reduced": {"busy_s": 8.0, "by_scope": {"decode_loop/attention/window": 5.0}}},
        {"trace_reduced": None}, {"trace_counters": None},
    ):
        assert read(SimpleNamespace(**{**vars(run), **missing})) is None, missing


def test_the_manifest_carries_command_a_plus_s_cell_and_its_two_metrics():
    cell = M.resolve_cell(MANIFEST, COHERE_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 32}
    assert cell.traffic_name == "longdoc-closed"
    law = cell.traffic["prompt_tokens"]
    assert (law["law"], law["median"], law["sigma"], law["min"], law["max"]) == (
        "lognormal", 8192, 0.5, 4097, 16000)
    assert cell.traffic["output_tokens"] == {
        "law": "choice", "values": [128, 256, 512], "weights": [0.3, 0.4, 0.3]}
    # delivered tokens/s judges a cell only where the slots bound it (PERF.md section 2):
    # here the prefill lane does (occupancy 44-47%), ~27 requests fall into a window, and
    # the driver's two sets of six runs spread 4.1% and 6.7%, past half the 10% bound
    assert {m.name for m in cell.end_to_end} == {"tpot_p95_ms", "setup_s"}
    own = {m["name"]: m for m in MANIFEST["per_layer"]
           if m.get("workloads", [None])[0] == COHERE_CELL}  # told by the FIRST cell of their list
    assert {n: own[n]["moves"] for n in own} == {
        "swa_device_pct": "tpot_p95_ms", "swa_cache_roofline": "tpot_p95_ms",
        "chunk_attn_roofline": "tpot_p95_ms"}  # (the third: PR 39)
    registered = {m.name for m in cell.per_layer}
    assert {*own, "moe_device_pct", "moe_expert_roofline", "dispatch_roofline"} <= registered
    assert not {"ssm_state_roofline", "gdn_state_roofline", "mla_cache_roofline"} & registered
    names = [m["name"] for m in MANIFEST["per_layer"]]  # appended in PRs 38 and 39, in order
    at = names.index("swa_device_pct")
    assert names[at:at + 3] == list(own)
    assert MANIFEST["workloads"][4]["name"] == COHERE_CELL and len(MANIFEST["workloads"]) >= 5
    entry = MANIFEST["configs"][4]
    assert entry["name"] == COHERE and len(MANIFEST["configs"]) >= 5
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    for listed in ("moe_device_pct", "moe_expert_roofline"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == listed)
        assert COHERE_CELL in metric["workloads"], listed


def test_what_moves_tokens_a_second_is_recorded_in_command_a_plus_s_cell_and_judges_nothing():
    """Every metric that moves ``out_tok_s_per_chip`` keeps its file and its reader, so a
    traced run of the cell logs it (``recorded-only``); none is in the cell's result."""
    cell = M.resolve_cell(MANIFEST, COHERE_CELL, M.ROOT)
    registered = {m.name for m in cell.per_layer}
    logged = {m.name: m for m in M.unregistered(cell)}
    for name in ("kv_pages_given_back_pct", "batch_occupancy_pct", "empty_slot_queued_pct",
                 "kv_pages_peak_pct", "hbm_peak_gb", "moe_expert_load_ratio"):
        assert name not in registered and logged[name].moves == "out_tok_s_per_chip", name
        assert callable(logged[name].read)
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if metric["name"] == "out_tok_s_per_chip" or metric.get("moves") == "out_tok_s_per_chip":
            assert COHERE_CELL not in metric["workloads"], metric["name"]
    assert "kv_pages_given_back_pct" not in {m["name"] for m in MANIFEST["per_layer"]}


# ------------------------------------------------ ling-3.0-flash-vl (PR 40)
LING, LING_CELL = "ling-3.0-flash-vl", "ling-3.0-flash-vl.reason-closed"


def test_ling_s_counts_are_what_a_hand_reckons():
    """The cut's bytes as ISSUE 40 reckons them, recounted from the tree, and
    the counts of THIS chip's share: the held experts hit, the channel-decay
    state read and written, ONE latent a token, the chunk form's FLOPs."""
    arch = M.load_architecture("bailing-kda-mla-moe")
    config = config_file(LING)
    assert arch.weight_bytes(config) == 2 * config["parameters"] == 5_607_690_112
    assert arch.weight_bytes(config) == config["hbm"]["weights_bytes"]
    expert = 3 * 2560 * 768
    assert config["hbm"]["experts_bytes"] == 6 * 64 * expert * 2 == 4_529_848_320
    assert config["hbm"]["kda_mixers_bytes"] == 6 * 52_592_640 * 2
    assert config["hbm"]["mla_mixer_bytes"] == 31_965_184 * 2
    assert arch.state_bytes_per_token(config) == config["hbm"]["kv_bytes_per_token"] == 1152
    assert arch.recurrent_state_bytes(config, 1) == 13_025_280 == 6 * (2_097_152 + 73_728)
    assert arch.recurrent_state_bytes(config, 128) == config["hbm"]["recurrent_state_bytes"]
    step = arch.recurrent_state_step(config, 100)
    assert step["bytes"] == 2 * 100 * 13_025_280 and step["flops"] == 8 * 100 * 6 * 32 * 128 * 128
    assert arch.experts_hit(config, 128) == pytest.approx(64 * (1 - (1 - 8 / 512) ** 128))
    assert 55 < arch.experts_hit(config, 128) < 56
    layer = arch.expert_layer_step(config, 128, 55.0)
    gate = 2560 * 512
    assert layer["bytes"] == (55 * expert + expert + gate) * 2
    assert layer["flops"] == 2 * 128 * (1.0 * expert + expert + gate)  # 8 x 64 / 512 lie here
    # a head's token in a block of 64: 32 (2 dk + (dv + dk) + dv) + 3 dk dv multiply-adds
    chunk = arch.recurrent_chunk(config, 1000)
    assert chunk == {"flops": 2 * (32 * 5 * 128 + 3 * 128 * 128) * 32 * 6 * 1000, "bytes": 0.0}
    whole = arch.decode_step(config, 128, 1100)
    assert whole["bytes"] < arch.weight_bytes(config) + 2 * 128 * 13_025_280 + 1152 * 128 * 1100
    assert whole["bytes"] > 0.6 * arch.weight_bytes(config) + 2 * 128 * 13_025_280
    prefill = arch.prefill_chunk(config, 4, 1024, 0)
    assert prefill["flops"] > 2 * 4096 * 6 * 2 * expert + chunk["flops"] and prefill["bytes"] > 0


def test_the_program_s_description_of_ling_is_the_file_s():
    arch = M.load_architecture("bailing-kda-mla-moe")
    config = config_file(LING)
    described, runtime = arch.model(config, False)
    assert described.param_count == config["parameters"] == 2_803_845_056
    assert config["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                                   "vocab_size": 157184}
    assert config["published_layers"] == [0, 6, 7, 8, 9, 10, 11]
    assert "8 chips share a layer" in config["deployment"]
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (described.n_routed_experts, described.experts_scored, described.expert_first,
            described.n_experts_per_tok, described.n_group, described.topk_group) == (
        64, 512, 0, 8, 8, 4)
    assert described.layer_types == ("kda",) * 6 + ("attention",) and described.first_k_dense == 1
    assert described.stack_plan == (1, ("kda",) * 5 + ("attention",))
    assert (described.head_dim, described.cache_dims, described.n_kv_layers) == (192, (512, 64), 1)
    assert (described.gdn_n_v_heads, described.gdn_d_k, described.gdn_d_v) == (32, 128, 128)
    assert described.kda_lower_bound == -5.0 and described.attn_output_gate
    assert described.expert_swiglu_limits == (0.0,) * 6 == described.shared_expert_swiglu_limits
    assert described.state_dtype == "float32" and described.dtype == "bfloat16"
    assert described.recurrent_state_bytes(128) == config["hbm"]["recurrent_state_bytes"]
    assert (runtime.max_batch_size, runtime.max_seq_len, runtime.prefill_chunk,
            runtime.max_prefill_wave, runtime.prefix_cache, runtime.window_buckets) == (
        128, 4096, 1024, 4, False, (4096,))
    assert runtime.pool_pages() * 64 * 1152 == 8193 * config["hbm"]["page_bytes"]
    from calfkit_tpu.inference.config import preset

    assert preset("ling-3.0-flash-vl").param_count == config["published_parameters"]
    toy, _ = arch.model(config, True)
    assert toy.expert_share and toy.layer_types == described.layer_types
    # a HELD layer with a nonzero swiglu limit is refused by name; a variant not read, by its key
    with pytest.raises(ValueError, match="swiglu limit"):
        arch.model({**config, "published_layers": [0, 6, 7, 8, 9, 10, 35]}, False)
    with pytest.raises(ValueError, match="kda_safe_gate"):
        arch.model({**config, "kda_safe_gate": False}, False)
    tied, _ = arch.model({**config, "agreement": {**config["agreement"], "routing_tie": 0.03}},
                         False)
    assert tied.routing_tie == 0.03 and described.routing_tie == config["agreement"]["routing_tie"]


@pytest.mark.parametrize("fault", ["none", "jitter_inside_the_tie", "bias_left_out_of_the_choice",
                                   "top_k_without_groups"])
def test_ling_s_reference_follows_a_near_tie_and_catches_a_wrong_gate(fault):
    """The tie rule at the file's rehearsal sizes, in float32: the walk goes
    through delta-rule, latent and dense layers alike.  A program whose gate
    sees scores off by LESS than the tie serves tokens the reference accepts
    at every position it decides; one that leaves the bias out of the choice,
    or takes the top k without the groups, serves tokens no admitted routing
    gives, and fails.  (The bias added to the WEIGHTS moves a held expert's
    weight by a few per cent and no token at this size: tests/test_kda_mla_moe.py
    holds that control in the logits at 1e-4.)"""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import agreement
    from calfkit_tpu.inference import model as program
    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.config import RuntimeConfig
    from calfkit_tpu.inference.mamba import make_recurrent_state
    from calfkit_tpu.inference.sharding import make_mesh

    arch = M.load_architecture("bailing-kda-mla-moe")
    toy, _ = arch.model(config_file(LING), True)
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=24, routing_tie=0.004,
                              agreement_margin=0.25)
    params = arch.params(toy, RuntimeConfig(), make_mesh(tp=1, dp=1, devices=jax.devices()[:1]), 5)
    assert 0.9 < float(jnp.std(params["embed"])) < 1.1  # the token's own row at unit scale
    assert float(jnp.abs(params["layers"]["moe"]["router_bias"]).mean()) > 0.01  # NOT zero
    right, right_groups = moe.route, moe.kept_groups

    def jitter(h, lp, c):  # the choice's scores off by up to 0.0015: under half the tie either way
        noise = jax.random.uniform(jax.random.key(0), (c.experts_scored,), jnp.float32,
                                   -0.0015, 0.0015)
        return right(h, {**lp, "router_bias": lp["router_bias"] + noise}, c)

    def unbiased(h, lp, c):
        return right(h, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, c)

    if fault == "jitter_inside_the_tie":
        moe.route = jitter
    elif fault == "bias_left_out_of_the_choice":
        moe.route = unbiased
    elif fault == "top_k_without_groups":
        moe.kept_groups = lambda pick, c: jnp.ones((pick.shape[0], c.n_group), bool)
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (9, 14, 20, 27)]
    S = 27 + 24
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    try:
        forward = jax.jit(lambda tokens: program.forward(
            params, toy, tokens, pos, program.make_empty_cache(toy, 1, S),
            jnp.full((1,), S, jnp.int32), state=make_recurrent_state(toy, 1))[0])
        outs = []
        for prompt in prompts:
            seq = list(prompt)
            for _ in range(24):  # causal: the padding after a position moves nothing before it
                tokens = np.zeros((1, S), np.int32)
                tokens[0, :len(seq)] = seq
                seq.append(int(np.argmax(np.asarray(forward(jnp.asarray(tokens)))[0, len(seq) - 1])))
            outs.append(seq[len(prompt):])
    finally:
        moe.route, moe.kept_groups = right, right_groups
    result = agreement(arch.forward_top2, params, toy, prompts, outs, toy.agreement_margin, 8)
    if fault in ("none", "jitter_inside_the_tie"):
        assert result["ok"] and result["compared"] >= 24, result
    else:
        assert not result["ok"] and result["compared"] - result["equal"] >= 3, result


@pytest.mark.parametrize("case,rows,held_chosen", [
    ("held_group_in_doubt", 2, [[0, 1, 0, 0], [0, 0, 0, 0]]),  # kept or not: two streams
    ("doubt_between_groups_held_elsewhere", 1, [[0, 0, 0, 0]]),  # the same held experts: one
    ("no_doubt", 1, [[1, 0, 0, 0]]),
])
def test_ling_s_reference_follows_a_near_tie_between_groups(case, rows, held_chosen):
    """The tie rule one level up (PR 40: a run of the cell was refused at a
    position where the held group was kept by 0.00066): 4 groups of 4, 2 kept,
    3 experts a token, this device holding group 1.  A group within the tie of
    the last one kept opens a stream with the other choice of groups, unless
    both choices name the same held experts."""
    import numpy as np

    arch = M.load_architecture("bailing-kda-mla-moe")
    scored = np.full((1, 16), 0.1, np.float32)
    scored[0, 0:2] = 0.9, 0.8  # group 0: 1.7, kept in every case
    if case == "held_group_in_doubt":
        scored[0, 4:6], scored[0, 8:10] = (0.5, 0.75), (0.7, 0.548)  # 1.25 against 1.248
    elif case == "doubt_between_groups_held_elsewhere":
        scored[0, 4:6], scored[0, 8:10], scored[0, 12:14] = (0.4, 0.4), (0.7, 0.6), (0.7, 0.598)
    else:
        scored[0, 4:6], scored[0, 8:10] = (0.75, 0.5), (0.7, 0.5)  # 1.25 against 1.2
    parent, chosen, first, crowded = arch._choices(scored, 3, 0.004, 4, 2, (4, 4))
    assert list(parent) == [0] * rows and list(first) == [True] + [False] * (rows - 1)
    assert chosen[:, 4:8].tolist() == held_chosen and not crowded.any()
    assert (chosen.sum(-1) == 3).all() and chosen[:, :2].all()  # group 0's two lead every choice


def test_the_catalog_s_numbers_of_ling_are_the_file_s():
    """Every number of the published config under its own key, but the three
    cuts; the nested lists copied whole (the driver holds the file to the
    catalog the same way)."""
    config = config_file(LING)
    published = {
        "image_patch_token": 157157, "video_patch_token": 156909, "image_start_token": 157158,
        "video_start_token": 157160, "hidden_size": 2560, "intermediate_size": 6144,
        "first_k_dense_replace": 2, "max_position_embeddings": 131072,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8, "num_attention_heads": 32,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_key_value_heads": 32, "rope_theta": 6000000, "rms_norm_eps": 1e-06, "head_dim": 128,
        "partial_rotary_factor": 0.5, "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
        "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1, "rotary_dim": 64,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    }
    assert {k: config[k] for k in published} == published
    assert config["q_lora_rank"] is None and config["kda_safe_gate"] is True
    assert len(config["expert_swiglu_limit_list"]) == 42 == len(
        config["share_expert_swiglu_limit_list"])
    assert config["expert_swiglu_limit_list"][35:] == [4] * 7
    assert config["share_expert_swiglu_limit_list"][34:] == [5] * 6 + [7] * 2
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        7, 64, 19648)


def test_the_gdn_and_moe_readers_take_ling_s_architecture_as_they_stand():
    """``gdn_device_pct``, ``gdn_state_roofline`` and the three ``moe_*`` readers read
    the new cell through the scopes and counters' names, unedited; the new
    ``gdn_chunk_roofline`` reads the chunk form's share; each under 100%;
    nothing without a trace, and nothing from a program without the scopes."""
    from types import SimpleNamespace

    read = {n: M.load_reader(n) for n in (
        "gdn_device_pct", "gdn_state_roofline", "gdn_chunk_roofline", "moe_device_pct",
        "moe_expert_roofline", "moe_expert_load_ratio", "ssm_state_roofline")}
    arch, config = M.load_architecture("bailing-kda-mla-moe"), config_file(LING)
    steps, rows = 40, 100
    by_scope = {
        "decode_loop/gdn/state": 0.40, "decode_loop/gdn/conv": 0.02, "decode_loop/gdn/decay": 0.03,
        "decode_loop/gdn/in_proj": 0.05, "chunk_loop/gdn/state": 0.10,
        "chunk_loop/gdn/decay": 0.01, "decode_loop/mlp/moe/experts": 0.5,
        "decode_loop/mlp/moe/router/groups": 0.02, "decode_loop/mla/attention": 0.03,
        "chunk_loop/mlp/moe/experts": 0.1}
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": by_scope, "own_by_op": {}},
        trace_counters={"decode_tokens": rows * steps, "decode_dispatches": 5,
                        "short_dispatches": 0, "moe_experts_hit": 50 * 6 * steps,
                        "prefill_tokens": 20_000},
        counters={"window": {"moe_expert_tokens_max": 300, "moe_expert_tokens_mean": 100.0}},
        arch=arch, config=config, chips=1,
        model_config=SimpleNamespace(n_moe_layers=6, n_layers=7),
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64),
        peaks=M.load_peaks("TPU v5 lite"))
    assert read["gdn_device_pct"](run) == pytest.approx(100 * 0.61 / 2.0)
    state = 2 * rows * 13_025_280 / 819e9  # bytes bound
    assert read["gdn_state_roofline"](run) == pytest.approx(100 * state * steps / 0.42)
    assert 0 < read["gdn_state_roofline"](run) < 100
    least = arch.recurrent_chunk(config, 20_000)["flops"] / 197e12
    assert read["gdn_chunk_roofline"](run) == pytest.approx(100 * least / 0.10)
    assert 0 < read["gdn_chunk_roofline"](run) < 100
    assert read["moe_device_pct"](run) == pytest.approx(100 * 0.62 / 2.0)
    assert read["moe_expert_load_ratio"](run) == pytest.approx(3.0)
    # the grouped decode products run in the compiler's own kernel, which keeps no scope:
    # moe_device_pct adds it by its name, moe_expert_roofline cannot (not listed for the cell)
    grouped = SimpleNamespace(**{**vars(run), "trace_reduced": {
        **run.trace_reduced, "own_by_op": {"(unscoped) ragged-dot-none": 0.4}}})
    assert read["moe_device_pct"](grouped) == pytest.approx(100 * 1.02 / 2.0)
    assert read["moe_expert_roofline"](grouped) == read["moe_expert_roofline"](run)
    assert read["ssm_state_roofline"](run) is None  # granite's own finds nothing of its
    # the parent's side and every other cell: no chunk scope, no prompt tokens, no trace,
    # an architecture without the count
    for missing in (
        {"trace_reduced": {"busy_s": 2.0, "by_scope": {"decode_loop/gdn/state": 0.4}}},
        {"trace_counters": {**run.trace_counters, "prefill_tokens": 0}},
        {"trace_reduced": None}, {"trace_counters": None},
        {"arch": M.load_architecture("qwen3-next-gdn-moe")},
    ):
        assert read["gdn_chunk_roofline"](SimpleNamespace(**{**vars(run), **missing})) is None
    untraced = SimpleNamespace(**{**vars(run), "trace_reduced": None, "trace_counters": None})
    assert all(read[n](untraced) is None for n in (
        "gdn_device_pct", "gdn_state_roofline", "gdn_chunk_roofline"))


def test_the_manifest_carries_ling_s_cell_and_its_one_metric():
    cell = M.resolve_cell(MANIFEST, LING_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 128}
    assert cell.traffic_name == "reason-closed" and cell.traffic["loop"] == "closed"
    law = cell.traffic["prompt_tokens"]
    assert (law["law"], law["median"], law["sigma"], law["min"], law["max"]) == (
        "lognormal", 512, 0.6, 128, 1024)
    assert cell.traffic["output_tokens"] == {
        "law": "choice", "values": [768, 1024, 1536], "weights": [0.3, 0.4, 0.3]}
    assert cell.traffic["sharing"] == "none"
    assert {m.name for m in cell.end_to_end} >= {"tpot_p95_ms", "setup_s"}
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "gdn_chunk_roofline")
    assert entry == {"name": "gdn_chunk_roofline", "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels", "moves": "tpot_p95_ms",
                     "workloads": [LING_CELL]}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[names.index("chunk_attn_roofline") + 1] == entry["name"]  # appended (PR 40)
    registered = {m.name for m in cell.per_layer}
    assert {"gdn_chunk_roofline", "gdn_device_pct", "gdn_state_roofline", "moe_device_pct",
            "moe_expert_load_ratio", "dispatch_roofline", "dispatch_step_ms"} <= registered
    # mla_cache_roofline's reader would count one latent layer seven times over, and
    # moe_expert_roofline's sums the seconds under decode_loop/.../moe, where this cell's
    # decode products (the compiler's ragged-dot kernel) carry NO scope: it read 179% on the
    # chip (PERF.md sections 6 and 7; moe_device_pct adds the unscoped kernel and reads right)
    assert not {"mla_cache_roofline", "moe_expert_roofline"} & registered
    for listed in ("gdn_device_pct", "gdn_state_roofline", "moe_device_pct",
                   "moe_expert_load_ratio"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == listed)
        assert LING_CELL in metric["workloads"], listed
    assert MANIFEST["workloads"][5]["name"] == LING_CELL  # the sixth cell, appended at PR 40
    config = MANIFEST["configs"][5]
    assert config["name"] == LING
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    assert all(len(w["why"]) <= 200 for w in MANIFEST["workloads"] + MANIFEST["configs"])


# ---------------------------------------------------------------------------
# LFM2-8B-A1B (PR 44): a gated short convolution beside rotary GQA attention
# with normed heads, two dense layers, 32 bias-routed experts and no shared one
# ---------------------------------------------------------------------------
LFM2, LFM2_CELL = "lfm2-8b-a1b", "lfm2-8b-a1b.longform-closed"


def test_lfm2_s_counts_are_what_a_hand_reckons():
    """The cut's bytes as ISSUE 44 reckons them, recounted from the tree, and
    the counts of a decode step: every expert hit at 128 rows, the tails read
    and written, K and V of three layers, the mixers' weight stream."""
    arch = M.load_architecture("lfm2-conv-gqa-moe")
    config = config_file(LFM2)
    hbm = config["hbm"]
    assert arch.weight_bytes(config) == 2 * config["parameters"] == 7_857_456_512
    assert arch.weight_bytes(config) == hbm["weights_bytes"]
    expert = 3 * 2048 * 1792
    assert hbm["experts_bytes"] == 10 * 32 * expert * 2 == 7_046_430_720
    assert hbm["conv_mixers_bytes"] == 9 * 4 * 2048 * 2048 * 2  # W_in (2,048 -> 6,144) and W_out
    assert hbm["attention_mixers_bytes"] == 3 * 10_485_760 * 2
    assert hbm["dense_ffn_bytes"] == 2 * 3 * 2048 * 7168 * 2
    assert hbm["embedding_and_tied_head_bytes"] == 65536 * 2048 * 2  # ONCE: the head is tied
    assert sum(hbm[k] for k in hbm if k.endswith("_bytes") and k not in (
        "weights_bytes", "recurrent_state_bytes", "pool_bytes", "page_bytes",
        "chunk_logits_bytes", "temporaries_bytes")) == hbm["weights_bytes"]
    assert arch.state_bytes_per_token(config) == hbm["kv_bytes_per_token"] == 6144
    assert arch.recurrent_state_bytes(config, 1) == hbm["recurrent_state_bytes_per_slot"] == 73_728
    assert arch.recurrent_state_bytes(config, 128) == hbm["recurrent_state_bytes"] == 9_437_184
    assert hbm["page_bytes"] == 64 * 6144 and hbm["pool_bytes"] == 4225 * 64 * 6144
    assert hbm["pool_tokens"] == 128 * 33 * 64 >= 128 * (1024 + 25 + 1024)  # no oversubscription
    assert hbm["chunk_logits_bytes"] == 4 * 1024 * 65536 * 2
    assert arch.experts_hit(config, 128) == pytest.approx(32 * (1 - (1 - 4 / 32) ** 128))
    assert arch.experts_hit(config, 128) > 31.999  # every expert, every step: 16 rows each
    layer = arch.expert_layer_step(config, 128, 32.0)
    gate = 2048 * 32
    assert layer["bytes"] == (32 * expert + gate) * 2  # no shared expert
    assert layer["flops"] == 2 * 128 * (4 * expert + gate)
    mixers = arch.shortconv_step(config, 128)
    assert mixers["bytes"] == 9 * (4 * 2048 * 2048 + 3 * 2048 + 2048) * 2 + 2 * 128 * 73_728
    whole = arch.decode_step(config, 128, 1100)
    assert whole["bytes"] < arch.weight_bytes(config) + 2 * 128 * 73_728 + 6144 * 128 * 1100 + 1
    assert whole["bytes"] > 8.4e9  # the ISSUE's 8.5 GB a step
    prefill = arch.prefill_chunk(config, 4, 1024, 0)
    assert prefill["flops"] > 2 * 4096 * 10 * 4 * expert and prefill["bytes"] > 0


def test_the_program_s_description_of_lfm2_is_the_file_s():
    arch = M.load_architecture("lfm2-conv-gqa-moe")
    config = config_file(LFM2)
    described, runtime = arch.model(config, False)
    assert described.param_count == config["parameters"] == 3_928_728_256
    assert config["published"] == {"num_hidden_layers": 24}
    assert config["published_layers"] == list(range(12)) and config["reduced"] == ["num_hidden_layers"]
    assert "TWO TPU v5e chips as two pipeline stages" in config["deployment"]
    assert (described.n_routed_experts, described.experts_scored, described.n_experts_per_tok,
            described.n_shared_experts, described.first_k_dense) == (32, 32, 4, 0, 2)
    assert described.layer_types == ("conv", "conv", "attention", "conv") * 3
    assert described.stack_plan == (4, ("conv", "conv", "attention", "conv"))
    assert (described.head_dim, described.rotary_dim, described.cache_heads, described.cache_dims,
            described.n_kv_layers) == (64, 64, 8, (64, 64), 3)
    assert described.qk_norm and described.tie_embeddings and described.conv_L_cache == 3
    assert (described.scoring_func, described.topk_method, described.topk_norm_eps) == (
        "sigmoid", "noaux_tc", 1e-6)
    assert described.dtype == "bfloat16" and described.rope_theta == 1e6
    assert described.recurrent_state_bytes(128) == config["hbm"]["recurrent_state_bytes"]
    assert (runtime.max_batch_size, runtime.max_seq_len, runtime.prefill_chunk,
            runtime.max_prefill_wave, runtime.prefix_cache, runtime.window_buckets,
            runtime.pool_pages()) == (128, 3072, 1024, 4, False, (3072,), 4225)
    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.config import preset

    assert preset("lfm2-8b-a1b").param_count == config["published_parameters"]
    # since PR 45 the shape takes the default (moe.py): its decode steps' 128 rows dense,
    # every chunk of the cell (a row of 1,024 at the least) grouped
    assert moe.dense_form(runtime.max_batch_size, described)
    assert not moe.dense_form(runtime.prefill_chunk, described)
    toy, toy_runtime = arch.model(config, True)
    assert toy.layer_types == described.layer_types and toy.first_k_dense == 2
    assert toy_runtime.max_batch_size == 8 and toy.tail_error_limit == 0.0
    assert (described.tail_error_limit, described.refused_limit) == (0.02, 10)
    for key, value in (("use_expert_bias", False), ("conv_bias", True), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            arch.model({**config, key: value}, False)
    with pytest.raises(ValueError, match="published_layers"):
        arch.model({**config, "published_layers": list(range(11))}, False)
    tied, _ = arch.model({**config, "agreement": {**config["agreement"], "routing_tie": 0.03}},
                         False)
    assert tied.routing_tie == 0.03 and described.routing_tie == config["agreement"]["routing_tie"]


def test_the_catalog_s_numbers_of_lfm2_are_the_file_s():
    """Every number of the published config under its own key, but the one
    cut; ``layer_types`` copied whole (24 entries: ``published_layers`` names
    the 12 kept)."""
    config = config_file(LFM2)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    assert {k: config[k] for k in published} == published
    assert len(config["layer_types"]) == 24 and config["layer_types"].count("full_attention") == 6
    assert [i for i, t in enumerate(config["layer_types"]) if t == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert config["num_hidden_layers"] == 12


@pytest.mark.parametrize("fault", ["none", "jitter_inside_the_tie", "bias_left_out_of_the_choice",
                                   "bias_left_out_and_a_limit_of_ten"])
def test_lfm2_s_reference_follows_a_near_tie_and_catches_a_wrong_gate(fault, capsys):
    """The tie rule at the file's rehearsal sizes, in float32: the walk goes
    through conv, attention and dense layers alike.  A program whose gate sees
    scores off by LESS than the tie serves tokens the reference accepts at
    every position it decides; one that leaves the bias out of the choice
    serves tokens no admitted routing gives, and fails.  ``refused_limit``
    counts those positions: up to it they are returned undecided (the
    reading printed beside its limit), over it they stand and fail."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import agreement
    from calfkit_tpu.inference import model as program
    from calfkit_tpu.inference import moe
    from calfkit_tpu.inference.config import RuntimeConfig
    from calfkit_tpu.inference.mamba import make_recurrent_state
    from calfkit_tpu.inference.sharding import make_mesh

    arch = M.load_architecture("lfm2-conv-gqa-moe")
    toy, _ = arch.model(config_file(LFM2), True)
    # (refused_limit 0: in float32 no neighbour's stream flips, so no refusal is forgiven)
    toy = dataclasses.replace(toy, dtype="float32", agreement_new_tokens=24, routing_tie=0.004,
                              agreement_margin=0.25,
                              refused_limit=10 if fault.endswith("ten") else 0)
    params = arch.params(toy, RuntimeConfig(), make_mesh(tp=1, dp=1, devices=jax.devices()[:1]), 5)
    assert 0.9 < float(jnp.std(params["embed"])) * toy.d_model ** 0.5 < 1.1  # 1/sqrt(hidden): tied
    assert float(jnp.abs(params["layers"]["moe"]["router_bias"]).mean()) > 0.01  # NOT zero
    right = moe.route

    def jitter(h, lp, c):  # the choice's scores off by up to 0.0015: under half the tie either way
        noise = jax.random.uniform(jax.random.key(0), (c.experts_scored,), jnp.float32,
                                   -0.0015, 0.0015)
        return right(h, {**lp, "router_bias": lp["router_bias"] + noise}, c)

    def unbiased(h, lp, c):
        return right(h, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, c)

    if fault == "jitter_inside_the_tie":
        moe.route = jitter
    elif fault.startswith("bias_left_out"):
        moe.route = unbiased
    rng = np.random.default_rng(1)
    prompts = [[int(t) for t in rng.integers(3, toy.vocab_size, n)] for n in (9, 14, 20, 27)]
    S = 27 + 24
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    try:
        forward = jax.jit(lambda tokens: program.forward(
            params, toy, tokens, pos, program.make_empty_cache(toy, 1, S),
            jnp.full((1,), S, jnp.int32), state=make_recurrent_state(toy, 1))[0])
        outs = []
        for prompt in prompts:
            seq = list(prompt)
            for _ in range(24):  # causal: the padding after a position moves nothing before it
                tokens = np.zeros((1, S), np.int32)
                tokens[0, :len(seq)] = seq
                seq.append(int(np.argmax(np.asarray(forward(jnp.asarray(tokens)))[0, len(seq) - 1])))
            outs.append(seq[len(prompt):])
    finally:
        moe.route = right
    capsys.readouterr()
    result = agreement(arch.forward_top2, params, toy, prompts, outs, toy.agreement_margin, 8)
    printed = capsys.readouterr()
    line = next(json.loads(l) for l in printed.out.splitlines() if '"phase": "reference"' in l)
    if fault in ("none", "jitter_inside_the_tie"):
        assert result["ok"] and result["compared"] >= 24 and line["refused"] == 0, result
    elif fault == "bias_left_out_of_the_choice":
        assert not result["ok"] and result["compared"] - result["equal"] >= 1, result
        assert line["refused"] == result["compared"] - result["equal"] and "FAIL" in printed.err
    else:  # the same wrong tokens, forgiven up to the limit and said so
        assert result["ok"] and 1 <= line["refused"] <= 10 == line["refused_limit"], line
        assert "(limit <= 10)" in printed.err and "FAIL" not in printed.err


def test_the_moe_readers_take_lfm2_s_architecture_as_they_stand():
    """``moe_device_pct`` and ``moe_expert_load_ratio`` read the new cell
    through the scopes' and counters' names, unedited (the grouped decode
    products by their kernel's name); the two new readers read theirs; the
    other recurrent kinds' readers find nothing of theirs."""
    from types import SimpleNamespace

    read = {n: M.load_reader(n) for n in (
        "moe_device_pct", "moe_expert_load_ratio", "shortconv_device_pct",
        "shortconv_mixer_roofline", "gdn_device_pct", "gdn_state_roofline", "ssm_state_roofline",
        "ssm_device_pct")}
    arch, config = M.load_architecture("lfm2-conv-gqa-moe"), config_file(LFM2)
    steps, rows = 40, 120
    by_scope = {
        "decode_loop/shortconv/in_proj": 0.03, "decode_loop/shortconv/conv": 0.005,
        "decode_loop/shortconv/out_proj": 0.015, "chunk_loop/shortconv/in_proj": 0.01,
        "decode_loop/mlp/moe/router": 0.05, "decode_loop/mlp/moe/combine": 0.05,
        "decode_loop/attention": 0.03, "chunk_loop/mlp/moe/group": 0.1}
    run = SimpleNamespace(
        trace_reduced={"busy_s": 2.0, "by_scope": by_scope,
                       "own_by_op": {"(unscoped) ragged-dot-none": 0.9}},
        trace_counters={"decode_tokens": rows * steps, "decode_dispatches": 5,
                        "short_dispatches": 0, "moe_experts_hit": 32 * 10 * steps},
        counters={"window": {"moe_expert_tokens_max": 130, "moe_expert_tokens_mean": 100.0}},
        arch=arch, config=config, chips=1,
        runtime=SimpleNamespace(decode_steps_per_dispatch=8, page_size=64),
        peaks=M.load_peaks("TPU v5 lite"))
    assert read["shortconv_device_pct"](run) == pytest.approx(100 * 0.06 / 2.0)
    least = arch.shortconv_step(config, rows)["bytes"] / 819e9
    assert read["shortconv_mixer_roofline"](run) == pytest.approx(100 * least * steps / 0.05)
    assert 0 < read["shortconv_mixer_roofline"](run) < 100
    assert read["moe_device_pct"](run) == pytest.approx(100 * (0.2 + 0.9) / 2.0)
    assert read["moe_expert_load_ratio"](run) == pytest.approx(1.3)
    for other in ("gdn_device_pct", "gdn_state_roofline", "ssm_state_roofline", "ssm_device_pct"):
        assert read[other](run) is None, other
    untraced = SimpleNamespace(**{**vars(run), "trace_reduced": None, "trace_counters": None})
    assert read["shortconv_device_pct"](untraced) is None
    assert read["shortconv_mixer_roofline"](untraced) is None


def test_the_manifest_carries_lfm2_s_cell_and_its_two_metrics():
    cell = M.resolve_cell(MANIFEST, LFM2_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 128}
    assert cell.traffic_name == "longform-closed" and cell.traffic["loop"] == "closed"
    law = cell.traffic["prompt_tokens"]
    assert (law["law"], law["median"], law["sigma"], law["min"], law["max"]) == (
        "lognormal", 512, 0.6, 128, 1024)
    # uniform 256-1,024 as the generator can run it: the uniform law's own 64 quantiles,
    # equally likely (Traffic.agents() builds one Agent a budget from 64 quantiles)
    out = cell.traffic["output_tokens"]
    assert out["law"] == "choice" and out["weights"] == [1] * 64
    assert out["values"] == [int(round(256 + (i + 0.5) / 64 * 768)) for i in range(64)]
    assert sum(out["values"]) / 64 == 640 and (out["values"][0], out["values"][-1]) == (262, 1018)
    from benchmarks.traffic import Traffic

    mix = Traffic(cell.traffic, cell.params, 1)
    budgets = {a.max_tokens for a in mix.agents()}
    stream = mix.caller_stream(0)
    assert len(budgets) == 64 and {next(stream).out_tokens for _ in range(200)} <= budgets
    assert (cell.traffic["trace_s"], cell.traffic["drain_s"], cell.traffic["request_timeout_s"]) == (
        8, 60, 120)  # ISSUE 44's 40 s of drain was tried and failed a request: drain_why
    assert cell.traffic["sharing"] == "none"
    assert {m.name for m in cell.end_to_end} == {"tpot_p95_ms", "out_tok_s_per_chip", "setup_s"}
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index("shortconv_device_pct")
    assert names[at:at + 3] == [
        "shortconv_device_pct", "shortconv_mixer_roofline",
        "moe_grouped_roofline"]  # appended in PR 44, in order
    for entry in MANIFEST["per_layer"][at:at + 3]:  # told by the FIRST cell of their list
        assert entry["workloads"][0] == LFM2_CELL and entry["moves"] == "tpot_p95_ms"
    assert MANIFEST["configs"][6]["name"] == LFM2 and MANIFEST["workloads"][6]["name"] == LFM2_CELL
    registered = {m.name for m in cell.per_layer}
    assert {"shortconv_device_pct", "shortconv_mixer_roofline", "moe_grouped_roofline", "moe_device_pct",
            "moe_expert_load_ratio", "dispatch_roofline", "dispatch_step_ms", "hbm_peak_gb",
            "batch_occupancy_pct", "empty_slot_queued_pct", "kv_pages_peak_pct"} <= registered
    # the decode steps' products are grouped (the compiler's ragged-dot kernel, under NO
    # scope): moe_expert_roofline's reader sums the seconds under decode_loop/.../moe and
    # would pass 100% (it read 179% in Ling's cell): not listed for this cell either;
    # moe_grouped_roofline reads that kernel by its name
    assert not {"moe_expert_roofline", "gdn_device_pct", "ssm_device_pct",
                "mla_cache_roofline"} & registered
    for listed in ("out_tok_s_per_chip",):
        metric = next(m for m in MANIFEST["end_to_end"] if m["name"] == listed)
        assert LFM2_CELL in metric["workloads"][-3:]  # last, until PR 47's and PR 54's cells joined


# ------------------------------------------------ mellum2-12b-a2.5b-instruct (PR 47)
MELLUM, MELLUM_CELL = ("mellum2-12b-a2.5b-instruct",
                       "mellum2-12b-a2.5b-instruct.mixed-lengths-closed")


def test_mellum_s_counts_are_what_a_hand_reckons():
    """The cut's bytes as ISSUE 47's table reckons them, and the counts of
    THIS chip: all 64 experts of 8 layers, the embedding AND the untied head,
    a window layer's keys and values at min(context, 1,024) tokens."""
    arch = M.load_architecture("mellum-moe-swa")
    config = config_file(MELLUM)
    expert = 3 * 2304 * 896
    attn = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    layer = attn + 64 * expert + 2304 * 64 + 2 * 2304
    assert (attn, expert, layer) == (21_233_664, 6_193_152, 417_747_456)
    hbm = config["hbm"]
    assert config["parameters"] == 8 * layer + 2 * 98304 * 2304 + 2304 == 3_794_966_784
    assert config["published_parameters"] == 28 * layer + 2 * 98304 * 2304 + 2304 == 12_149_915_904
    assert arch.weight_bytes(config) == 2 * config["parameters"] == hbm["weights_bytes"]
    assert round(hbm["weights_bytes"] / 1e9, 2) == 7.59
    assert hbm["experts_bytes"] == 8 * 64 * expert * 2 and hbm["attention_bytes"] == 8 * attn * 2
    assert hbm["embedding_bytes"] == hbm["head_bytes"] == 98304 * 2304 * 2
    assert hbm["gate_and_norms_bytes"] == (8 * (2304 * 64 + 2 * 2304) + 2304) * 2
    assert sum(hbm[k] for k in ("experts_bytes", "attention_bytes", "gate_and_norms_bytes",
                                "embedding_bytes", "head_bytes")) == hbm["weights_bytes"]
    assert hbm["kv_bytes_per_token_per_layer"] == 2048 and hbm["page_bytes_per_layer"] == 64 * 2048
    assert arch.state_bytes_per_token(config) == 2 * 2048  # the two global layers keep a token
    assert hbm["window_ring_pages_per_slot"] == 18 and hbm["window_pool_pages"] == 64 * 18
    assert hbm["window_pool_bytes"] == 6 * (64 * 18 + 1) * 64 * 2048
    assert hbm["global_pool_bytes"] == 2 * config["runtime"]["num_kv_pages"] * 64 * 2048
    assert hbm["global_pool_tokens"] == 14336 * 64 == 917_504  # ISSUE 47's 8,192 pages, grown
    assert hbm["prefill_scratch_bytes_per_row"] == 8 * 16384 * 2048
    assert hbm["chunk_logits_bytes"] == 2048 * 98304 * 2
    assert hbm["one_pool_for_every_layer_bytes"] > 16e9  # what does not fit beside anything
    before_temporaries = sum(hbm[k] for k in (
        "weights_bytes", "window_pool_bytes", "global_pool_bytes",
        "prefill_scratch_bytes_per_row", "chunk_logits_bytes"))
    assert 12.8e9 < before_temporaries < 13.0e9 and hbm["weights_bytes"] > 0.25 * 16e9
    # every expert is held and, at 64 rows, hit: 8 rows an expert a step, the deployment's own
    assert arch.experts_hit(config, 64) == pytest.approx(64 * (1 - (1 - 8 / 64) ** 64))
    assert 63.9 < arch.experts_hit(config, 64) < 64
    step = arch.expert_layer_step(config, 64, 64.0)
    gate = 2304 * 64
    assert step["bytes"] == (64 * expert + gate) * 2  # 0.79 GB a layer
    assert step["flops"] == 2 * 64 * (8 * expert + gate)
    ring = arch.window_layers_step(config, 64, 6 * 64 * 1024.0)
    assert ring["bytes"] == 6 * 64 * 1024 * 2048 and ring["flops"] == 4 * 32 * 128 * 6 * 64 * 1024
    pages = arch.global_layers_step(config, 64, 2 * 64 * 3000.0)
    assert pages["bytes"] == 2 * 64 * 3000 * 2048 and pages["flops"] == 4 * 32 * 128 * 2 * 64 * 3000
    short, long = (arch.decode_step(config, 64, n) for n in (1024, 12000))
    # past the window only the TWO global layers' keys and values grow
    assert long["bytes"] - short["bytes"] == pytest.approx(2 * 64 * (12000 - 1024) * 2048)
    assert long["flops"] - short["flops"] == pytest.approx(4 * 32 * 128 * 2 * 64 * (12000 - 1024))
    assert 8.0e9 < short["bytes"] < 9.0e9  # ISSUE 47: ~8.5 GB a step, 7.1 of them weights
    early, late = (arch.prefill_chunk(config, 1, 2048, at) for at in (2048, 12288))
    assert late["flops"] - early["flops"] == pytest.approx(
        4 * 32 * 128 * 2 * 2048 * (12288 - 2048))
    assert 1.1e9 < early["flops"] / 2048 < 1.6e9  # a prompt token: 1.13 GFLOP of products + attention


def test_the_program_s_description_of_mellum_is_the_file_s():
    arch = M.load_architecture("mellum-moe-swa")
    config = config_file(MELLUM)
    described, runtime = arch.model(config, False)
    assert described.param_count == config["parameters"]
    assert config["published"] == {"num_hidden_layers": 28} and config["reduced"] == [
        "num_hidden_layers"]
    assert "four-stage pipeline" in config["deployment"] and "stage 0" in config["deployment"]
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 28  # kept whole
    assert described.layer_types == ("window", "window", "window", "attention") * 2
    assert (described.n_routed_experts, described.experts_scored, described.expert_first,
            described.n_experts_per_tok, described.n_shared_experts, described.expert_share) == (
        64, 64, 0, 8, 0, False)
    assert (described.head_dim, described.rotary_dim, described.n_heads, described.n_kv_heads,
            described.sliding_window, described.moe_d_ff, described.vocab_size) == (
        128, 128, 32, 4, 1024, 896, 98304)
    assert (described.norm, described.parallel_block, described.position_embedding,
            described.scoring_func, described.topk_method, described.tie_embeddings,
            described.norm_topk_prob) == ("rms", False, "rope", "softmax", "greedy", False, True)
    scaling = described.rope_scaling_global
    assert (scaling.rope_type, scaling.factor, scaling.original_max_position_embeddings,
            scaling.beta_fast, scaling.beta_slow, scaling.attention_factor) == (
        "yarn", 16, 8192, 32, 1, 1.2772588722239782)
    assert (runtime.max_batch_size, runtime.max_seq_len, runtime.prefill_chunk,
            runtime.max_prefill_wave, runtime.prefix_cache, runtime.window_buckets,
            runtime.decode_steps_per_dispatch) == (64, 18432, 2048, 1, False, (18432,), 4)
    assert described.sliding_window < runtime.prefill_chunk  # the first cell of which that is true
    assert described.window_ring_pages(runtime.page_size, runtime.decode_steps_per_dispatch) == 18
    from calfkit_tpu.inference.config import preset

    published = preset("mellum2-12b-a2.5b-instruct")
    assert published.param_count == config["published_parameters"]
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "moe_d_ff", "sliding_window",
                  "n_experts_per_tok", "n_routed_experts", "vocab_size", "rope_theta", "norm_eps",
                  "rope_scaling_global", "tie_embeddings", "norm", "parallel_block"):
        assert getattr(described, field) == getattr(published, field), field
    assert published.layer_types[:8] == described.layer_types
    toy, toy_runtime = arch.model(config, True)
    assert toy.layer_types == described.layer_types and not toy.expert_share
    assert toy.sliding_window < toy_runtime.prefill_chunk
    for key, value in (("tie_word_embeddings", True), ("attention_bias", True),
                       ("max_window_layers", 14)):
        with pytest.raises(ValueError, match=key):
            arch.model({**config, key: value}, False)
    with pytest.raises(ValueError, match="not sparse"):
        arch.model({**config, "mlp_layer_types": ["dense"] * 28}, False)
    with pytest.raises(ValueError, match="only 'default' and 'yarn'"):
        arch.model({**config, "rope_parameters": {
            **config["rope_parameters"], "full_attention": {
                **config["rope_parameters"]["full_attention"], "rope_type": "llama3"}}}, False)


def test_the_catalog_s_numbers_of_mellum_are_the_file_s():
    """Every number of the published config under its own key, but the one
    cut, and ``rope_parameters`` whole (the driver holds the file to the
    catalog the same way)."""
    config = config_file(MELLUM)
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0, "moe_intermediate_size": 896,
        "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "sliding_window": 1024,
        "vocab_size": 98304, "model_type": "mellum", "hidden_act": "silu",
        "attention_bias": False, "norm_topk_prob": True, "tie_word_embeddings": False,
        "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                               "original_max_position_embeddings": 8192, "beta_fast": 32,
                               "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    }
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert config["source"] == row["source_url"]
        assert {k: config[k] for k in row["config"] if k != "num_hidden_layers"} == {
            k: v for k, v in row["config"].items() if k != "num_hidden_layers"}


def test_the_mixed_lengths_traffic_is_the_issue_s():
    """Short and long prompts in one queue: 87.5% of 256-2,048 (mean 976),
    12.5% of 8,448-16,000 (mean 11,888), every long one past YaRN's original
    context; five prefill buckets at a chunk of 2,048; outputs of mean 589."""
    from benchmarks.traffic import Traffic, quantiles

    cell = M.resolve_cell(MANIFEST, MELLUM_CELL, M.ROOT)
    assert cell.chips == 1 and cell.params == {"callers": 64}
    assert cell.traffic_name == "mixed-lengths-closed" and cell.traffic["loop"] == "closed"
    assert (cell.traffic["drain_s"], cell.traffic["request_timeout_s"],
            cell.traffic["trace_s"], cell.traffic["sharing"]) == (80, 300, 8, "none")
    law = cell.traffic["prompt_tokens"]
    assert law["law"] == "choice" and law["weights"] == [7] * 8 + [1] * 8
    short, long = law["values"][:8], law["values"][8:]
    assert short == [256, 384, 512, 768, 1024, 1280, 1536, 2048]
    assert long == [8448, 9216, 10240, 11264, 12288, 13312, 14336, 16000]
    assert sum(short) / 8 == 976 and sum(long) / 8 == 11888 and min(long) > 8192
    sizes = quantiles(law, 4096)
    assert sum(n > 2048 for n in sizes) / 4096 == 0.125
    assert round(sum(sizes) / 4096) == 2340
    assert 0.63 < sum(n for n in sizes if n > 2048) / sum(sizes) < 0.64  # ISSUE 47: "63%"
    chunk = cell.config["runtime"]["prefill_chunk"]
    assert sorted({-(-n // chunk) * chunk for n in sizes}) == [2048, 10240, 12288, 14336, 16384]
    out = cell.traffic["output_tokens"]
    assert out == {"law": "choice", "values": [256, 384, 512, 768, 1024],
                   "weights": [1, 1, 1, 1, 1]}
    assert round(sum(out["values"]) / 5) == 589
    traffic = Traffic(cell.traffic, cell.params, seed=1)
    assert sorted(a.max_tokens for a in traffic.agents()) == out["values"]  # an Agent a budget
    assert traffic.prompt_range() == (256, 16000) and traffic.callers() == 64
    # the longest request fits a slot, and the agreement's prompts stand on both sides of
    # the window, of the ring's first wrap and of the original context
    assert 16000 + 1024 + 25 <= cell.config["runtime"]["max_seq_len"]
    prompts = cell.config["agreement"]["prompt_tokens"]
    for edge in (1024, 18 * 64, 8192):
        assert min(prompts) < edge < max(prompts)
        assert any(edge - 200 < n < edge for n in prompts) and any(edge < n <= edge + 200 for n in prompts)


def test_the_manifest_carries_mellum_s_cell_and_its_two_metrics():
    cell = M.resolve_cell(MANIFEST, MELLUM_CELL, M.ROOT)
    entry = MANIFEST["configs"][7]  # the eighth configuration, appended at PR 47
    assert entry["name"] == MELLUM and entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
                               "blob/main/config.json")
    assert MANIFEST["workloads"][7]["name"] == MELLUM_CELL and len(MANIFEST["workloads"]) >= 8
    assert len(MANIFEST["workloads"][7]["why"]) <= 200 and len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
    own = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [MELLUM_CELL]]
    # (the last two until PR 52's eight, in every cell, were appended behind them)
    assert own == MANIFEST["per_layer"][29:31] and [m["name"] for m in own] == [
        "global_cache_roofline", "chunk_padding_pct"]
    assert own[0] == {"name": "global_cache_roofline", "unit": "%", "better": "higher",
                      "source": "device_trace", "layer": "kernels", "moves": "tpot_p95_ms",
                      "workloads": [MELLUM_CELL]}
    assert own[1] == {"name": "chunk_padding_pct", "unit": "%", "better": "lower",
                      "source": "program_counter", "layer": "admission and batching",
                      "moves": "tpot_p95_ms", "workloads": [MELLUM_CELL]}
    registered = {m.name for m in cell.per_layer}
    for listed in ("swa_cache_roofline", "swa_device_pct", "chunk_attn_roofline",
                   "moe_device_pct", "moe_expert_roofline", "moe_grouped_roofline"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == listed)
        # (appended last at PR 47; what a later PR's cell joined stands behind it)
        assert MELLUM_CELL in metric["workloads"][-2:] and listed in registered, listed
    assert {"global_cache_roofline", "chunk_padding_pct", "dispatch_roofline",
            "device_idle_closed_pct"} <= registered
    # delivered tokens/s judges the cell: six untraced seeds of the final tree spread it 1.1%
    # and the slots bound it (occupancy 93.9-97.0%): PERF.md section 2's rule, ISSUE 47's too
    assert {m.name for m in cell.end_to_end} == {"tpot_p95_ms", "out_tok_s_per_chip", "setup_s"}
    for listed in ("batch_occupancy_pct", "empty_slot_queued_pct", "kv_pages_peak_pct",
                   "hbm_peak_gb", "moe_expert_load_ratio"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == listed)
        # (appended last at PR 47; what a later PR's cell joined stands behind it)
        assert MELLUM_CELL in metric["workloads"][-2:] and listed in registered, listed
    assert not {"ssm_state_roofline", "gdn_state_roofline", "mla_cache_roofline",
                "shortconv_mixer_roofline"} & registered
    assert "kv_pages_given_back_pct" not in {m["name"] for m in MANIFEST["per_layer"]}


# ------------- the stream's road, stage by stage, and the stall no phase names (PR 52)
STREAM_METRICS = {
    "engine_tpot_landed_p95_ms": ("ms", "program_span", "admission and batching"),
    "stream_deliver_wait_p95_ms": ("ms", "program_span", "admission and batching"),
    "loop_stall_pct": ("%", "program_counter", "admission and batching"),
    "phase_long_pct": ("%", "program_counter", "admission and batching"),
    "stream_emit_ms_per_event": ("ms", "program_counter", "node and agent"),
    "stream_backpressure_p95_ms": ("ms", "program_span", "node and agent"),
    "publish_ack_p95_ms": ("ms", "program_span", "client and mesh"),
    "stream_path_p95_ms": ("ms", "program_span", "client and mesh"),
}


def test_the_manifest_holds_the_eight_stream_metrics_appended_in_every_cell():
    """Appended behind everything that was there, with no ``workloads`` key
    (the manifest's spelling of "every cell"), each moving ``tpot_p95_ms``,
    each with its file and a reader; nothing that was there moved."""
    last = MANIFEST["per_layer"][31:39]  # (what later PRs appended stands behind them)
    assert [m["name"] for m in last] == list(STREAM_METRICS)
    for m in last:
        unit, source, layer = STREAM_METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "tpot_p95_ms"}
    assert len(MANIFEST["per_layer"]) >= 39 and len(MANIFEST["workloads"]) >= 8
    for row in MANIFEST["workloads"]:
        cell = M.resolve_cell(MANIFEST, row["name"], M.ROOT)
        registered = {m.name: m for m in cell.per_layer}
        assert set(STREAM_METRICS) <= set(registered), row["name"]
        assert all(callable(registered[name].read) for name in STREAM_METRICS)


@pytest.mark.parametrize("name", sorted(STREAM_METRICS))
def test_a_stream_reader_reads_nothing_from_a_program_without_the_account(name):
    """The driver lays these files over the parent's checkout too: there a
    reader returns None and does not raise, and the line leaves it out."""
    from types import SimpleNamespace

    span = SimpleNamespace(name="engine.decode", trace_id="a", status="ok", start_s=0.0,
                           duration_ms=9e3, span_id="s", parent_span_id="p",
                           attrs={"generated_tokens": 101, "first_seq": 1, "last_seq": 9})
    turn = SimpleNamespace(name="agent.turn", trace_id="a", status="ok", start_s=0.0,
                           duration_ms=9e3, span_id="t", parent_span_id="h",
                           attrs={"generated_tokens": 101})
    sample = SimpleNamespace(correlation_id="a", events=[(1.0, 1), (9.0, 100)])
    parent = SimpleNamespace(
        spans=[span, turn], samples=[sample], trace_reduced={"window_s": 8.0},
        trace_counters={"decode_dispatches": 16, "starved_s": 0.0}, t0=0.0, t_end=51.0,
        counters={"window": {"decode_dispatches": 99, "phase_sync_s": 40.0}}, seconds=51.0)
    assert M.load_reader(name)(parent) is None
