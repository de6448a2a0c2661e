"""Mellum 2's checkpoint (``mellum``): the names the loader ASSUMES (they are
unverified against the published files) into the window stack's sequential
tree, whole and as a share; the multi-token-prediction head skipped and
counted, a pipeline's later layers skipped and counted, q/k-norm tensors
REFUSED; what the description does not hold refused at the config.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import json
import warnings
from dataclasses import replace

import jax
import numpy as np
import pytest

from calfkit_tpu.inference.config import ModelConfig
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import MELLUM_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


def _checkpoint(path, config: ModelConfig, tree, extra: dict | None = None) -> None:
    """``tree`` as a mellum checkpoint: the assumed names and HF's layouts
    (``[out, in]`` matrices), an untied head, two norms a layer."""
    from safetensors.numpy import save_file

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    out = {"model.embed_tokens.weight": tree["embed"], "model.norm.weight": tree["final_norm"],
           "lm_head.weight": tree["lm_head"].T, **(extra or {})}
    attn, ffn = tree["layers"]["attn"], tree["layers"]["moe"]
    for i in range(c.n_layers):
        at = f"model.layers.{i}."
        out.update({
            at + "self_attn.q_proj.weight": attn["wq"][i].reshape(D, H * hd).T,
            at + "self_attn.k_proj.weight": attn["wk"][i].reshape(D, K * hd).T,
            at + "self_attn.v_proj.weight": attn["wv"][i].reshape(D, K * hd).T,
            at + "self_attn.o_proj.weight": attn["wo"][i].reshape(H * hd, D).T,
            at + "input_layernorm.weight": attn["attn_norm"][i],
            at + "post_attention_layernorm.weight": ffn["mlp_norm"][i],
            at + "mlp.gate.weight": ffn["router"][i].T,
            **{at + f"mlp.experts.{e}.{n}_proj.weight": ffn[f"w_{n}"][i, e].T
               for e in range(c.n_routed_experts) for n in ("gate", "up", "down")},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    s = c.rope_scaling_global
    text = {
        "model_type": "mellum", "vocab_size": c.vocab_size, "hidden_size": D,
        "num_hidden_layers": c.n_layers, "num_attention_heads": H, "num_key_value_heads": K,
        "head_dim": hd, "intermediate_size": 4 * D, "moe_intermediate_size": c.moe_d_ff,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "mlp_layer_types": ["sparse"] * c.n_layers,
        "sliding_window": c.sliding_window, "rms_norm_eps": c.norm_eps,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": c.rope_theta, "factor": s.factor,
                "original_max_position_embeddings": s.original_max_position_embeddings,
                "beta_fast": s.beta_fast, "beta_slow": s.beta_slow},
            "sliding_attention": {"rope_type": "default", "rope_theta": c.rope_theta}},
        "num_experts": c.n_routed_experts, "num_experts_per_tok": c.n_experts_per_tok,
        "norm_topk_prob": True, "attention_bias": False, "hidden_act": "silu",
        "tie_word_embeddings": False, "use_sliding_window": True, "max_window_layers": 0,
        "max_position_embeddings": 256,
    }
    (path / "config.json").write_text(json.dumps(text))


@pytest.mark.parametrize("share", [None, (0, 2), (1, 2)], ids=["whole", "share-0-of-2", "share-1-of-2"])
def test_a_fabricated_mellum_checkpoint_loads_whole_and_as_a_share(tmp_path, share):
    """The assumed names load into the tree the program serves, no column
    permuted; a share loads its experts, its rows of the embedding and its
    columns of the head, the gate whole; the MTP head's tensors are skipped
    and counted.  The loaded tree serves the logits the reference gives."""
    from calfkit_tpu.inference.loader import MtpSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=12))
    _checkpoint(tmp_path, TOY, tree, extra={
        "mtp.layers.0.self_attn.q_proj.weight": np.zeros((4, 4), np.float32),
        "mtp.norm.weight": np.zeros((4,), np.float32),
        "model.mtp_head.proj.weight": np.zeros((4, 4), np.float32)})
    config = replace(config_from_hf(tmp_path, share), dtype="float32")
    rank, of = share or (0, 1)
    assert config == replace(
        TOY, name=config.name, vocab_size=128 // of, n_routed_experts=8 // of,
        n_experts_total=8 if of > 1 else 0, expert_first=rank * 8 // of)
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, mesh))
    assert [w for w in caught if issubclass(w.category, MtpSkipped)
            and "3 tensors" in str(w.message)]
    rows = slice(rank * 128 // of, (rank + 1) * 128 // of)
    held = slice(config.expert_first, config.expert_first + config.n_routed_experts)
    want = {**tree, "embed": tree["embed"][rows], "lm_head": tree["lm_head"][:, rows],
            "layers": {**tree["layers"], "moe": {
                **tree["layers"]["moe"],
                **{n: tree["layers"]["moe"][n][:, held] for n in ("w_gate", "w_up", "w_down")}}}}
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(got), expected), path
    tokens = np.random.default_rng(1).integers(3, 128 // of, (1, 96)).astype(np.int32)
    logits = FAMILY.forward(loaded, config, tokens)[0]
    reference = ARCH.forward_logits(loaded, config, tokens, np.asarray([96], np.int32))
    assert np.abs(np.asarray(logits) - reference).max() < LOGIT_TOL


def test_a_pipeline_stage_loads_its_leading_layers_and_counts_the_rest(tmp_path):
    from calfkit_tpu.inference.loader import LayersSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=3))
    _checkpoint(tmp_path, TOY, tree)
    stage = replace(config_from_hf(tmp_path), dtype="float32", n_layers=4,
                    layer_types=TOY.layer_types[:4])
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, stage, param_shardings(stage, mesh))
    said = [str(w.message) for w in caught if issubclass(w.category, LayersSkipped)]
    assert len(said) == 1 and "layers 4-7 (4 of 8)" in said[0]
    assert loaded["layers"]["attn"]["wq"].shape[0] == 4
    assert np.array_equal(np.asarray(loaded["layers"]["moe"]["mlp_norm"]),
                          tree["layers"]["moe"]["mlp_norm"][:4])


@pytest.mark.parametrize("name", ["q_norm", "k_norm"])
def test_a_checkpoint_that_holds_a_head_norm_is_refused_not_skipped(tmp_path, name):
    from calfkit_tpu.inference.loader import config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=3))
    _checkpoint(tmp_path, TOY, tree, extra={
        f"model.layers.5.self_attn.{name}.weight": np.ones((TOY.head_dim,), np.float32)})
    config = replace(config_from_hf(tmp_path), dtype="float32")
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="q/k-norm tensors.*refused, not skipped"):
        load_params(tmp_path, config, param_shardings(config, mesh))


def test_what_the_program_does_not_describe_is_refused_at_the_config(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    _checkpoint(tmp_path, TOY, jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=1)))
    raw = json.loads((tmp_path / "config.json").read_text())
    rope = raw["rope_parameters"]
    for key, value in (("attention_bias", True), ("tie_word_embeddings", True),
                       ("use_sliding_window", False), ("max_window_layers", 4),
                       ("hidden_act", "gelu")):
        (tmp_path / "config.json").write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=key):
            config_from_hf(tmp_path)
    for change, why in (
            ({"mlp_layer_types": ["dense"] + ["sparse"] * 7}, "not sparse"),
            ({"rope_parameters": {**rope, "full_attention": {
                **rope["full_attention"], "rope_type": "llama3"}}}, "rope_type 'llama3'"),
            ({"rope_parameters": {**rope, "sliding_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0}}}, "sliding layers"),
            ({"rope_parameters": {**rope, "sliding_attention": {
                "rope_type": "default", "rope_theta": 500.0}}}, "ONE rope_theta")):
        (tmp_path / "config.json").write_text(json.dumps({**raw, **change}))
        with pytest.raises(ValueError, match=why):
            config_from_hf(tmp_path)
    (tmp_path / "config.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="does not divide"):
        config_from_hf(tmp_path, (0, 3))
    assert config_from_hf(tmp_path).layer_types == TOY.layer_types
