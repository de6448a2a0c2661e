"""LFM2-8B-A1B's checkpoint (``lfm2_moe``): the names and layouts the loader
takes into the short-convolution hybrid's tree, whole and as a pipeline's
first stage (the later layers left on disk, counted and said); and the new
cell's rehearsal, the benchmark's one command end to end on the CPU.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import warnings
from dataclasses import replace

import jax
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference.config import ATTENTION, CONV, ModelConfig
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import LFM2_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

# the published pattern at toy widths: 24 layers, the attention layers where
# the published file has them
WHOLE = replace(TOY, n_layers=24, layer_types=tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24)))


def _checkpoint(path, config: ModelConfig, tree, **raw) -> None:
    """``tree`` as an lfm2_moe checkpoint: the names of the loader's module
    text, the depthwise conv as ``[D, 1, taps]``, the tied embedding once."""
    from safetensors.numpy import save_file

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    layers = tree["layers"]
    attn, conv, dense, ffn = layers["attn"], layers["conv"], layers["dense"], layers["moe"]
    out = {"model.embed_tokens.weight": tree["embed"],
           "model.embedding_norm.weight": tree["final_norm"]}
    ia = im = 0
    for i, kind in enumerate(c.layer_types):
        at = f"model.layers.{i}."
        if kind == ATTENTION:
            out.update({
                at + "self_attn.q_proj.weight": attn["wq"][ia].reshape(D, H * hd).T,
                at + "self_attn.k_proj.weight": attn["wk"][ia].reshape(D, K * hd).T,
                at + "self_attn.v_proj.weight": attn["wv"][ia].reshape(D, K * hd).T,
                at + "self_attn.out_proj.weight": attn["wo"][ia].reshape(H * hd, D).T,
                at + "self_attn.q_layernorm.weight": attn["q_norm"][ia],
                at + "self_attn.k_layernorm.weight": attn["k_norm"][ia],
                at + "operator_norm.weight": attn["attn_norm"][ia],
            })
            ia += 1
        else:
            out.update({
                at + "conv.in_proj.weight": conv["w_in"][im],
                at + "conv.conv.weight": conv["conv_w"][im].T[:, None, :],
                at + "conv.out_proj.weight": conv["w_out"][im].T,
                at + "operator_norm.weight": conv["mixer_norm"][im],
            })
            im += 1
        if i < c.first_k_dense:
            out.update({at + f"feed_forward.{hf}.weight": dense[ours][i].T
                        for hf, ours in (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down"))})
            out[at + "ffn_norm.weight"] = dense["mlp_norm"][i]
            continue
        m = i - c.first_k_dense
        out.update({
            at + "feed_forward.gate.weight": ffn["router"][m].T,
            at + "feed_forward.expert_bias": ffn["router_bias"][m],
            at + "ffn_norm.weight": ffn["mlp_norm"][m],
            **{at + f"feed_forward.experts.{e}.{hf}.weight": ffn[ours][m, e].T
               for e in range(c.n_routed_experts)
               for hf, ours in (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down"))},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    names = {CONV: "conv", ATTENTION: "full_attention"}
    text = {
        "model_type": "lfm2_moe", "vocab_size": c.vocab_size, "hidden_size": D,
        "num_hidden_layers": c.n_layers, "num_attention_heads": H, "num_key_value_heads": K,
        "intermediate_size": c.d_ff, "moe_intermediate_size": c.moe_d_ff,
        "num_dense_layers": c.first_k_dense, "num_experts": c.n_routed_experts,
        "num_experts_per_tok": c.n_experts_per_tok, "conv_L_cache": c.conv_L_cache,
        "conv_bias": False, "layer_types": [names[t] for t in c.layer_types],
        "rope_theta": c.rope_theta, "norm_eps": c.norm_eps, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1, "max_position_embeddings": 256,
        **raw,
    }
    (path / "config.json").write_text(json.dumps(text))


@pytest.mark.parametrize("stage", [None, 12], ids=["whole", "the-first-12-layers"])
def test_a_fabricated_lfm2_moe_checkpoint_loads_whole_and_as_the_first_stage(tmp_path, stage):
    """The names load into the tree the program serves; a description of the
    leading 12 of the checkpoint's 24 layers loads those, leaves the other 12
    on disk and says how many; the loaded tree serves the logits the
    reference gives for it."""
    from calfkit_tpu.inference.loader import LayersSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    tree = jax.tree.map(np.asarray, FAMILY.seeded(WHOLE, key=12))
    _checkpoint(tmp_path, WHOLE, tree)
    config = replace(config_from_hf(tmp_path), dtype="float32")
    assert config == replace(WHOLE, name=config.name)
    want = tree
    if stage:
        config = replace(config, n_layers=stage, layer_types=config.layer_types[:stage])
        assert config == replace(TOY, name=config.name)
        want = {**tree, "layers": {
            "attn": jax.tree.map(lambda a: a[:3], tree["layers"]["attn"]),
            "conv": jax.tree.map(lambda a: a[:9], tree["layers"]["conv"]),
            "dense": tree["layers"]["dense"],
            "moe": jax.tree.map(lambda a: a[:10], tree["layers"]["moe"])}}
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, mesh))
    said = [str(w.message) for w in caught if issubclass(w.category, LayersSkipped)]
    assert len(said) == (1 if stage else 0)
    if stage:
        assert "layers 12-23 (12 of 24) were not loaded" in said[0]
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(got), expected), path
    tokens = np.random.default_rng(1).integers(3, 128, (1, 40)).astype(np.int32)
    logits = FAMILY.forward(loaded, config, tokens)[0]
    reference = ARCH.forward_logits(loaded, config, tokens, np.asarray([40], np.int32))
    assert np.abs(np.asarray(logits) - reference).max() < LOGIT_TOL


def test_what_the_program_does_not_describe_is_refused_at_the_config(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    _checkpoint(tmp_path, TOY, jax.tree.map(np.asarray, FAMILY.seeded(key=1)))
    raw = json.loads((tmp_path / "config.json").read_text())
    for key, value in (("use_expert_bias", False), ("conv_bias", True), ("tie_embedding", False),
                       ("rope_scaling", {"type": "yarn"})):
        (tmp_path / "config.json").write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=key):
            config_from_hf(tmp_path)
    (tmp_path / "config.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="a share is described"):
        config_from_hf(tmp_path, (0, 2))


def test_the_configuration_file_names_the_tensors_as_unverified_and_states_the_cut():
    with open(os.path.join(os.path.dirname(manifest.__file__), "configs", "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    names = next(a for a in config["assumed"] if "checkpoint tensor names" in a)
    for name in ("model.embed_tokens", "model.embedding_norm", "operator_norm", "ffn_norm",
                 "conv.{in_proj,conv,out_proj}", "q_layernorm", "expert_bias", "UNVERIFIED"):
        assert name in names, name
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 24} and config["num_hidden_layers"] == 12
    assert config["published_layers"] == list(range(12))
    assert (config["num_experts"], config["vocab_size"]) == (32, 65536)
    for key in ("deployment", "precision", "hbm", "agreement", "rehearsal", "worker"):
        assert key in config, key


def test_the_new_cell_s_rehearsal_ends_on_the_cpu():
    """``benchmarks/run.py --workload lfm2-8b-a1b.longform-closed --rehearse``:
    the benchmark's one command end to end on the CPU at the file's toy
    widths (engine, broker, worker, agreement, warm-up, ramp-in, window,
    drain); its last line names ``platform: cpu`` and carries no metric."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "benchmarks", "run.py"), "--workload",
         "lfm2-8b-a1b.longform-closed", "--seed", "2147483777", "--seconds", "4", "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=manifest.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    last = lines[-1]
    assert last["platform"] == "cpu" and last["rehearsal"] is True and "metrics" not in last
    assert last["attempted"] >= 1 and last["failed"] == 0
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["compiles_in_window"] == 0 and window["planned_equals_realised"]
    reference = next(l for l in lines if l.get("phase") == "reference")
    assert len(reference["tail_error_by_layer"]) == 9 and reference["over_their_limit"] == []
