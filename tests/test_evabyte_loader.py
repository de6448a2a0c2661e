"""EvaByte's checkpoint (``evabyte``): the names the loader ASSUMES (they are
unverified against the published files) into the EVA stack's tree: the two
learned vectors a head out of their ``[1, H, 1, 1, hd]``, the head's 2 x 64
rows kept in their order; a pipeline's later layers skipped and counted; what
the description does not hold refused at the config.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import json
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from calfkit_tpu.inference import eva
from calfkit_tpu.inference.config import ModelConfig
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import EVABYTE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


def _checkpoint(path, config: ModelConfig, tree, extra: dict | None = None) -> None:
    """``tree`` as an evabyte checkpoint: the assumed names and HF's layouts
    (``[out, in]`` matrices), a head of P x V rows, phi and mu as published."""
    from safetensors.numpy import save_file

    c = config
    D, H, hd = c.d_model, c.n_heads, c.head_dim
    out = {"model.embed_tokens.weight": tree["embed"], "model.norm.weight": tree["final_norm"],
           "lm_head.weight": tree["lm_head"].T, **(extra or {})}
    layers = tree["layers"]
    for i in range(c.n_layers):
        at = f"model.layers.{i}."
        out.update({
            **{at + f"self_attn.{n}_proj.weight": layers[f"w{n}"][i].T
               for n in ("q", "k", "v", "o")},
            at + "self_attn.adaptive_phi": layers["phi"][i].reshape(1, H, 1, 1, hd),
            at + "self_attn.adaptive_mu_k": layers["mu"][i].reshape(1, H, 1, 1, hd),
            at + "input_layernorm.weight": layers["attn_norm"][i],
            at + "post_attention_layernorm.weight": layers["mlp_norm"][i],
            **{at + f"mlp.{n}_proj.weight": layers[f"w_{n}"][i].T for n in ("gate", "up", "down")},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps({
        "model_type": "evabyte", "attention_class": "eva", "vocab_size": c.vocab_size,
        "hidden_size": D, "num_hidden_layers": c.n_layers, "num_attention_heads": H,
        "num_key_value_heads": H, "intermediate_size": c.d_ff, "window_size": c.window_size,
        "chunk_size": c.chunk_size, "num_pred_heads": c.num_pred_heads, "num_chunks": None,
        "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta, "rope_scaling": None,
        "norm_add_unit_offset": True, "fp32_skip_add": True, "fp32_logits": True,
        "mixedp_attn": True, "attention_bias": False, "hidden_act": "silu",
        "tie_word_embeddings": False, "max_position_embeddings": 256,
    }))


def _load(path, config):
    from calfkit_tpu.inference.loader import load_params
    from calfkit_tpu.inference.sharding import param_shardings

    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    return load_params(path, config, param_shardings(config, mesh))


def test_a_fabricated_evabyte_checkpoint_loads_and_serves_the_reference_s_logits(tmp_path):
    """The assumed names load into the tree the program serves, leaf for
    leaf; the loaded tree's forward of three windows gives the reference's
    logits at BOTH prediction heads."""
    from calfkit_tpu.inference.loader import config_from_hf

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=12))
    _checkpoint(tmp_path, TOY, tree)
    config = replace(config_from_hf(tmp_path), dtype="float32")
    assert config == replace(TOY, name=config.name)
    loaded = _load(tmp_path, config)
    assert jax.tree.structure(loaded) == jax.tree.structure(tree)
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(tree)):
        assert np.array_equal(np.asarray(got), expected), path
    W = TOY.window_size
    tokens = np.random.default_rng(1).integers(3, TOY.vocab_size, (1, 3 * W)).astype(np.int32)
    scratch, got = eva.make_scratch(config, 1, 3 * W, jnp.float32), []
    for at in range(0, 3 * W, W):
        pos = at + jnp.arange(W, dtype=jnp.int32)[None, :]
        logits, scratch = eva.eva_forward(
            loaded, config, jnp.asarray(tokens[:, at:at + W]), pos, scratch, heads=2)
        got.append(np.asarray(logits))
    want = ARCH.forward_heads(loaded, config, tokens, np.asarray([3 * W], np.int32))
    assert np.abs(np.concatenate(got, axis=1).reshape(want.shape) - want).max() < LOGIT_TOL


def test_a_pipeline_stage_loads_its_leading_layers_and_counts_the_rest(tmp_path):
    from calfkit_tpu.inference.loader import LayersSkipped, config_from_hf

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=3))
    _checkpoint(tmp_path, TOY, tree)
    stage = replace(config_from_hf(tmp_path), dtype="float32", n_layers=2,
                    layer_types=TOY.layer_types[:2])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = _load(tmp_path, stage)
    said = [str(w.message) for w in caught if issubclass(w.category, LayersSkipped)]
    assert len(said) == 1 and "layers 2-2 (1 of 3)" in said[0]
    assert loaded["layers"]["wq"].shape[0] == 2
    assert np.array_equal(np.asarray(loaded["layers"]["phi"]), tree["layers"]["phi"][:2])
    assert loaded["lm_head"].shape == (TOY.d_model, 2 * TOY.vocab_size)


def test_what_the_program_does_not_describe_is_refused(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    tree = jax.tree.map(np.asarray, FAMILY.seeded(TOY, key=1))
    _checkpoint(tmp_path, TOY, tree)
    raw = json.loads((tmp_path / "config.json").read_text())
    for key, value in (("attention_class", "softmax"), ("attention_bias", True),
                       ("tie_word_embeddings", True), ("hidden_act", "gelu"),
                       ("norm_add_unit_offset", False), ("fp32_skip_add", False),
                       ("rope_scaling", {"type": "linear"}), ("num_chunks", 4)):
        (tmp_path / "config.json").write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=key):
            config_from_hf(tmp_path)
    (tmp_path / "config.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="a share is described"):
        config_from_hf(tmp_path, (0, 2))
    # a head of other rows than num_pred_heads x vocab_size is another model's
    one_head = replace(config_from_hf(tmp_path), dtype="float32", num_pred_heads=1)
    with pytest.raises(ValueError, match="num_pred_heads x vocab_size = 1 x 64"):
        _load(tmp_path, one_head)
