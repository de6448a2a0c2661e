"""Ling-3.0-flash's checkpoint (``bailing_hybrid``): the names and layouts the
loader takes into the Kimi Delta Attention hybrid's tree, whole and as a
share, the tower and the extra prediction layer skipped and counted, the
latent layers' rope columns from interleaved pairs to the tree's halves.

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
from tests.arch_harness import KDA_MLA_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy

WHOLE = replace(TOY, n_routed_experts=16, n_experts_total=0, expert_first=0)


def _pairs(w: np.ndarray, start: int) -> np.ndarray:
    """The last axis from ``start`` on, from the tree's halves back to the
    published interleaved pairs."""
    rope = w[..., start:]
    half = rope.shape[-1] // 2
    rope = np.stack([rope[..., :half], rope[..., half:]], axis=-1).reshape(rope.shape)
    return np.concatenate([w[..., :start], rope], axis=-1)


def _checkpoint(path, config: ModelConfig, tree, extras: bool = True, **raw) -> None:
    """``tree`` (ALL the experts, the whole vocabulary) as a bailing_hybrid
    checkpoint: the names of the loader's module text, the latent layers'
    rope columns in interleaved pairs, a tower and an extra prediction layer
    beside."""
    from safetensors.numpy import save_file

    c = config
    D, H, r, dn, dr, dv = (c.d_model, c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    Hk, dk = c.gdn_n_v_heads * c.gdn_d_k, c.gdn_d_k
    layers = tree["layers"]
    attn, kda, dense, ffn = layers["attn"], layers["gdn"], layers["dense"], layers["moe"]
    out = {"model.word_embeddings.weight": tree["embed"], "model.norm.weight": tree["final_norm"],
           "lm_head.weight": tree["lm_head"].T}
    if extras:
        out.update({"vision.patch_embed.weight": np.zeros((4, 4), np.float32),
                    "vision.blocks.0.attn.qkv.weight": np.zeros((4, 4), np.float32),
                    f"model.layers.{c.n_layers}.eh_proj.weight": np.zeros((4, 4), np.float32)})
    ia = im = 0
    for i, kind in enumerate(c.layer_types):
        at = f"model.layers.{i}."
        if kind == "attention":
            kv_b = np.concatenate([attn["w_uk"][ia], attn["w_uv"][ia]], axis=-1)
            out.update({
                at + "attention.q_proj.weight": _pairs(attn["wq"][ia], dn).reshape(D, -1).T,
                at + "attention.kv_a_proj_with_mqa.weight": _pairs(attn["w_kva"][ia], r).T,
                at + "attention.kv_a_layernorm.weight": attn["kv_norm"][ia],
                at + "attention.kv_b_proj.weight": kv_b.reshape(r, H * (dn + dv)).T,
                at + "attention.g_proj.weight": attn["w_z"][ia].T,
                at + "attention.dense.weight": attn["wo"][ia].reshape(H * dv, D).T,
                at + "input_layernorm.weight": attn["attn_norm"][ia],
            })
            ia += 1
        else:
            w_in, conv = kda["w_in"][im], kda["conv_w"][im].T  # [C + 2 H, D], [C, taps]
            out.update({
                at + "attention.q_proj.weight": w_in[:Hk],
                at + "attention.k_proj.weight": w_in[Hk:2 * Hk],
                at + "attention.v_proj.weight": w_in[2 * Hk:3 * Hk],
                at + "attention.g_proj.weight": w_in[3 * Hk:3 * Hk + c.gdn_n_v_heads],
                at + "attention.b_proj.weight": w_in[3 * Hk + c.gdn_n_v_heads:],
                at + "attention.q_conv1d.weight": conv[:Hk, None, :],
                at + "attention.k_conv1d.weight": conv[Hk:2 * Hk, None, :],
                at + "attention.v_conv1d.weight": conv[2 * Hk:, None, :],
                at + "attention.f_proj.weight": kda["w_alpha"][im],
                at + "attention.A_log": kda["A_log"][im],
                at + "attention.dt_bias": kda["dt_bias"][im].reshape(-1),
                at + "attention.o_norm.weight": kda["norm"][im],
                at + "attention.dense.weight": kda["w_out"][im].T,
                at + "input_layernorm.weight": kda["mixer_norm"][im],
            })
            assert kda["dt_bias"][im].shape == (c.gdn_n_v_heads, dk)
            im += 1
        if i < c.first_k_dense:
            out.update({at + f"mlp.{n}_proj.weight": dense[f"w_{n}"][i].T
                        for n in ("gate", "up", "down")})
            out[at + "post_attention_layernorm.weight"] = dense["mlp_norm"][i]
            continue
        m = i - c.first_k_dense
        out.update({
            at + "mlp.gate.weight": ffn["router"][m].T,
            at + "mlp.gate.expert_bias": ffn["router_bias"][m],
            at + "post_attention_layernorm.weight": ffn["mlp_norm"][m],
            **{at + f"mlp.experts.{e}.{n}_proj.weight": ffn[f"w_{n}"][m, e].T
               for e in range(c.n_routed_experts) for n in ("gate", "up", "down")},
            **{at + f"mlp.shared_experts.{n}_proj.weight": ffn[f"s_{n}"][m].T
               for n in ("gate", "up", "down")},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    text = {
        "model_type": "bailing_hybrid", "vocab_size": c.vocab_size, "hidden_size": D,
        "num_hidden_layers": c.n_layers, "num_attention_heads": H, "num_key_value_heads": H,
        "head_dim": dk, "intermediate_size": c.d_ff, "first_k_dense_replace": c.first_k_dense,
        "moe_intermediate_size": c.moe_d_ff, "moe_shared_expert_intermediate_size": c.moe_d_ff,
        "num_experts": c.n_routed_experts, "num_experts_per_tok": c.n_experts_per_tok,
        "n_group": c.n_group, "topk_group": c.topk_group, "q_lora_rank": None,
        "kv_lora_rank": r, "qk_nope_head_dim": dn, "qk_rope_head_dim": dr, "v_head_dim": dv,
        "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps, "layer_group_size": 3,
        "routed_scaling_factor": c.routed_scaling_factor, "score_function": "sigmoid",
        "moe_router_enable_expert_bias": True, "norm_topk_prob": True, "kda_safe_gate": True,
        "kda_lower_bound": c.kda_lower_bound, "no_kda_lora": True, "linear_silu": True,
        "short_conv_kernel_size": c.gdn_d_conv, "num_kv_heads_for_linear_attn": 0,
        "gated_attention_proj_granularity_type": "head_wise", "max_position_embeddings": 256,
        "expert_swiglu_limit_list": [0] * c.n_layers,
        "share_expert_swiglu_limit_list": [0] * c.n_layers, **raw,
    }
    (path / "config.json").write_text(json.dumps(text))


@pytest.mark.parametrize("share", [None, (0, 4), (3, 4)],
                         ids=["whole", "share-0-of-4", "share-3-of-4"])
def test_a_fabricated_bailing_hybrid_checkpoint_loads_whole_and_as_a_share(tmp_path, share):
    """The names and the interleaved rope columns load into the tree the
    program serves; a share loads its group of experts and its rows of the
    vocabulary, the gate and its bias whole; the tower's tensors and the
    extra prediction layer's are skipped and counted.  The loaded tree serves
    the logits the reference gives for it."""
    from calfkit_tpu.inference.loader import (
        MtpSkipped, VisionTowerSkipped, config_from_hf, load_params,
    )
    from calfkit_tpu.inference.sharding import param_shardings

    tree = jax.tree.map(np.asarray, FAMILY.seeded(WHOLE, key=12))
    _checkpoint(tmp_path, WHOLE, tree)
    config = replace(config_from_hf(tmp_path, share), dtype="float32", gdn_chunk_size=8,
                     kda_sub_block=4)
    rank, of = share or (0, 1)
    assert config == replace(
        WHOLE, name=config.name, vocab_size=128 // of, n_routed_experts=16 // of,
        n_experts_total=16 if of > 1 else 0, expert_first=rank * 16 // of,
        expert_swiglu_limits=(0.0,) * 5, shared_expert_swiglu_limits=(0.0,) * 5)
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, mesh))
    assert [w for w in caught if issubclass(w.category, VisionTowerSkipped)
            and "2 tensors" in str(w.message)]
    assert [w for w in caught if issubclass(w.category, MtpSkipped) and "1 tensors" in str(w.message)]
    rows = slice(rank * 128 // of, (rank + 1) * 128 // of)
    held = slice(config.expert_first, config.expert_first + config.n_routed_experts)
    want = {**tree, "embed": tree["embed"][rows], "lm_head": tree["lm_head"][:, rows],
            "layers": {**tree["layers"], "moe": {
                **tree["layers"]["moe"],
                **{n: tree["layers"]["moe"][n][:, held] for n in ("w_gate", "w_up", "w_down")}}}}
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(got), expected), path
    tokens = np.random.default_rng(1).integers(3, 128 // of, (1, 40)).astype(np.int32)
    logits = FAMILY.forward(loaded, config, tokens)[0]
    reference = ARCH.forward_logits(loaded, config, tokens, np.asarray([40], np.int32))
    assert np.abs(np.asarray(logits) - reference).max() < LOGIT_TOL


def test_what_the_program_does_not_describe_is_refused_at_the_config(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    _checkpoint(tmp_path, WHOLE, jax.tree.map(np.asarray, FAMILY.seeded(WHOLE, key=1)), extras=False)
    raw = json.loads((tmp_path / "config.json").read_text())
    for key, value in (("kda_safe_gate", False), ("no_kda_lora", False), ("q_lora_rank", 64),
                       ("score_function", "softmax"), ("use_mla_nope", True),
                       ("gated_attention_proj_granularity_type", "element_wise"),
                       ("num_kv_heads_for_linear_attn", 2)):
        (tmp_path / "config.json").write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=key):
            config_from_hf(tmp_path)
    # a HELD layer's nonzero swiglu limit: refused by name, with its reason
    (tmp_path / "config.json").write_text(json.dumps(
        {**raw, "expert_swiglu_limit_list": [0] * 5 + [4]}))
    with pytest.raises(ValueError, match="expert_swiglu_limits.*clamp's form"):
        config_from_hf(tmp_path)
    (tmp_path / "config.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="does not divide"):
        config_from_hf(tmp_path, (0, 3))
    assert config_from_hf(tmp_path).layer_types == TOY.layer_types
