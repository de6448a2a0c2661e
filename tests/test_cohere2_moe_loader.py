"""command-a-plus's checkpoint (``cohere2_moe``): HF names and layouts into
the window stack's tree, whole and as a share, the vision tower skipped and
counted, the q and k columns from interleaved pairs to the tree's halves.

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

from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import ModelConfig
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import WINDOW_MOE as FAMILY
from tests.arch_harness import both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


def _interleaved(w: np.ndarray) -> np.ndarray:
    """A head's columns from the tree's halves back to the published pairs."""
    half = w.shape[-1] // 2
    return np.stack([w[..., :half], w[..., half:]], axis=-1).reshape(w.shape)


def _checkpoint(path, config: ModelConfig, tree, tower: bool = True) -> None:
    """``tree`` (ALL the experts, the whole vocabulary) as a cohere2_moe
    checkpoint: HF's names and layouts, q and k in interleaved pairs, one
    module a shared expert, tied head, a vision tower beside."""
    from safetensors.numpy import save_file

    c = config
    D, H, K, hd, Fe = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim, c.moe_d_ff
    pre = "language_model." if tower else ""
    out = {pre + "model.embed_tokens.weight": tree["embed"],
           pre + "model.norm.weight": tree["final_norm"]}
    if tower:
        out.update({"vision_tower.patch_embed.weight": np.zeros((4, 4), np.float32),
                    "vision_tower.blocks.0.attn.qkv.weight": np.zeros((4, 4), np.float32),
                    "multi_modal_projector.linear.weight": np.zeros((4, 4), np.float32)})
    attn, ffn = tree["layers"]["attn"], tree["layers"]["moe"]
    for i in range(c.n_layers):
        at = pre + f"model.layers.{i}."
        out.update({
            at + "self_attn.q_proj.weight": _interleaved(attn["wq"][i]).reshape(D, H * hd).T,
            at + "self_attn.k_proj.weight": _interleaved(attn["wk"][i]).reshape(D, K * hd).T,
            at + "self_attn.v_proj.weight": attn["wv"][i].reshape(D, K * hd).T,
            at + "self_attn.o_proj.weight": attn["wo"][i].reshape(H * hd, D).T,
            at + "input_layernorm.weight": attn["attn_norm"][i],
            at + "mlp.gate.weight": ffn["router"][i].T,
            **{at + f"mlp.experts.{e}.{n}_proj.weight": ffn[f"w_{n}"][i, e].T
               for e in range(c.n_routed_experts) for n in ("gate", "up", "down")},
            **{at + f"mlp.shared_experts.{j}.{n}_proj.weight":
               ffn[f"s_{n}"][i][:, j * Fe:(j + 1) * Fe].T for j in range(c.n_shared_experts)
               for n in ("gate", "up")},
            **{at + f"mlp.shared_experts.{j}.down_proj.weight":
               ffn["s_down"][i][j * Fe:(j + 1) * Fe].T for j in range(c.n_shared_experts)},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    text = {
        "model_type": "cohere2_moe", "vocab_size": c.vocab_size, "hidden_size": D,
        "num_hidden_layers": c.n_layers, "num_attention_heads": H, "num_key_value_heads": K,
        "head_dim": hd, "intermediate_size": Fe, "layer_switch": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"], "sliding_window": c.sliding_window, "layer_norm_eps": c.norm_eps,
        "rms_norm_eps": None, "rope_theta": c.rope_theta, "rotary_pct": 1,
        "position_embedding_type": "rope_gptj", "num_experts": c.n_routed_experts,
        "num_experts_per_tok": c.n_experts_per_tok, "num_shared_experts": c.n_shared_experts,
        "expert_selection_fn": "sigmoid", "norm_topk_prob": True, "first_k_dense_replace": 0,
        "shared_expert_combination_strategy": "average", "use_parallel_block": True,
        "use_qk_norm": False, "logit_scale": 1, "tie_word_embeddings": True,
        "max_position_embeddings": 256,
    }
    (path / "config.json").write_text(json.dumps(text))


@pytest.mark.parametrize("share", [None, (0, 2), (1, 2)], ids=["whole", "share-0-of-2", "share-1-of-2"])
def test_a_fabricated_cohere2_moe_checkpoint_loads_whole_and_as_a_share(tmp_path, share):
    """HF's names and the interleaved q and k columns load into the tree the
    program serves; a share loads its experts and its rows of the tied
    vocabulary, the gate whole; the tower's tensors are skipped and counted.
    The loaded tree serves the logits the reference gives for it."""
    from calfkit_tpu.inference.loader import VisionTowerSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    whole = replace(TOY, n_routed_experts=8, n_experts_total=0, expert_first=0)
    tree = jax.tree.map(np.asarray, FAMILY.seeded(whole, key=12))
    _checkpoint(tmp_path, whole, tree)
    config = replace(config_from_hf(tmp_path, share), dtype="float32")
    rank, of = share or (0, 1)
    assert (config.n_routed_experts, config.experts_scored, config.expert_first,
            config.vocab_size) == (8 // of, 8, rank * 8 // of, 128 // of)
    assert config == replace(
        whole, name=config.name, vocab_size=128 // of, n_routed_experts=8 // of,
        n_experts_total=8 if of > 1 else 0, expert_first=rank * 8 // of)
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, mesh))
    assert [w for w in caught if issubclass(w.category, VisionTowerSkipped)
            and "3 tensors" in str(w.message)]
    rows = slice(rank * 128 // of, (rank + 1) * 128 // of)
    held = slice(config.expert_first, config.expert_first + config.n_routed_experts)
    want = {**tree, "embed": tree["embed"][rows],
            "layers": {**tree["layers"], "moe": {
                **tree["layers"]["moe"],
                **{n: tree["layers"]["moe"][n][:, held] for n in ("w_gate", "w_up", "w_down")}}}}
    assert jax.tree.structure(loaded) == jax.tree.structure(want) and "lm_head" not in loaded
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(got), expected), path
    tokens = np.random.default_rng(1).integers(3, 128 // of, (1, 48)).astype(np.int32)
    logits = FAMILY.forward(loaded, config, tokens)[0]
    reference = ARCH.forward_logits(loaded, config, tokens, np.asarray([48], np.int32))
    assert np.abs(np.asarray(logits) - reference).max() < LOGIT_TOL


def test_the_loader_s_permutation_is_the_one_the_two_rotations_differ_by():
    """The checkpoint's columns are interleaved pairs and HF rotates pairs
    ``(2i, 2i+1)``; the tree's are halves and ``model.apply_rope`` rotates
    ``(i, i + hd/2)``: the same rotation, so the same scores."""
    rng = np.random.default_rng(0)
    hf_q, hf_k = (rng.normal(size=(6, 2, 8)).astype(np.float32) for _ in range(2))  # [S, heads, hd]
    pos = jnp.arange(6)
    halves = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    cos, sin = M.rope_tables(pos[None], *M.rope_frequencies(8, 50000.0))
    tree = [np.asarray(M.apply_rope(jnp.asarray(x[None][..., halves]), cos, sin))[0]
            for x in (hf_q, hf_k)]
    published = [np.asarray(ARCH._rotate_pairs(jnp.asarray(x), pos, 50000.0)) for x in (hf_q, hf_k)]
    assert np.allclose(np.einsum("snh,tnh->nst", *tree), np.einsum("snh,tnh->nst", *published),
                       atol=1e-5)
    assert np.allclose(np.asarray(ARCH._published_order(jnp.asarray(hf_q[..., halves]))), hf_q)


def test_what_the_program_does_not_describe_is_refused_at_the_config(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    whole = replace(TOY, n_routed_experts=8, n_experts_total=0, expert_first=0)
    _checkpoint(tmp_path, whole, jax.tree.map(np.asarray, FAMILY.seeded(whole, key=1)), tower=False)
    raw = json.loads((tmp_path / "config.json").read_text())
    for key, value in (("use_parallel_block", False), ("use_qk_norm", True),
                       ("first_k_dense_replace", 1), ("expert_selection_fn", "softmax"),
                       ("shared_expert_combination_strategy", "sum")):
        (tmp_path / "config.json").write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=key):
            config_from_hf(tmp_path)
    (tmp_path / "config.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="does not divide"):
        config_from_hf(tmp_path, (0, 3))
    assert config_from_hf(tmp_path).layer_types == TOY.layer_types
