"""HF checkpoint → sharded JAX params.

Loads a *local* Llama-family HF directory (config.json + safetensors) into
the stacked-layer pytree of :mod:`calfkit_tpu.inference.model`, placing each
tensor straight onto its NamedSharding so no host copy of the full model
lingers (model-side "checkpointing is loading", SURVEY.md §5).

Weight name mapping (HF → ours):
    model.embed_tokens.weight                     → embed [V, D]
    model.layers.{i}.self_attn.{q,k,v}_proj.weight→ wq/wk/wv (transposed,
                                                    reshaped to [D, N, hd])
    model.layers.{i}.self_attn.o_proj.weight      → wo [H, hd, D]
    model.layers.{i}.mlp.{gate,up,down}_proj.weight → w_gate/w_up/w_down
    model.layers.{i}.{input,post_attention}_layernorm.weight → norms
    model.norm.weight                              → final_norm
    lm_head.weight                                 → lm_head [D, V]

``model_type`` ``granitemoehybrid`` (no routed experts) loads into the
hybrid tree of :mod:`calfkit_tpu.inference.model` (layer N is the n-th
attention or Mamba layer by ``layer_types``):
    model.layers.{N}.self_attn.{q,k,v,o}_proj.weight → layers.attn.wq/wk/wv/wo
    model.layers.{N}.mamba.in_proj.weight [z|xBC|dt, D] → layers.mamba.w_in (as it is)
    model.layers.{N}.mamba.conv1d.weight [C, 1, d_conv] → conv_w [d_conv, C]; .bias → conv_b
    model.layers.{N}.mamba.{A_log,D,dt_bias}          → float32 leaves
    model.layers.{N}.mamba.norm.weight, out_proj.weight → norm, w_out (transposed)
    model.layers.{N}.shared_mlp.input_linear.weight [2F, D], gate first
                                                      → layers.mlp.w_gate, w_up (split, transposed)
    model.layers.{N}.shared_mlp.output_linear.weight  → layers.mlp.w_down
    model.layers.{N}.input_layernorm / post_attention_layernorm
                                → attn_norm or mixer_norm / layers.mlp.mlp_norm

``model_type`` ``deepseek_v3``, and ``kimi_vl`` (its ``text_config``; tensor
names under ``language_model.``, the vision tower's tensors skipped and
counted with one :class:`VisionTowerSkipped` notice), load into the
latent-attention tree (``q_lora_rank`` null):
    self_attn.q_proj.weight [H (dn + dr), D]      → layers.attn.wq [D, H, dn + dr]
    self_attn.kv_a_proj_with_mqa.weight [r + dr, D] → w_kva [D, r + dr]
    self_attn.kv_a_layernorm.weight               → kv_norm
    self_attn.kv_b_proj.weight [H (dn + dv), r]   → w_uk [r, H, dn] | w_uv [r, H, dv]
    self_attn.o_proj.weight                       → wo [H, dv, D]
    mlp.{gate,up,down}_proj.weight (leading dense layers) → layers.dense.*
    mlp.gate.weight [E, D], .e_score_correction_bias → layers.moe.router [D, E], router_bias
    mlp.experts.{e}.{gate,up,down}_proj.weight    → layers.moe.w_gate/w_up/w_down [E, ..]
    mlp.shared_experts.{gate,up,down}_proj.weight → layers.moe.s_gate/s_up/s_down
``model_type`` ``qwen3_next`` loads into the Gated DeltaNet hybrid's tree
(layer N is the n-th attention or DeltaNet layer by ``full_attention_interval``;
``mtp.*``, the multi-token-prediction module, skipped and counted with one
:class:`MtpSkipped` notice):
    linear_attn.in_proj_qkvz.weight, in_proj_ba.weight → layers.gdn.w_in, ONE
        matrix with rows q | k | v | z | b | a (HF interleaves them per KEY
        head: q, k, its value heads' v, their z; b and a likewise)
    linear_attn.conv1d.weight [C, 1, d_conv]      → conv_w [d_conv, C]
    linear_attn.{A_log,dt_bias}                   → float32 leaves
    linear_attn.norm.weight, out_proj.weight      → norm, w_out (transposed)
    self_attn.q_proj.weight [H 2 hd, D]           → layers.attn.wq [D, H, 2 hd] (q | gate a head)
    self_attn.{k,v,o}_proj, {q,k}_norm.weight     → wk, wv, wo, q_norm, k_norm
    mlp.gate.weight [E, D]                        → layers.moe.router [D, E] (ALL the experts)
    mlp.experts.{e}.{gate,up,down}_proj.weight    → layers.moe.w_gate/w_up/w_down [held, ..]
    mlp.shared_expert.{gate,up,down}_proj.weight, shared_expert_gate.weight
                                                  → s_gate/s_up/s_down, shared_gate [D]
``model_type`` ``cohere2_moe`` (command-a-plus; its text decoder, tensor
names under ``language_model.`` where the checkpoint has a vision tower,
whose tensors are skipped and counted with one :class:`VisionTowerSkipped`
notice) loads into the window stack's tree (ONE norm a layer: the parallel
block).  The expert block's names are taken as the other expert checkpoints
here have them (``mlp.gate``, ``mlp.experts.{e}``), the shared experts' as
``mlp.shared_experts.{j}``, one module an expert:
    self_attn.{q,k}_proj.weight [N hd, D]         → layers.attn.wq, wk [D, N, hd], each head's
        columns from the published interleaved pairs (x_0, y_0, x_1, y_1, ..) to
        halves (x_0 .. | y_0 ..): ``model.apply_rope`` pairs the two halves, so the
        same rotation gives the same scores (a permutation: changes no product)
    self_attn.{v,o}_proj.weight                   → wv, wo
    input_layernorm.weight                        → attn_norm (a weight, no bias)
    mlp.gate.weight [E, D]                        → layers.moe.router [D, E] (ALL the experts)
    mlp.experts.{e}.{gate,up,down}_proj.weight    → layers.moe.w_gate/w_up/w_down [held, ..]
    mlp.shared_experts.{j}.{gate,up,down}_proj.weight → s_gate, s_up [D, n Fe] (columns
        side by side), s_down [n Fe, D] (rows stacked): ONE SwiGLU, averaged by one scale
    model.norm.weight, model.embed_tokens.weight  → final_norm, embed (tied: no lm_head)
A SHARE ``(r, s)`` (``config_from_hf(path, share=...)``) loads what device
``r`` of ``s`` that share a layer holds: experts ``[r E/s, (r+1) E/s)`` (the
gate whole) and rows ``[r V/s, (r+1) V/s)`` of the embedding and the head.

HF's rotary embedding for this family pairs ADJACENT columns of the rope
part (``rope_interleave``) and ``model.apply_rope`` pairs the two halves:
the rope columns of ``W_q`` (each head's last ``dr``) and of ``W_kva`` (its
last ``dr``) are permuted ONCE here, evens first, so that the same rotation
gives the same scores.
``model_type`` ``mellum`` (Mellum2-12B-A2.5B) loads into the window
stack's tree too, the SEQUENTIAL block's (two norms a layer, an untied head;
``_build_mellum_params``).  The names are taken as the sibling expert
checkpoints have them, UNVERIFIED against the published files; the rotation
pairs a head's halves as ``model.apply_rope`` does (``rotate_half``), so no
column is permuted; the published config names no q/k norm, and a checkpoint
layer that HOLDS ``self_attn.{q,k}_norm`` tensors is refused (never skipped:
the scores would be another model's); its multi-token-prediction head
(tensors under ``mtp.`` / ``model.mtp``) is skipped and counted with one
:class:`MtpSkipped` notice, layers past the description's ``n_layers`` (a
pipeline's later stages) with one :class:`LayersSkipped` notice:
    self_attn.{q,k,v,o}_proj.weight               → layers.attn.wq, wk, wv, wo (as they are)
    input_layernorm, post_attention_layernorm     → attn_norm, layers.moe.mlp_norm
    mlp.gate.weight [E, D]                        → layers.moe.router [D, E] (ALL the experts)
    mlp.experts.{e}.{gate,up,down}_proj.weight    → layers.moe.w_gate/w_up/w_down [held, ..]
    model.norm, model.embed_tokens, lm_head       → final_norm, embed, lm_head [D, V]
A share ``(r, s)`` is accepted as for ``cohere2_moe``.
``model_type`` ``evabyte`` (EvaByte; ``attention_class`` ``eva``) loads into
the EVA stack's tree (``_build_eva_params``; ``eva.py`` has the layer).  The
tensor names are ASSUMED (the Llama family's, with the two learned vectors
beside the projections) and unverified against the published files;
``adaptive_phi`` and ``adaptive_mu_k`` come as ``[1, H, 1, 1, hd]`` and are
held ``[H, hd]`` a layer; the head's ``num_pred_heads x vocab_size`` rows are
kept in their order (head-major: head 0, the served one, is the first
``vocab_size``); the rotation pairs a head's halves, so no column is
permuted; layers past the description's ``n_layers`` are skipped and counted
(:class:`LayersSkipped`):
    self_attn.{q,k,v,o}_proj.weight               → layers.wq, wk, wv [L, D, H hd], wo [L, H hd, D]
    self_attn.adaptive_phi, adaptive_mu_k         → layers.phi, mu [L, H, hd]
    input_layernorm, post_attention_layernorm     → layers.attn_norm, mlp_norm (g of 1 + g)
    mlp.{gate,up,down}_proj.weight                → layers.w_gate, w_up, w_down
    model.norm, model.embed_tokens, lm_head [P V, D] → final_norm, embed, lm_head [D, P V]
``model_type`` ``bailing_hybrid`` (Ling-3.0-flash, and Ling-3.0-flash-VL's
text decoder: tensors outside ``model.`` and ``lm_head.``, a tower and its
projector, skipped and counted with one :class:`VisionTowerSkipped` notice;
the extra prediction layer ``model.layers.<num_hidden_layers>`` skipped and
counted with one :class:`MtpSkipped` notice) loads into the Kimi Delta
Attention hybrid's tree.  The names are taken as the family's earlier
checkpoints have them (``model.word_embeddings``, ``attention.dense``,
``mlp.gate.expert_bias``), the delta rule's as Kimi Linear's without its
low-rank pairs (``no_kda_lora``), the latent layers' as DeepseekV3's:
    attention.{q,k,v}_proj, g_proj [H, D], b_proj [H, D] (a KDA layer)
                                                  → layers.gdn.w_in, ONE matrix q | k | v | z | b
    attention.{q,k,v}_conv1d.weight [H d, 1, taps] → conv_w [taps, C]
    attention.f_proj.weight [H dk, D]             → w_alpha (the decay's one full matrix)
    attention.{A_log [H], dt_bias [H dk]}         → float32 leaves [H], [H, dk]
    attention.o_norm.weight, dense.weight         → norm, w_out (transposed)
    attention.q_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, dense (a latent layer)
                                                  → layers.attn.* as DeepseekV3's, the rope
        dimensions from interleaved pairs to halves (``rope_interleave``)
    attention.g_proj.weight [H, D] (a latent layer) → layers.attn.w_z [D, H]
    mlp.gate.weight [E, D], mlp.gate.expert_bias  → layers.moe.router [D, E], router_bias (ALL the experts)
    mlp.experts.{e}.*, mlp.shared_experts.*       → the HELD experts' stacks, the shared expert
    mlp.{gate,up,down}_proj (the leading layers)  → layers.dense.*
``model_type`` ``lfm2_moe`` (LFM2-8B-A1B) loads into the short-convolution
hybrid's tree (layer N is the n-th attention or conv layer by ``layer_types``;
the embedding tied: no ``lm_head``).  The names are taken as the published
``lfm2_moe`` implementation has them, UNVERIFIED against the published files.
A description of fewer layers than the checkpoint has (a pipeline stage: the
leading ``n_layers``) loads those and leaves the rest on disk, counted with
one :class:`LayersSkipped` notice:
    model.embed_tokens.weight, model.embedding_norm.weight → embed, final_norm
    operator_norm.weight, ffn_norm.weight         → attn_norm or mixer_norm, mlp_norm
    conv.in_proj.weight [3 D, D] (B | C | x)      → layers.conv.w_in (as it is)
    conv.conv.weight [D, 1, taps]                 → conv_w [taps, D]
    conv.out_proj.weight                          → w_out (transposed)
    self_attn.{q,k,v}_proj, out_proj.weight       → layers.attn.wq, wk, wv, wo
    self_attn.{q,k}_layernorm.weight              → q_norm, k_norm
    feed_forward.{w1,w3,w2}.weight (the leading layers) → layers.dense.w_gate, w_up, w_down
    feed_forward.gate.weight [E, D], .expert_bias → layers.moe.router [D, E], router_bias
    feed_forward.experts.{e}.{w1,w3,w2}.weight    → layers.moe.w_gate, w_up, w_down [E, ..]
"""

from __future__ import annotations

import json
import logging
import re
from pathlib import Path
from typing import Any

import numpy as np

from calfkit_tpu.inference.config import ModelConfig

logger = logging.getLogger(__name__)


class VisionTowerSkipped(UserWarning):
    """A checkpoint's vision-tower tensors were left on disk: the language
    decoder is loaded and serves text alone."""


class MtpSkipped(UserWarning):
    """A checkpoint's multi-token-prediction module (``mtp.*``) was left on
    disk: no program here drafts from it."""


class LayersSkipped(UserWarning):
    """A checkpoint's layers past the description's ``n_layers`` (the later
    stages of a pipeline) were left on disk: this device serves the leading
    layers alone."""


# tensor names of a multimodal checkpoint's language decoder start with this
_LANGUAGE_PREFIX = "language_model."
_MTP_PREFIXES = ("mtp.", "model.mtp")


def config_from_hf(path: str | Path, share: "tuple[int, int] | None" = None) -> ModelConfig:
    """``share`` ``(r, s)``: the description of what device ``r`` of the ``s``
    that share a layer holds (``qwen3_next`` alone: its experts and its rows
    of the vocabulary)."""
    raw = json.loads((Path(path) / "config.json").read_text())
    if raw.get("model_type") == "qwen3_next":
        return _qwen3_next_config(raw, str(path), share)
    if raw.get("model_type") == "cohere2_moe":
        return _cohere2_moe_config(raw.get("text_config", raw), str(path), share)
    if raw.get("model_type") == "bailing_hybrid":
        return _bailing_hybrid_config(raw.get("text_config", raw), str(path), share)
    if raw.get("model_type") == "mellum":
        return _mellum_config(raw, str(path), share)
    if share is not None:
        raise ValueError(
            f"{path}: a share is described for qwen3_next, cohere2_moe, bailing_hybrid and "
            "mellum alone")
    if raw.get("model_type") == "evabyte":
        return _evabyte_config(raw, str(path))
    if raw.get("model_type") == "lfm2_moe":
        return _lfm2_moe_config(raw, str(path))
    if raw.get("model_type") == "granitemoehybrid":
        return _granite_hybrid_config(raw, str(path))
    if raw.get("model_type") == "kimi_vl":
        return _deepseek_config(raw["text_config"], str(path))
    if raw.get("model_type") == "deepseek_v3":
        return _deepseek_config(raw, str(path))
    return ModelConfig(
        name=raw.get("_name_or_path", str(path)),
        vocab_size=raw["vocab_size"],
        d_model=raw["hidden_size"],
        n_layers=raw["num_hidden_layers"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw.get("num_key_value_heads", raw["num_attention_heads"]),
        d_ff=raw["intermediate_size"],
        rope_theta=raw.get("rope_theta", 10000.0),
        norm_eps=raw.get("rms_norm_eps", 1e-5),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=raw.get("tie_word_embeddings", False),
    )


class RoutedExpertsUnsupported(ValueError):
    """A checkpoint with routed experts: ``model.py``'s MLP runs none."""


def _granite_hybrid_config(raw: dict, path: str) -> ModelConfig:
    """GraniteMoeHybrid's ``config.json`` -> the hybrid description."""
    if raw.get("num_local_experts", 0):
        raise RoutedExpertsUnsupported(
            f"{path}: num_local_experts = {raw['num_local_experts']}: routed "
            "experts are not supported (the shared MLP alone is)"
        )
    if raw.get("position_embedding_type", "nope") not in ("nope", "rope"):
        raise ValueError(f"unknown position_embedding_type {raw['position_embedding_type']!r}")
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=raw["vocab_size"],
        d_model=raw["hidden_size"],
        n_layers=raw["num_hidden_layers"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw.get("num_key_value_heads", raw["num_attention_heads"]),
        d_ff=raw["shared_intermediate_size"],
        rope_theta=raw.get("rope_theta", 10000.0),
        norm_eps=raw.get("rms_norm_eps", 1e-5),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=raw.get("tie_word_embeddings", False),
        layer_types=tuple(raw["layer_types"]),
        mamba_n_heads=raw["mamba_n_heads"],
        mamba_d_head=raw["mamba_d_head"],
        mamba_d_state=raw["mamba_d_state"],
        mamba_n_groups=raw.get("mamba_n_groups", 1),
        mamba_d_conv=raw.get("mamba_d_conv", 4),
        mamba_chunk_size=raw.get("mamba_chunk_size", 256),
        position_embedding=(
            "none" if raw.get("position_embedding_type", "nope") == "nope" else "rope"
        ),
        attention_multiplier=raw.get("attention_multiplier"),
        embedding_multiplier=float(raw.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(raw.get("residual_multiplier", 1.0)),
        logits_scaling=float(raw.get("logits_scaling", 1.0)),
    )


def _deepseek_config(raw: dict, path: str) -> ModelConfig:
    """DeepseekV3's ``config.json`` (or Kimi-VL's ``text_config``) -> the
    latent-attention description."""
    for key, only in (("q_lora_rank", None), ("rope_scaling", None)):
        if raw.get(key) is not only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    if raw.get("moe_layer_freq", 1) != 1:
        raise ValueError(f"{path}: moe_layer_freq = {raw['moe_layer_freq']} is not supported")
    experts = raw.get("n_routed_experts") or 0
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=raw["vocab_size"],
        d_model=raw["hidden_size"],
        n_layers=raw["num_hidden_layers"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw.get("num_key_value_heads", raw["num_attention_heads"]),
        d_ff=raw["intermediate_size"],
        rope_theta=raw.get("rope_theta", 10000.0),
        norm_eps=raw.get("rms_norm_eps", 1e-6),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=raw.get("tie_word_embeddings", False),
        kv_lora_rank=raw["kv_lora_rank"],
        qk_nope_head_dim=raw["qk_nope_head_dim"],
        qk_rope_head_dim=raw["qk_rope_head_dim"],
        v_head_dim=raw["v_head_dim"],
        n_routed_experts=experts,
        n_experts_per_tok=raw.get("num_experts_per_tok", 0) if experts else 0,
        n_shared_experts=(raw.get("n_shared_experts") or 0) if experts else 0,
        moe_d_ff=raw.get("moe_intermediate_size", 0) if experts else 0,
        first_k_dense=raw.get("first_k_dense_replace", 0) if experts else 0,
        routed_scaling_factor=float(raw.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func=raw.get("scoring_func", "sigmoid"),
        topk_method=raw.get("topk_method", "noaux_tc"),
        n_group=raw.get("n_group", 1),
        topk_group=raw.get("topk_group", 1),
    )


def _qwen3_next_config(raw: dict, path: str, share: "tuple[int, int] | None") -> ModelConfig:
    """Qwen3-Next's ``config.json`` -> the Gated DeltaNet hybrid's description."""
    from calfkit_tpu.inference.config import ATTENTION, GDN

    for key, only in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("rope_scaling", None), ("use_sliding_window", False)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    E, V = raw["num_experts"], raw["vocab_size"]
    rank, of = share or (0, 1)
    if not 0 <= rank < of or E % of or V % of:
        raise ValueError(f"{path}: share {share} does not divide {E} experts and {V} rows")
    every = raw.get("full_attention_interval", 4)
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=V // of,
        d_model=raw["hidden_size"],
        n_layers=raw["num_hidden_layers"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["intermediate_size"],
        rope_theta=raw.get("rope_theta", 10000.0),
        norm_eps=raw.get("rms_norm_eps", 1e-6),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=raw.get("tie_word_embeddings", False),
        layer_types=tuple(ATTENTION if (i + 1) % every == 0 else GDN
                          for i in range(raw["num_hidden_layers"])),
        gdn_n_k_heads=raw["linear_num_key_heads"],
        gdn_n_v_heads=raw["linear_num_value_heads"],
        gdn_d_k=raw["linear_key_head_dim"],
        gdn_d_v=raw["linear_value_head_dim"],
        gdn_d_conv=raw.get("linear_conv_kernel_dim", 4),
        attn_head_dim=raw["head_dim"],
        partial_rotary_factor=float(raw.get("partial_rotary_factor", 1.0)),
        qk_norm=True, attn_output_gate=True, norm_plus_one=True,
        n_routed_experts=E // of,
        n_experts_total=E if of > 1 else 0,
        expert_first=rank * (E // of),
        n_experts_per_tok=raw["num_experts_per_tok"],
        n_shared_experts=raw["shared_expert_intermediate_size"] // raw["moe_intermediate_size"],
        moe_d_ff=raw["moe_intermediate_size"],
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func="softmax", topk_method="greedy", shared_expert_gate=True,
    )


def _cohere2_moe_config(raw: dict, path: str, share: "tuple[int, int] | None") -> ModelConfig:
    """Cohere2-MoE's ``config.json`` -> the window stack's description."""
    from calfkit_tpu.inference.config import ATTENTION, WINDOW

    for key, only in (("use_parallel_block", True), ("use_qk_norm", False),
                      ("first_k_dense_replace", 0), ("rotary_pct", 1),
                      ("position_embedding_type", "rope_gptj"),
                      ("expert_selection_fn", "sigmoid"), ("logit_scale", 1),
                      ("shared_expert_combination_strategy", "average"),
                      ("tie_word_embeddings", True), ("attention_bias", False)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    E, V, L = raw["num_experts"], raw["vocab_size"], raw["num_hidden_layers"]
    rank, of = share or (0, 1)
    if not 0 <= rank < of or E % of or V % of:
        raise ValueError(f"{path}: share {share} does not divide {E} experts and {V} rows")
    every = raw.get("layer_switch", 4)
    kinds = {"sliding_attention": WINDOW, "full_attention": ATTENTION}
    types = raw.get("layer_types") or [
        "full_attention" if (i + 1) % every == 0 else "sliding_attention" for i in range(L)]
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=V // of,
        d_model=raw["hidden_size"],
        n_layers=L,
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["intermediate_size"],
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        norm_eps=float(raw.get("layer_norm_eps", 1e-5)),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=True,
        layer_types=tuple(kinds[t] for t in types[:L]),
        position_embedding="rope_window",
        attn_head_dim=raw["head_dim"],
        sliding_window=raw["sliding_window"],
        norm="layer", parallel_block=True,
        n_routed_experts=E // of,
        n_experts_total=E if of > 1 else 0,
        expert_first=rank * (E // of),
        n_experts_per_tok=raw["num_experts_per_tok"],
        n_shared_experts=raw["num_shared_experts"],
        moe_d_ff=raw["intermediate_size"],
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func="sigmoid", topk_method="greedy", shared_expert_combine="average",
    )


def _mellum_config(raw: dict, path: str, share: "tuple[int, int] | None") -> ModelConfig:
    """Mellum's ``config.json`` -> the window stack's description: the
    sequential RMSNorm block, the rotation by layer kind (``rope_parameters``:
    the plain law on the sliding layers, the full layers' own), softmax-routed
    experts in every layer, an untied head.  ``max_window_layers`` 0 with
    ``use_sliding_window`` is read as "``layer_types`` decides"."""
    from calfkit_tpu.inference.config import ATTENTION, WINDOW, RopeScaling

    for key, only in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("use_sliding_window", True),
                      ("max_window_layers", 0)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    E, V, L = raw["num_experts"], raw["vocab_size"], raw["num_hidden_layers"]
    if set(raw.get("mlp_layer_types", ["sparse"])[:L]) != {"sparse"}:
        raise ValueError(f"{path}: a layer whose FFN is not sparse is not supported")
    rank, of = share or (0, 1)
    if not 0 <= rank < of or E % of or V % of:
        raise ValueError(f"{path}: share {share} does not divide {E} experts and {V} rows")
    rope = raw["rope_parameters"]
    plain, scaled = dict(rope["sliding_attention"]), dict(rope["full_attention"])
    if plain.get("rope_type", "default") != "default":
        raise ValueError(
            f"{path}: rope_type {plain['rope_type']!r} on the sliding layers is not supported")
    if scaled.pop("rope_theta") != plain["rope_theta"]:
        raise ValueError(f"{path}: the two kinds of layer rotate from ONE rope_theta here")
    kinds = {"sliding_attention": WINDOW, "full_attention": ATTENTION}
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=V // of,
        d_model=raw["hidden_size"],
        n_layers=L,
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["moe_intermediate_size"],
        rope_theta=float(plain["rope_theta"]),
        norm_eps=float(raw.get("rms_norm_eps", 1e-6)),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=False,
        layer_types=tuple(kinds[t] for t in raw["layer_types"][:L]),
        position_embedding="rope",
        # RopeScaling refuses any rope_type but default and yarn
        rope_scaling_global=RopeScaling(**scaled),
        attn_head_dim=raw["head_dim"],
        sliding_window=raw["sliding_window"],
        n_routed_experts=E // of,
        n_experts_total=E if of > 1 else 0,
        expert_first=rank * (E // of),
        n_experts_per_tok=raw["num_experts_per_tok"],
        moe_d_ff=raw["moe_intermediate_size"],
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func="softmax", topk_method="greedy",
    )


def _evabyte_config(raw: dict, path: str) -> ModelConfig:
    """EvaByte's ``config.json`` -> the EVA stack's description.  A key that
    selects a variant is held to the ONE reading described (``eva.py``)."""
    from calfkit_tpu.inference.config import EVA

    for key, only in (("attention_class", "eva"), ("attention_bias", False),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("norm_add_unit_offset", True),
                      ("fp32_skip_add", True), ("num_chunks", None)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    L = raw["num_hidden_layers"]
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=raw["vocab_size"],
        d_model=raw["hidden_size"],
        n_layers=L,
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["intermediate_size"],
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw.get("rms_norm_eps", 1e-5)),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=False,
        layer_types=(EVA,) * L,
        norm_plus_one=True,
        window_size=raw["window_size"],
        chunk_size=raw["chunk_size"],
        num_pred_heads=raw.get("num_pred_heads", 1),
    )


def _bailing_hybrid_config(raw: dict, path: str, share: "tuple[int, int] | None") -> ModelConfig:
    """``bailing_hybrid``'s ``config.json`` (Ling-3.0-flash and its -VL's text
    decoder) -> the Kimi Delta Attention hybrid's description.  A key that
    selects a variant is held to the ONE reading described (module text); the
    two swiglu limit lists go into the description, which refuses a nonzero
    entry by name."""
    from calfkit_tpu.inference.config import ATTENTION, KDA

    for key, only in (("q_lora_rank", None), ("score_function", "sigmoid"),
                      ("moe_router_enable_expert_bias", True), ("use_mla_nope", False),
                      ("use_nGPT", False), ("scale_router_input", False), ("value_norm", False),
                      ("up_proj_norm", False), ("group_norm_size", 1), ("linear_silu", True),
                      ("no_kda_lora", True), ("use_kda_lora", False), ("kda_safe_gate", True),
                      ("gated_attention_proj_granularity_type", "head_wise"),
                      ("num_kv_heads_for_linear_attn", 0), ("rope_scaling", None)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    E, V, L = raw["num_experts"], raw["vocab_size"], raw["num_hidden_layers"]
    rank, of = share or (0, 1)
    if not 0 <= rank < of or E % of or V % of:
        raise ValueError(f"{path}: share {share} does not divide {E} experts and {V} rows")
    every, nd = raw.get("layer_group_size", 6), raw.get("first_k_dense_replace", 0)
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=V // of,
        d_model=raw["hidden_size"],
        n_layers=L,
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw.get("num_key_value_heads", raw["num_attention_heads"]),
        d_ff=raw["intermediate_size"],
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        norm_eps=float(raw.get("rms_norm_eps", 1e-6)),
        kv_norm_eps=float(raw.get("rms_norm_eps", 1e-6)),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=raw.get("tie_word_embeddings", False),
        layer_types=tuple(ATTENTION if (i + 1) % every == 0 else KDA for i in range(L)),
        kv_lora_rank=raw["kv_lora_rank"],
        qk_nope_head_dim=raw["qk_nope_head_dim"],
        qk_rope_head_dim=raw["qk_rope_head_dim"],
        v_head_dim=raw["v_head_dim"],
        attn_output_gate=True,
        gdn_n_k_heads=raw["num_attention_heads"], gdn_n_v_heads=raw["num_attention_heads"],
        gdn_d_k=raw["head_dim"], gdn_d_v=raw["head_dim"],
        gdn_d_conv=raw.get("short_conv_kernel_size", 4),
        kda_lower_bound=float(raw.get("kda_lower_bound", -5.0)),
        n_routed_experts=E // of,
        n_experts_total=E if of > 1 else 0,
        expert_first=rank * (E // of),
        n_experts_per_tok=raw["num_experts_per_tok"],
        n_shared_experts=(raw.get("moe_shared_expert_intermediate_size", 0)
                          // raw["moe_intermediate_size"]),
        moe_d_ff=raw["moe_intermediate_size"],
        first_k_dense=nd,
        routed_scaling_factor=float(raw.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func="sigmoid", topk_method="noaux_tc",
        n_group=raw.get("n_group", 1), topk_group=raw.get("topk_group", 1),
        expert_swiglu_limits=tuple(
            float(v) for v in raw.get("expert_swiglu_limit_list", [])[nd:L]),
        shared_expert_swiglu_limits=tuple(
            float(v) for v in raw.get("share_expert_swiglu_limit_list", [])[nd:L]),
    )


def _lfm2_moe_config(raw: dict, path: str) -> ModelConfig:
    """Lfm2Moe's ``config.json`` -> the short-convolution hybrid's description."""
    from calfkit_tpu.inference.config import ATTENTION, CONV

    for key, only in (("use_expert_bias", True), ("conv_bias", False),
                      ("tie_embedding", True), ("rope_scaling", None)):
        if raw.get(key, only) != only:
            raise ValueError(f"{path}: {key} = {raw[key]!r} is not supported")
    kinds = {"conv": CONV, "full_attention": ATTENTION}
    return ModelConfig(
        name=raw.get("_name_or_path", path),
        vocab_size=raw["vocab_size"],
        d_model=raw["hidden_size"],
        n_layers=raw["num_hidden_layers"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["intermediate_size"],
        rope_theta=float(raw.get("rope_theta", 1000000.0)),
        norm_eps=raw.get("norm_eps", 1e-5),
        max_seq_len=raw.get("max_position_embeddings", 2048),
        tie_embeddings=True,
        layer_types=tuple(kinds[t] for t in raw["layer_types"]),
        conv_L_cache=raw["conv_L_cache"],
        qk_norm=True,
        n_routed_experts=raw["num_experts"],
        n_experts_per_tok=raw["num_experts_per_tok"],
        moe_d_ff=raw["moe_intermediate_size"],
        first_k_dense=raw["num_dense_layers"],
        routed_scaling_factor=float(raw.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(raw.get("norm_topk_prob", True)),
        scoring_func="sigmoid", topk_method="noaux_tc",
        topk_norm_eps=1e-6,  # Lfm2MoeSparseMoeBlock's own constant: not a key of the file
    )


def _open_safetensors(path: Path) -> dict[str, Any]:
    """name -> lazy tensor getter across all shards."""
    from safetensors import safe_open  # ships with transformers

    index_file = path / "model.safetensors.index.json"
    files: dict[str, Path] = {}
    if index_file.exists():
        index = json.loads(index_file.read_text())
        for name, shard in index["weight_map"].items():
            files[name] = path / shard
    else:
        single = path / "model.safetensors"
        if not single.exists():
            raise FileNotFoundError(f"no safetensors found under {path}")
        with safe_open(str(single), framework="np") as f:
            for name in f.keys():
                files[name] = single
    return files


def load_params(
    path: str | Path,
    config: ModelConfig,
    shardings: dict[str, Any],
    *,
    quantize: str | None = None,
) -> dict[str, Any]:
    """Load + transpose + stack + shard-place the checkpoint.

    ``quantize="int8"``/``"int4"`` quantizes each matmul weight ON HOST
    before the device_put, so device memory never holds a full-precision
    copy — the path that fits Llama-3-8B on one 16 GB chip (int8) or in
    ~4 GB of weights (int4, packed nibbles + group scales).  Pass
    shardings already expanded by
    :func:`calfkit_tpu.inference.quant.quantize_shardings`.
    """
    import jax
    from safetensors import safe_open

    if quantize not in (None, "int8", "int4"):
        raise ValueError(f"unsupported quantization {quantize!r}")

    path = Path(path)
    files = _open_safetensors(path)
    handles: dict[Path, Any] = {}

    prefix = ""
    if any(name.startswith(_LANGUAGE_PREFIX) for name in files):
        prefix = _LANGUAGE_PREFIX
        skipped = sum(1 for name in files if not name.startswith(prefix))
        if skipped:
            import warnings

            warnings.warn(VisionTowerSkipped(
                f"{path}: {skipped} tensors outside {prefix!r} (a vision tower and its "
                "projector) were not loaded: the language decoder serves text alone"
            ), stacklevel=2)

    mtp = sum(1 for name in files if name.startswith(_MTP_PREFIXES))
    if config.kda:
        # bailing_hybrid: the extra prediction layer follows the stack as
        # model.layers.<num_hidden_layers>..., the tower lies outside model. / lm_head.
        layer_of = re.compile(r"^model\.layers\.(\d+)\.")
        mtp += sum(1 for name in files
                   if (m := layer_of.match(name)) and int(m.group(1)) >= config.n_layers)
        tower = sum(1 for name in files if not name.startswith(("model.", "lm_head.")))
        if tower:
            import warnings

            warnings.warn(VisionTowerSkipped(
                f"{path}: {tower} tensors outside 'model.' and 'lm_head.' (a vision tower "
                "and its projector) were not loaded: the language decoder serves text alone"
            ), stacklevel=2)
    if config.windowed and not config.eva:
        normed = sorted(name for name in files
                        if re.search(r"\.self_attn\.[qk]_norm\.", name))
        if normed:
            raise ValueError(
                f"{path}: {len(normed)} q/k-norm tensors ({normed[0]} ..): the window stack "
                "normalises no query or key head (no key of the published config names such "
                "a norm), and a checkpoint that holds one is another model: refused, not "
                "skipped")
    if config.shortconv or config.windowed:
        # a pipeline stage of such a checkpoint: the leading n_layers
        layer_of = re.compile(r"^model\.layers\.(\d+)\.")
        later = {int(m.group(1)) for name in files
                 if (m := layer_of.match(name)) and int(m.group(1)) >= config.n_layers}
        if later:
            import warnings

            warnings.warn(LayersSkipped(
                f"{path}: layers {min(later)}-{max(later)} ({len(later)} of "
                f"{config.n_layers + len(later)}) were not loaded: the description keeps the "
                f"leading {config.n_layers} (a pipeline's first stage, with the final norm "
                "and the head so that tokens come out)"
            ), stacklevel=2)
    if mtp:
        import warnings

        warnings.warn(MtpSkipped(
            f"{path}: {mtp} tensors under {_MTP_PREFIXES[0]!r} (the multi-token-prediction "
            "module) were not loaded: no program drafts from it"
        ), stacklevel=2)

    def get(name: str) -> np.ndarray:
        f = files[name := prefix + name]
        if f not in handles:
            handles[f] = safe_open(str(f), framework="np").__enter__()
        return handles[f].get_tensor(name)

    try:
        return _build_params(config, shardings, get, quantize)
    finally:
        for handle in handles.values():
            handle.__exit__(None, None, None)


def _build_params(
    config: ModelConfig,
    shardings: dict[str, Any],
    get: Any,
    quantize: str | None,
) -> dict[str, Any]:
    import jax

    D, H, K, hd = config.d_model, config.n_heads, config.n_kv_heads, config.head_dim
    L = config.n_layers
    if config.kda:
        if quantize is not None:
            raise ValueError("no quantized load for a model with Kimi Delta Attention layers")
        return _build_kda_params(config, shardings, get)
    if config.gdn:
        if quantize is not None:
            raise ValueError("no quantized load for a model with Gated DeltaNet layers")
        return _build_gdn_params(config, shardings, get)
    if config.shortconv:
        if quantize is not None:
            raise ValueError("no quantized load for a model with short-convolution layers")
        return _build_shortconv_params(config, shardings, get)
    if config.eva:
        if quantize is not None:
            raise ValueError("no quantized load for a model with EVA layers")
        return _build_eva_params(config, shardings, get)
    if config.windowed:
        if quantize is not None:
            raise ValueError("no quantized load for a model with window layers and experts")
        # the window stack's two checkpoint families differ by their block: the
        # parallel one is cohere2_moe's names, the sequential one mellum's
        build = _build_window_params if config.parallel_block else _build_mellum_params
        return build(config, shardings, get)
    if config.layer_types:
        if quantize is not None:
            raise ValueError("no quantized load for a model with Mamba layers")
        return _build_hybrid_params(config, shardings, get)
    if config.latent:
        if quantize is not None:
            raise ValueError("no quantized load for a model with latent attention and experts")
        return _build_latent_params(config, shardings, get)
    _quant_axes: dict[str, tuple[int, ...]] = {}
    _bits = 8 if quantize == "int8" else 4
    if quantize in ("int8", "int4"):
        from calfkit_tpu.inference.quant import (
            LAYER_REDUCTION_AXES,
            LM_HEAD_REDUCTION_AXES,
        )

        _quant_axes = {**LAYER_REDUCTION_AXES, "lm_head": LM_HEAD_REDUCTION_AXES}

    def put(arr: np.ndarray, sharding: Any, name: str = "") -> Any:
        axes = _quant_axes.get(name)
        if axes is not None:
            from calfkit_tpu.inference.quant import quantize_array_host

            q = quantize_array_host(arr, axes, bits=_bits)
            packed_key = next(k for k in q if k != "scale")
            packed_sh = sharding.get(packed_key, sharding.get("__q4__"))
            if packed_sh is None:
                # a silent fallback here would device_put int4 bytes under
                # an int8 spec — fail loudly on the bits mismatch instead
                raise ValueError(
                    f"shardings for {name!r} were expanded for a different "
                    f"quantization than quantize={'int4' if _bits == 4 else 'int8'!r}"
                )
            return {
                packed_key: jax.device_put(q[packed_key], packed_sh),
                "scale": jax.device_put(q["scale"], sharding["scale"]),
            }
        return jax.device_put(arr.astype(np.dtype(config.dtype)), sharding)

    def stack(fmt: str, transform: Any) -> np.ndarray:
        return np.stack([transform(get(fmt.format(i))) for i in range(L)])

    ls = shardings["layers"]
    params: dict[str, Any] = {
        "embed": put(get("model.embed_tokens.weight"), shardings["embed"]),
        "layers": {
            # HF projections are [out, in]; ours are [in, heads, hd]
            "wq": put(
                stack(
                    "model.layers.{}.self_attn.q_proj.weight",
                    lambda w: w.T.reshape(D, H, hd),
                ),
                ls["wq"],
                "wq",
            ),
            "wk": put(
                stack(
                    "model.layers.{}.self_attn.k_proj.weight",
                    lambda w: w.T.reshape(D, K, hd),
                ),
                ls["wk"],
                "wk",
            ),
            "wv": put(
                stack(
                    "model.layers.{}.self_attn.v_proj.weight",
                    lambda w: w.T.reshape(D, K, hd),
                ),
                ls["wv"],
                "wv",
            ),
            "wo": put(
                stack(
                    "model.layers.{}.self_attn.o_proj.weight",
                    lambda w: w.T.reshape(H, hd, D),
                ),
                ls["wo"],
                "wo",
            ),
            "w_gate": put(
                stack("model.layers.{}.mlp.gate_proj.weight", lambda w: w.T),
                ls["w_gate"],
                "w_gate",
            ),
            "w_up": put(
                stack("model.layers.{}.mlp.up_proj.weight", lambda w: w.T),
                ls["w_up"],
                "w_up",
            ),
            "w_down": put(
                stack("model.layers.{}.mlp.down_proj.weight", lambda w: w.T),
                ls["w_down"],
                "w_down",
            ),
            "attn_norm": put(
                stack("model.layers.{}.input_layernorm.weight", lambda w: w),
                ls["attn_norm"],
            ),
            "mlp_norm": put(
                stack(
                    "model.layers.{}.post_attention_layernorm.weight", lambda w: w
                ),
                ls["mlp_norm"],
            ),
        },
        "final_norm": put(get("model.norm.weight"), shardings["final_norm"]),
    }
    if not config.tie_embeddings:
        params["lm_head"] = put(
            get("lm_head.weight").T, shardings["lm_head"], "lm_head"
        )
    logger.info("loaded %s params", config.name)
    return params


def _build_hybrid_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The hybrid tree from HF GraniteMoeHybrid names and fused layouts."""
    import jax

    from calfkit_tpu.inference.config import ATTENTION

    D, H, K, hd, F = (config.d_model, config.n_heads, config.n_kv_heads, config.head_dim,
                      config.d_ff)
    dtype = np.dtype(config.dtype)
    attn_at = [i for i, t in enumerate(config.layer_types) if t == ATTENTION]
    mamba_at = [i for i, t in enumerate(config.layer_types) if t != ATTENTION]

    def stack(layers: list[int], name: str, transform: Any, as_type: Any = dtype) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in layers]
        ).astype(as_type)

    everywhere = list(range(config.n_layers))
    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight").astype(dtype),
        "layers": {
            "attn": {
                "wq": stack(attn_at, "self_attn.q_proj.weight", lambda w: w.T.reshape(D, H, hd)),
                "wk": stack(attn_at, "self_attn.k_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wv": stack(attn_at, "self_attn.v_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wo": stack(attn_at, "self_attn.o_proj.weight", lambda w: w.T.reshape(H, hd, D)),
                "attn_norm": stack(attn_at, "input_layernorm.weight", lambda w: w),
            },
            "mamba": {
                "w_in": stack(mamba_at, "mamba.in_proj.weight", lambda w: w),
                # HF's depthwise conv1d weight is [C, 1, d_conv]; ours is tap-major
                "conv_w": stack(mamba_at, "mamba.conv1d.weight", lambda w: w[:, 0, :].T),
                "conv_b": stack(mamba_at, "mamba.conv1d.bias", lambda w: w),
                "A_log": stack(mamba_at, "mamba.A_log", lambda w: w, np.float32),
                "D": stack(mamba_at, "mamba.D", lambda w: w, np.float32),
                "dt_bias": stack(mamba_at, "mamba.dt_bias", lambda w: w, np.float32),
                "norm": stack(mamba_at, "mamba.norm.weight", lambda w: w),
                "w_out": stack(mamba_at, "mamba.out_proj.weight", lambda w: w.T),
                "mixer_norm": stack(mamba_at, "input_layernorm.weight", lambda w: w),
            },
            "mlp": {
                # input_linear is [2F, D]: the gate's rows first, then up's
                "w_gate": stack(everywhere, "shared_mlp.input_linear.weight", lambda w: w[:F].T),
                "w_up": stack(everywhere, "shared_mlp.input_linear.weight", lambda w: w[F:].T),
                "w_down": stack(everywhere, "shared_mlp.output_linear.weight", lambda w: w.T),
                "mlp_norm": stack(everywhere, "post_attention_layernorm.weight", lambda w: w),
            },
        },
        "final_norm": get("model.norm.weight").astype(dtype),
    }
    if not config.tie_embeddings:
        tree["lm_head"] = get("lm_head.weight").T.astype(dtype)
    logger.info("loaded %s params", config.name)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_gdn_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The Gated DeltaNet hybrid's tree from HF Qwen3-Next names (module
    text); of a share, the experts and the vocabulary rows it holds."""
    import jax

    from calfkit_tpu.inference.config import ATTENTION

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    Hk, Hv, dk, dv = c.gdn_n_k_heads, c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v
    per = Hv // Hk  # value heads a key head
    dtype = np.dtype(c.dtype)
    attn_at = [i for i, t in enumerate(c.layer_types) if t == ATTENTION]
    gdn_at = [i for i, t in enumerate(c.layer_types) if t != ATTENTION]
    everywhere = range(c.n_layers)
    rank = c.expert_first // c.n_routed_experts
    rows = slice(rank * c.vocab_size, (rank + 1) * c.vocab_size)

    def stack(layers: Any, name: str, transform: Any, as_type: Any = dtype) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in layers]
        ).astype(as_type)

    def w_in(i: int) -> np.ndarray:
        # per key head q | k | its value heads' v | their z, and b | a -> q | k | v | z | b | a
        qkvz = get(f"model.layers.{i}.linear_attn.in_proj_qkvz.weight").reshape(
            Hk, 2 * dk + 2 * per * dv, D)
        ba = get(f"model.layers.{i}.linear_attn.in_proj_ba.weight").reshape(Hk, 2 * per, D)
        parts = (qkvz[:, :dk], qkvz[:, dk:2 * dk], qkvz[:, 2 * dk:2 * dk + per * dv],
                 qkvz[:, 2 * dk + per * dv:], ba[:, :per], ba[:, per:])
        return np.concatenate([p.reshape(-1, D) for p in parts])

    def experts(name: str) -> np.ndarray:
        held = range(c.expert_first, c.expert_first + c.n_routed_experts)
        return np.stack([
            np.stack([get(f"model.layers.{i}.mlp.experts.{e}.{name}.weight").T for e in held])
            for i in everywhere
        ]).astype(dtype)

    q_out = hd * (2 if c.attn_output_gate else 1)
    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight")[rows].astype(dtype),
        "layers": {
            "attn": {
                "wq": stack(attn_at, "self_attn.q_proj.weight",
                            lambda w: w.T.reshape(D, H, q_out)),
                "wk": stack(attn_at, "self_attn.k_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wv": stack(attn_at, "self_attn.v_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wo": stack(attn_at, "self_attn.o_proj.weight", lambda w: w.T.reshape(H, hd, D)),
                "attn_norm": stack(attn_at, "input_layernorm.weight", lambda w: w),
                "q_norm": stack(attn_at, "self_attn.q_norm.weight", lambda w: w),
                "k_norm": stack(attn_at, "self_attn.k_norm.weight", lambda w: w),
            },
            "gdn": {
                "w_in": np.stack([w_in(i) for i in gdn_at]).astype(dtype),
                # HF's depthwise conv1d weight is [C, 1, d_conv]; ours is tap-major
                "conv_w": stack(gdn_at, "linear_attn.conv1d.weight", lambda w: w[:, 0, :].T),
                "A_log": stack(gdn_at, "linear_attn.A_log", lambda w: w, np.float32),
                "dt_bias": stack(gdn_at, "linear_attn.dt_bias", lambda w: w, np.float32),
                "norm": stack(gdn_at, "linear_attn.norm.weight", lambda w: w),
                "w_out": stack(gdn_at, "linear_attn.out_proj.weight", lambda w: w.T),
                "mixer_norm": stack(gdn_at, "input_layernorm.weight", lambda w: w),
            },
            "moe": {
                "router": stack(everywhere, "mlp.gate.weight", lambda w: w.T),
                "w_gate": experts("gate_proj"),
                "w_up": experts("up_proj"),
                "w_down": experts("down_proj"),
                "s_gate": stack(everywhere, "mlp.shared_expert.gate_proj.weight", lambda w: w.T),
                "s_up": stack(everywhere, "mlp.shared_expert.up_proj.weight", lambda w: w.T),
                "s_down": stack(everywhere, "mlp.shared_expert.down_proj.weight", lambda w: w.T),
                "shared_gate": stack(everywhere, "mlp.shared_expert_gate.weight",
                                     lambda w: w.reshape(D)),
                "mlp_norm": stack(everywhere, "post_attention_layernorm.weight", lambda w: w),
            },
        },
        "final_norm": get("model.norm.weight").astype(dtype),
    }
    if not c.tie_embeddings:
        tree["lm_head"] = get("lm_head.weight")[rows].T.astype(dtype)
    logger.info("loaded %s params (experts %d-%d of %d, vocabulary rows %d-%d)", c.name,
                c.expert_first, c.expert_first + c.n_routed_experts - 1, c.experts_scored,
                rows.start, rows.stop - 1)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_shortconv_params(
        config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The short-convolution hybrid's tree from ``lfm2_moe`` names (module
    text): the leading ``n_layers`` of the checkpoint, every expert."""
    import jax

    from calfkit_tpu.inference.config import ATTENTION

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    dtype = np.dtype(c.dtype)
    nd = c.first_k_dense
    attn_at = [i for i, t in enumerate(c.layer_types) if t == ATTENTION]
    conv_at = [i for i, t in enumerate(c.layer_types) if t != ATTENTION]
    dense_at, moe_at = range(nd), range(nd, c.n_layers)

    def stack(layers: Any, name: str, transform: Any, as_type: Any = dtype) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in layers]
        ).astype(as_type)

    def experts(name: str) -> np.ndarray:
        return np.stack([
            np.stack([get(f"model.layers.{i}.feed_forward.experts.{e}.{name}.weight").T
                      for e in range(c.n_routed_experts)])
            for i in moe_at
        ]).astype(dtype)

    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight").astype(dtype),
        "layers": {
            "attn": {
                "wq": stack(attn_at, "self_attn.q_proj.weight", lambda w: w.T.reshape(D, H, hd)),
                "wk": stack(attn_at, "self_attn.k_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wv": stack(attn_at, "self_attn.v_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wo": stack(attn_at, "self_attn.out_proj.weight",
                            lambda w: w.T.reshape(H, hd, D)),
                "attn_norm": stack(attn_at, "operator_norm.weight", lambda w: w),
                "q_norm": stack(attn_at, "self_attn.q_layernorm.weight", lambda w: w),
                "k_norm": stack(attn_at, "self_attn.k_layernorm.weight", lambda w: w),
            },
            "conv": {
                "w_in": stack(conv_at, "conv.in_proj.weight", lambda w: w),
                # HF's depthwise conv weight is [D, 1, taps]; ours is tap-major
                "conv_w": stack(conv_at, "conv.conv.weight", lambda w: w[:, 0, :].T),
                "w_out": stack(conv_at, "conv.out_proj.weight", lambda w: w.T),
                "mixer_norm": stack(conv_at, "operator_norm.weight", lambda w: w),
            },
            "dense": {
                "w_gate": stack(dense_at, "feed_forward.w1.weight", lambda w: w.T),
                "w_up": stack(dense_at, "feed_forward.w3.weight", lambda w: w.T),
                "w_down": stack(dense_at, "feed_forward.w2.weight", lambda w: w.T),
                "mlp_norm": stack(dense_at, "ffn_norm.weight", lambda w: w),
            },
            "moe": {
                "router": stack(moe_at, "feed_forward.gate.weight", lambda w: w.T),
                "router_bias": stack(moe_at, "feed_forward.expert_bias", lambda w: w, np.float32),
                "w_gate": experts("w1"),
                "w_up": experts("w3"),
                "w_down": experts("w2"),
                "mlp_norm": stack(moe_at, "ffn_norm.weight", lambda w: w),
            },
        },
        "final_norm": get("model.embedding_norm.weight").astype(dtype),
    }
    logger.info("loaded %s params (layers 0-%d, %d experts a layer)", c.name,
                c.n_layers - 1, c.n_routed_experts)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_kda_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The Kimi Delta Attention hybrid's tree from ``bailing_hybrid`` names
    (module text); of a share, the experts and the vocabulary rows it holds.
    q | k | v | g (one a head) | b become ONE ``w_in``, the three depthwise
    convs one ``conv_w``; the latent layers' rope dimensions go from
    interleaved pairs to halves (``rope_interleave``), as DeepseekV3's do."""
    import jax

    from calfkit_tpu.inference.config import ATTENTION

    c = config
    D, H, r, dn, dr, dv = (c.d_model, c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    Hv, dk = c.gdn_n_v_heads, c.gdn_d_k
    dtype = np.dtype(c.dtype)
    nd = c.first_k_dense
    attn_at = [i for i, t in enumerate(c.layer_types) if t == ATTENTION]
    kda_at = [i for i, t in enumerate(c.layer_types) if t != ATTENTION]
    dense_at, moe_at = range(nd), range(nd, c.n_layers)
    rank = c.expert_first // c.n_routed_experts
    rows = slice(rank * c.vocab_size, (rank + 1) * c.vocab_size)
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])  # pairs -> halves

    def rope_last(w: np.ndarray, start: int) -> np.ndarray:
        return np.concatenate([w[..., :start], w[..., start:][..., halves]], axis=-1)

    def stack(layers: Any, name: str, transform: Any, as_type: Any = dtype) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in layers]
        ).astype(as_type)

    def together(i: int, names: tuple, transform: Any) -> np.ndarray:
        return np.concatenate(
            [transform(get(f"model.layers.{i}.attention.{n}.weight")) for n in names])

    def experts(name: str) -> np.ndarray:
        held = range(c.expert_first, c.expert_first + c.n_routed_experts)
        return np.stack([
            np.stack([get(f"model.layers.{i}.mlp.experts.{e}.{name}.weight").T for e in held])
            for i in moe_at
        ]).astype(dtype)

    kv_b = stack(attn_at, "attention.kv_b_proj.weight", lambda w: w.T.reshape(r, H, dn + dv))
    layers: dict[str, Any] = {
        "attn": {
            "wq": stack(attn_at, "attention.q_proj.weight",
                        lambda w: rope_last(w.T.reshape(D, H, dn + dr), dn)),
            "w_kva": stack(attn_at, "attention.kv_a_proj_with_mqa.weight",
                           lambda w: rope_last(w.T, r)),
            "kv_norm": stack(attn_at, "attention.kv_a_layernorm.weight", lambda w: w),
            "w_uk": np.ascontiguousarray(kv_b[..., :dn]),
            "w_uv": np.ascontiguousarray(kv_b[..., dn:]),
            "w_z": stack(attn_at, "attention.g_proj.weight", lambda w: w.T),
            "wo": stack(attn_at, "attention.dense.weight", lambda w: w.T.reshape(H, dv, D)),
            "attn_norm": stack(attn_at, "input_layernorm.weight", lambda w: w),
        },
        "gdn": {
            "w_in": np.stack([together(i, ("q_proj", "k_proj", "v_proj", "g_proj", "b_proj"),
                                       lambda w: w) for i in kda_at]).astype(dtype),
            # a depthwise conv1d weight is [C, 1, d_conv]; ours is tap-major over q | k | v
            "conv_w": np.stack([together(i, ("q_conv1d", "k_conv1d", "v_conv1d"),
                                         lambda w: w[:, 0, :]).T for i in kda_at]).astype(dtype),
            "w_alpha": stack(kda_at, "attention.f_proj.weight", lambda w: w),
            "A_log": stack(kda_at, "attention.A_log", lambda w: w.reshape(Hv), np.float32),
            "dt_bias": stack(kda_at, "attention.dt_bias", lambda w: w.reshape(Hv, dk),
                             np.float32),
            "norm": stack(kda_at, "attention.o_norm.weight", lambda w: w),
            "w_out": stack(kda_at, "attention.dense.weight", lambda w: w.T),
            "mixer_norm": stack(kda_at, "input_layernorm.weight", lambda w: w),
        },
        "dense": {
            "w_gate": stack(dense_at, "mlp.gate_proj.weight", lambda w: w.T),
            "w_up": stack(dense_at, "mlp.up_proj.weight", lambda w: w.T),
            "w_down": stack(dense_at, "mlp.down_proj.weight", lambda w: w.T),
            "mlp_norm": stack(dense_at, "post_attention_layernorm.weight", lambda w: w),
        },
        "moe": {
            "router": stack(moe_at, "mlp.gate.weight", lambda w: w.T),
            "router_bias": stack(moe_at, "mlp.gate.expert_bias", lambda w: w, np.float32),
            "w_gate": experts("gate_proj"),
            "w_up": experts("up_proj"),
            "w_down": experts("down_proj"),
            "s_gate": stack(moe_at, "mlp.shared_experts.gate_proj.weight", lambda w: w.T),
            "s_up": stack(moe_at, "mlp.shared_experts.up_proj.weight", lambda w: w.T),
            "s_down": stack(moe_at, "mlp.shared_experts.down_proj.weight", lambda w: w.T),
            "mlp_norm": stack(moe_at, "post_attention_layernorm.weight", lambda w: w),
        },
    }
    tree: dict[str, Any] = {
        "embed": get("model.word_embeddings.weight")[rows].astype(dtype),
        "layers": layers,
        "final_norm": get("model.norm.weight").astype(dtype),
    }
    if not c.tie_embeddings:
        tree["lm_head"] = get("lm_head.weight")[rows].T.astype(dtype)
    logger.info("loaded %s params (experts %d-%d of %d, vocabulary rows %d-%d)", c.name,
                c.expert_first, c.expert_first + c.n_routed_experts - 1, c.experts_scored,
                rows.start, rows.stop - 1)
    return jax.tree.map(jax.device_put, tree, shardings)


def _layer_stackers(config: ModelConfig, get: Any) -> tuple[Any, Any]:
    """What both of the window stack's tree builders stack a layer at a time:
    ``stack(name, transform)`` over every layer of the description, and
    ``experts(name)``, the HELD experts' matrices transposed, ``[L, held, ..]``."""
    c = config
    dtype = np.dtype(c.dtype)
    everywhere = range(c.n_layers)

    def stack(name: str, transform: Any) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in everywhere]).astype(dtype)

    def experts(name: str) -> np.ndarray:
        held = range(c.expert_first, c.expert_first + c.n_routed_experts)
        return np.stack([
            np.stack([get(f"model.layers.{i}.mlp.experts.{e}.{name}.weight").T for e in held])
            for i in everywhere
        ]).astype(dtype)

    return stack, experts


def _build_window_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The window stack's tree from HF Cohere2-MoE names (module text); of a
    share, the experts and the rows of the tied vocabulary it holds."""
    import jax

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    dtype = np.dtype(c.dtype)
    everywhere = range(c.n_layers)
    rank = c.expert_first // c.n_routed_experts
    rows = slice(rank * c.vocab_size, (rank + 1) * c.vocab_size)
    halves = np.concatenate([np.arange(0, hd, 2), np.arange(1, hd, 2)])  # pairs -> halves
    stack, experts = _layer_stackers(c, get)

    def shared(name: str, axis: int) -> np.ndarray:
        # n modules -> the ONE SwiGLU of n x moe_d_ff: gate and up columns side by side, down rows stacked
        return np.stack([
            np.concatenate([
                get(f"model.layers.{i}.mlp.shared_experts.{j}.{name}.weight").T
                for j in range(c.n_shared_experts)], axis=axis)
            for i in everywhere
        ]).astype(dtype)

    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight")[rows].astype(dtype),
        "layers": {
            "attn": {
                "wq": stack("self_attn.q_proj.weight",
                            lambda w: w.T.reshape(D, H, hd)[..., halves]),
                "wk": stack("self_attn.k_proj.weight",
                            lambda w: w.T.reshape(D, K, hd)[..., halves]),
                "wv": stack("self_attn.v_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wo": stack("self_attn.o_proj.weight", lambda w: w.T.reshape(H, hd, D)),
                "attn_norm": stack("input_layernorm.weight", lambda w: w),
            },
            "moe": {
                "router": stack("mlp.gate.weight", lambda w: w.T),
                "w_gate": experts("gate_proj"),
                "w_up": experts("up_proj"),
                "w_down": experts("down_proj"),
                "s_gate": shared("gate_proj", 1),
                "s_up": shared("up_proj", 1),
                "s_down": shared("down_proj", 0),
            },
        },
        "final_norm": get("model.norm.weight").astype(dtype),
    }
    logger.info("loaded %s params (experts %d-%d of %d, vocabulary rows %d-%d)", c.name,
                c.expert_first, c.expert_first + c.n_routed_experts - 1, c.experts_scored,
                rows.start, rows.stop - 1)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_mellum_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The window stack's tree, its sequential block, from HF Mellum names
    (module text); of a share, the experts and the rows of the embedding and
    the columns of the head it holds.  No column is permuted: the published
    rotation pairs a head's halves, as ``model.apply_rope`` does."""
    import jax

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    dtype = np.dtype(c.dtype)
    rank = c.expert_first // c.n_routed_experts
    rows = slice(rank * c.vocab_size, (rank + 1) * c.vocab_size)
    stack, experts = _layer_stackers(c, get)

    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight")[rows].astype(dtype),
        "layers": {
            "attn": {
                "wq": stack("self_attn.q_proj.weight", lambda w: w.T.reshape(D, H, hd)),
                "wk": stack("self_attn.k_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wv": stack("self_attn.v_proj.weight", lambda w: w.T.reshape(D, K, hd)),
                "wo": stack("self_attn.o_proj.weight", lambda w: w.T.reshape(H, hd, D)),
                "attn_norm": stack("input_layernorm.weight", lambda w: w),
            },
            "moe": {
                "router": stack("mlp.gate.weight", lambda w: w.T),
                "w_gate": experts("gate_proj"),
                "w_up": experts("up_proj"),
                "w_down": experts("down_proj"),
                "mlp_norm": stack("post_attention_layernorm.weight", lambda w: w),
            },
        },
        "final_norm": get("model.norm.weight").astype(dtype),
        "lm_head": get("lm_head.weight")[rows].T.astype(dtype),
    }
    logger.info("loaded %s params (experts %d-%d of %d, vocabulary rows %d-%d)", c.name,
                c.expert_first, c.expert_first + c.n_routed_experts - 1, c.experts_scored,
                rows.start, rows.stop - 1)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_eva_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The EVA stack's tree from the ASSUMED EvaByte names (module text).  No
    column is permuted (the rotation pairs a head's halves); the head keeps
    its ``num_pred_heads x vocab_size`` rows in their order."""
    import jax

    c = config
    D, H, hd = c.d_model, c.n_heads, c.head_dim
    dtype = np.dtype(c.dtype)
    stack, _ = _layer_stackers(c, get)
    head = get("lm_head.weight")
    if head.shape != (c.num_pred_heads * c.vocab_size, D):
        raise ValueError(
            f"lm_head.weight is {head.shape}: the description's head has num_pred_heads x "
            f"vocab_size = {c.num_pred_heads} x {c.vocab_size} rows of {D}")

    def a_head(w: np.ndarray) -> np.ndarray:  # [1, H, 1, 1, hd] -> [H, hd]
        if w.size != H * hd:
            raise ValueError(f"a learned vector of {w.shape}: one of {hd} a head ({H}) is described")
        return w.reshape(H, hd)

    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight").astype(dtype),
        "layers": {
            "wq": stack("self_attn.q_proj.weight", lambda w: w.T),
            "wk": stack("self_attn.k_proj.weight", lambda w: w.T),
            "wv": stack("self_attn.v_proj.weight", lambda w: w.T),
            "wo": stack("self_attn.o_proj.weight", lambda w: w.T),
            "phi": stack("self_attn.adaptive_phi", a_head),
            "mu": stack("self_attn.adaptive_mu_k", a_head),
            "attn_norm": stack("input_layernorm.weight", lambda w: w),
            "mlp_norm": stack("post_attention_layernorm.weight", lambda w: w),
            "w_gate": stack("mlp.gate_proj.weight", lambda w: w.T),
            "w_up": stack("mlp.up_proj.weight", lambda w: w.T),
            "w_down": stack("mlp.down_proj.weight", lambda w: w.T),
        },
        "final_norm": get("model.norm.weight").astype(dtype),
        "lm_head": head.T.astype(dtype),
    }
    logger.info("loaded %s params (%d EVA layers, a head of %d x %d rows)", c.name, c.n_layers,
                c.num_pred_heads, c.vocab_size)
    return jax.tree.map(jax.device_put, tree, shardings)


def _build_latent_params(config: ModelConfig, shardings: dict[str, Any], get: Any) -> dict[str, Any]:
    """The latent-attention tree from HF DeepseekV3 names (module text)."""
    import jax

    c = config
    D, H, r, dn, dr, dv = (c.d_model, c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim,
                           c.qk_rope_head_dim, c.v_head_dim)
    dtype = np.dtype(c.dtype)
    nd = c.n_dense_layers
    # adjacent pairs -> halves: the even columns first, then the odd ones
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def rope_last(w: np.ndarray, start: int) -> np.ndarray:
        return np.concatenate([w[..., :start], w[..., start:][..., halves]], axis=-1)

    def stack(layers: Any, name: str, transform: Any, as_type: Any = dtype) -> np.ndarray:
        return np.stack(
            [transform(get(f"model.layers.{i}.{name}")) for i in layers]
        ).astype(as_type)

    def experts(layers: Any, name: str) -> np.ndarray:
        return np.stack([
            np.stack([get(f"model.layers.{i}.mlp.experts.{e}.{name}.weight").T
                      for e in range(c.n_routed_experts)])
            for i in layers
        ]).astype(dtype)

    everywhere, dense_at, moe_at = range(c.n_layers), range(nd), range(nd, c.n_layers)
    kv_b = stack(everywhere, "self_attn.kv_b_proj.weight", lambda w: w.T.reshape(r, H, dn + dv))
    layers: dict[str, Any] = {
        "attn": {
            "wq": stack(everywhere, "self_attn.q_proj.weight",
                        lambda w: rope_last(w.T.reshape(D, H, dn + dr), dn)),
            "w_kva": stack(everywhere, "self_attn.kv_a_proj_with_mqa.weight",
                           lambda w: rope_last(w.T, r)),
            "kv_norm": stack(everywhere, "self_attn.kv_a_layernorm.weight", lambda w: w),
            "w_uk": np.ascontiguousarray(kv_b[..., :dn]),
            "w_uv": np.ascontiguousarray(kv_b[..., dn:]),
            "wo": stack(everywhere, "self_attn.o_proj.weight", lambda w: w.T.reshape(H, dv, D)),
            "attn_norm": stack(everywhere, "input_layernorm.weight", lambda w: w),
        },
        "dense": {
            "w_gate": stack(dense_at, "mlp.gate_proj.weight", lambda w: w.T),
            "w_up": stack(dense_at, "mlp.up_proj.weight", lambda w: w.T),
            "w_down": stack(dense_at, "mlp.down_proj.weight", lambda w: w.T),
            "mlp_norm": stack(dense_at, "post_attention_layernorm.weight", lambda w: w),
        },
    }
    if c.moe:
        layers["moe"] = {
            "router": stack(moe_at, "mlp.gate.weight", lambda w: w.T),
            "router_bias": stack(moe_at, "mlp.gate.e_score_correction_bias", lambda w: w,
                                 np.float32),
            "w_gate": experts(moe_at, "gate_proj"),
            "w_up": experts(moe_at, "up_proj"),
            "w_down": experts(moe_at, "down_proj"),
            "mlp_norm": stack(moe_at, "post_attention_layernorm.weight", lambda w: w),
        }
        if c.n_shared_experts:
            layers["moe"].update(
                s_gate=stack(moe_at, "mlp.shared_experts.gate_proj.weight", lambda w: w.T),
                s_up=stack(moe_at, "mlp.shared_experts.up_proj.weight", lambda w: w.T),
                s_down=stack(moe_at, "mlp.shared_experts.down_proj.weight", lambda w: w.T),
            )
    tree: dict[str, Any] = {
        "embed": get("model.embed_tokens.weight").astype(dtype),
        "layers": layers,
        "final_norm": get("model.norm.weight").astype(dtype),
    }
    if not c.tie_embeddings:
        tree["lm_head"] = get("lm_head.weight").T.astype(dtype)
    logger.info("loaded %s params", c.name)
    return jax.tree.map(jax.device_put, tree, shardings)
