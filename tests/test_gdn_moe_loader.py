"""Gated DeltaNet hybrid (Qwen3-Next's kind): the loader on a fabricated checkpoint, whole and
as a share; and the older kinds' dispatch programs, which must be the parent commit's.

The toy model, its seeding, the tolerance and its reason: ``tests/arch_harness.py``.
"""

from __future__ import annotations

import hashlib
import json
import warnings
from dataclasses import replace
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import manifest
from calfkit_tpu.inference import gdn, moe
from calfkit_tpu.inference import model as M
from calfkit_tpu.inference.config import (
    ModelConfig,
    SpecConfig,
    UnsupportedWithRecurrentLayers,
    preset,
)
from calfkit_tpu.inference.engine import InferenceEngine
from calfkit_tpu.inference.mamba import make_recurrent_state
from calfkit_tpu.inference.sharding import make_mesh
from tests.arch_harness import GDN_MOE as FAMILY
from tests.arch_harness import Spy, both_forms_at_toy_size  # noqa: F401 - an autouse fixture

ARCH, LOGIT_TOL, TOY = FAMILY.arch, FAMILY.logit_tol, FAMILY.toy


# ------------------------------------------------ (f) the loader
def _checkpoint(path, config: ModelConfig, tree) -> None:
    """``tree`` (ALL the experts, the whole vocabulary) as a qwen3_next
    checkpoint: HF's names and layouts, ``in_proj_qkvz`` and ``in_proj_ba``
    interleaved per key head as HF keeps them, an ``mtp`` module beside."""
    from safetensors.numpy import save_file

    c = config
    D, H, K, hd = c.d_model, c.n_heads, c.n_kv_heads, c.head_dim
    Hk, Hv, dk, dv = c.gdn_n_k_heads, c.gdn_n_v_heads, c.gdn_d_k, c.gdn_d_v
    per, kd = Hv // Hk, Hk * dk
    out = {"model.embed_tokens.weight": tree["embed"], "model.norm.weight": tree["final_norm"],
           "lm_head.weight": tree["lm_head"].T, "mtp.fc.weight": np.zeros((4, 4), np.float32),
           "mtp.norm.weight": np.zeros((4,), np.float32)}
    attn, mixer, ffn = (tree["layers"][g] for g in ("attn", "gdn", "moe"))
    ia = im = 0
    for i, kind in enumerate(c.layer_types):
        pre = f"model.layers.{i}."
        if kind == "attention":
            out.update({
                pre + "self_attn.q_proj.weight": attn["wq"][ia].reshape(D, H * 2 * hd).T,
                pre + "self_attn.k_proj.weight": attn["wk"][ia].reshape(D, K * hd).T,
                pre + "self_attn.v_proj.weight": attn["wv"][ia].reshape(D, K * hd).T,
                pre + "self_attn.o_proj.weight": attn["wo"][ia].reshape(H * hd, D).T,
                pre + "self_attn.q_norm.weight": attn["q_norm"][ia],
                pre + "self_attn.k_norm.weight": attn["k_norm"][ia],
                pre + "input_layernorm.weight": attn["attn_norm"][ia],
            })
            ia += 1
        else:
            w = mixer["w_in"][im]
            q, k = (w[j * kd:(j + 1) * kd].reshape(Hk, dk, D) for j in (0, 1))
            v, z = (w[2 * kd + j * Hv * dv: 2 * kd + (j + 1) * Hv * dv].reshape(Hk, per * dv, D)
                    for j in (0, 1))
            b, a = (w[2 * kd + 2 * Hv * dv + j * Hv: 2 * kd + 2 * Hv * dv + (j + 1) * Hv]
                    .reshape(Hk, per, D) for j in (0, 1))
            out.update({
                pre + "linear_attn.in_proj_qkvz.weight":
                    np.concatenate([q, k, v, z], axis=1).reshape(-1, D),
                pre + "linear_attn.in_proj_ba.weight": np.concatenate([b, a], axis=1).reshape(-1, D),
                pre + "linear_attn.conv1d.weight": mixer["conv_w"][im].T[:, None, :],
                pre + "linear_attn.A_log": mixer["A_log"][im],
                pre + "linear_attn.dt_bias": mixer["dt_bias"][im],
                pre + "linear_attn.norm.weight": mixer["norm"][im],
                pre + "linear_attn.out_proj.weight": mixer["w_out"][im].T,
                pre + "input_layernorm.weight": mixer["mixer_norm"][im],
            })
            im += 1
        out.update({
            pre + "post_attention_layernorm.weight": ffn["mlp_norm"][i],
            pre + "mlp.gate.weight": ffn["router"][i].T,
            pre + "mlp.shared_expert_gate.weight": ffn["shared_gate"][i][None, :],
            **{pre + f"mlp.shared_expert.{n}_proj.weight": ffn[f"s_{n}"][i].T
               for n in ("gate", "up", "down")},
            **{pre + f"mlp.experts.{e}.{n}_proj.weight": ffn[f"w_{n}"][i, e].T
               for e in range(c.n_routed_experts) for n in ("gate", "up", "down")},
        })
    save_file({n: np.ascontiguousarray(np.asarray(t, np.float32)) for n, t in out.items()},
              str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps({
        "model_type": "qwen3_next", "vocab_size": c.vocab_size, "hidden_size": D,
        "num_hidden_layers": c.n_layers, "num_attention_heads": H, "num_key_value_heads": K,
        "head_dim": hd, "intermediate_size": c.d_ff, "full_attention_interval": 4,
        "linear_num_key_heads": Hk, "linear_num_value_heads": Hv, "linear_key_head_dim": dk,
        "linear_value_head_dim": dv, "linear_conv_kernel_dim": c.gdn_d_conv,
        "partial_rotary_factor": c.partial_rotary_factor, "rms_norm_eps": c.norm_eps,
        "rope_theta": c.rope_theta, "num_experts": c.n_routed_experts,
        "num_experts_per_tok": c.n_experts_per_tok, "moe_intermediate_size": c.moe_d_ff,
        "shared_expert_intermediate_size": c.moe_d_ff, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "max_position_embeddings": 256,
        "tie_word_embeddings": False,
    }))


@pytest.mark.parametrize("share", [None, (0, 4), (3, 4)], ids=["whole", "share-0-of-4", "share-3-of-4"])
def test_a_fabricated_qwen3_next_checkpoint_loads_whole_and_as_a_share(tmp_path, share):
    """HF's names and interleaved layouts load into the tree the program
    serves; a share loads its experts and its rows of the vocabulary, the
    gate whole; ``mtp.*`` is skipped and counted.  The loaded tree serves
    the logits the reference gives for it."""
    from calfkit_tpu.inference.loader import MtpSkipped, config_from_hf, load_params
    from calfkit_tpu.inference.sharding import param_shardings

    whole = replace(TOY, n_routed_experts=8, n_experts_total=0, expert_first=0)
    tree = jax.tree.map(np.asarray, FAMILY.seeded(whole, key=12))
    _checkpoint(tmp_path, whole, tree)
    config = replace(config_from_hf(tmp_path, share), dtype="float32", gdn_chunk_size=8)
    rank, of = share or (0, 1)
    assert (config.n_routed_experts, config.experts_scored, config.expert_first,
            config.vocab_size) == (8 // of, 8, rank * 8 // of, 128 // of)
    assert config.layer_types == TOY.layer_types and config.head_dim == 16
    mesh = make_mesh(tp=1, dp=1, devices=jax.devices()[:1])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = load_params(tmp_path, config, param_shardings(config, mesh))
    assert [w for w in caught if issubclass(w.category, MtpSkipped)
            and "2 tensors" in str(w.message)]
    rows = slice(rank * 128 // of, (rank + 1) * 128 // of)
    held = slice(config.expert_first, config.expert_first + config.n_routed_experts)
    want = {**tree, "embed": tree["embed"][rows], "lm_head": tree["lm_head"][:, rows],
            "layers": {**tree["layers"], "moe": {
                **tree["layers"]["moe"],
                **{n: tree["layers"]["moe"][n][:, held] for n in ("w_gate", "w_up", "w_down")}}}}
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for (path, got), expected in zip(jax.tree.leaves_with_path(loaded), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(got), expected), path
    tokens = np.random.default_rng(1).integers(3, 128 // of, (1, 24)).astype(np.int32)
    logits = FAMILY.forward(loaded, config, tokens)[0]
    reference = ARCH.forward_logits(loaded, config, tokens, np.asarray([24], np.int32))
    assert np.abs(np.asarray(logits) - reference).max() < LOGIT_TOL


def test_a_share_of_another_family_and_a_share_that_does_not_divide_are_refused(tmp_path):
    from calfkit_tpu.inference.loader import config_from_hf

    (tmp_path / "config.json").write_text(json.dumps({"model_type": "llama"}))
    with pytest.raises(ValueError, match="a share is described for"):
        config_from_hf(tmp_path, (0, 4))
    (tmp_path / "config.json").write_text(json.dumps(
        {"model_type": "qwen3_next", "num_experts": 8, "vocab_size": 128}))
    with pytest.raises(ValueError, match="does not divide"):
        config_from_hf(tmp_path, (0, 3))


# ------------------------------------------------ (g) the other cells' programs are the parent's
HYBRID = ModelConfig(
    name="toy-hybrid", vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2,
    d_ff=64, layer_types=("mamba", "mamba", "attention"), mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, dtype="float32",
    position_embedding="none", attention_multiplier=0.25, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, tie_embeddings=True, max_seq_len=1024,
)
LATENT = ModelConfig(
    name="toy-mla-moe", vocab_size=128, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
    d_ff=64, rope_theta=800000.0, max_seq_len=256, dtype="float32",
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_experts_per_tok=2, n_shared_experts=1, moe_d_ff=16,
    first_k_dense=1, routed_scaling_factor=2.446,
)
# sha256 of str(jaxpr) of the paged decode dispatch and of a ragged program
# carrying one chunk of a two-row wave, as the PARENT commit (6cf34d0, PR 32)
# traced them for a dense model (Mistral's kind), a Mamba-2 hybrid
# (granite's) and a latent-attention stack with routed experts (Kimi's),
# recorded there with this file's ``_programs``.  A PR that changes these
# programs on purpose records anew: PR 45 did for the latent-moe pair (the
# dense expert products spelled weights first: in each program the two up
# products' ``dot_general`` takes the rows and then the experts, ``[T, E, Fe]``
# with a transpose ``(1, 0, 2)`` behind it, where it took the experts and then
# the rows, ``[E, Fe, T]`` and ``(0, 2, 1)``; nothing else differs);
# PR 46 did for all of them, here and below (every program ends in the paged write's loop of
# window updates: with PR 45's scatter, tests/test_kv_write.py's reference, put back as
# ``consolidate_ring_paged``, each traced to the hash pinned before, letter for letter).
TRACED_AT_THE_PARENT = {
    "dense": {"decode": "6f3974d73e2b667aace9dd64f5c9d415cf02293d44858013c4406ec21fe28920",
              "ragged": "fca501f0667c6ddc88f13b2129a11e002cbd23600be1ff7a6eaf9196b326dada"},
    "hybrid": {"decode": "2a78532baaf81aaf37e67fef237b26b3576565d404af13faf57d59edc2aeea83",
               "ragged": "3692da3834602bc1b4c3e4821f7df499e01ed8843a6fe664a338db0ecc2d651a"},
    "latent-moe": {"decode": "34d24d67febc3db9e62b8e29db53c9d8acedc19ebeb115d5fdec1ff5e8edd77a",
                   "ragged": "0c7e5dd78606dff42c740cbd5e28f856fc30835ee5e90bef83948aafbc0b94c1"},
}


def _programs(engine) -> dict:
    rt, cfg = engine.runtime, engine.config
    args, window, steps, sampled = engine._decode_args()
    rows, chunk = 2, rt.prefill_chunk
    sk, sv = (jnp.zeros((cfg.n_kv_layers, rows, cfg.cache_heads, 2 * chunk, w), engine._k.dtype)
              for w in cfg.cache_dims)
    wave = [sk, sv, jnp.zeros((rows, chunk), jnp.int32), jnp.int32(0)]
    kw = {}
    if cfg.recurrent:
        kw.update(state=engine._state, wstate=make_recurrent_state(cfg, rows))
    if cfg.moe:
        kw.update(moe=moe.moe_stats_init(cfg), wmoe=moe.moe_stats_init(cfg))
    decode_kw = {k: v for k, v in kw.items() if k in ("state", "moe")}
    if kw:
        kw["true_lens"] = jnp.zeros((rows,), jnp.int32)
    return {
        "decode": jax.make_jaxpr(
            engine._decode_fn_paged(window // rt.page_size, steps, sampled))(*args, **decode_kw),
        "ragged": jax.make_jaxpr(
            engine._ragged_jit(window, steps, sampled, chunk, rows))(*args, *wave, **kw),
    }


@pytest.mark.parametrize("kind", sorted(TRACED_AT_THE_PARENT))
def test_the_three_older_kinds_trace_the_programs_the_parent_traced(monkeypatch, kind):
    """A description without the new fields builds the decode and ragged
    programs the parent commit built, letter for letter: the benchmark's
    three older cells run the parent's programs."""
    monkeypatch.undo()  # the measured limit of the dense form, as the parent had it
    config = {"dense": preset("debug"), "hybrid": HYBRID, "latent-moe": LATENT}[kind]
    engine = InferenceEngine(config, FAMILY.runtime(attention_impl="xla"))
    for name, jaxpr in _programs(engine).items():
        text = str(jaxpr)
        assert "gdn" not in text and "out_gate" not in text
        assert hashlib.sha256(text.encode()).hexdigest() == TRACED_AT_THE_PARENT[kind][name], name


# ... and of the ragged programs whose two-row chunk takes the GROUPED form
# (32 tokens past the toy limit of 8), as PR 34 traces them: the products
# over the flattened stack, the layer as the one run of groups that is not
# empty.  The parent's differed (a ragged product on the sliced layer).
# Recorded anew in PR 45: the decode steps BESIDE the chunk are dense, and
# their up products' ``dot_general`` changed as above; the chunk's did not.
TRACED_SINCE_PR_34 = {
    "gdn-moe": "11e4f435f43718b91384bd66356e2cbed477451f29c928078c8816aae3e53086",
    "latent-moe": "e915d8a7dcb29db89c1bfaa5cc0c21b03de7dcd87daca7af975c92b7b18791c0",
}


@pytest.mark.parametrize("kind", sorted(TRACED_SINCE_PR_34))
def test_a_grouped_chunk_s_ragged_program_is_what_pr_34_traced(kind):
    config = {"gdn-moe": TOY, "latent-moe": LATENT}[kind]
    engine = InferenceEngine(config, FAMILY.runtime(attention_impl="xla"))
    assert not moe.dense_form(2 * engine.runtime.prefill_chunk, config)
    text = str(_programs(engine)["ragged"])
    assert "ragged_dot" in text and "dynamic_update_slice" in text
    assert hashlib.sha256(text.encode()).hexdigest() == TRACED_SINCE_PR_34[kind]


def test_the_new_kind_s_programs_name_their_scopes():
    text = {name: jaxpr.pretty_print(name_stack=True) for name, jaxpr in _programs(
        InferenceEngine(TOY, FAMILY.runtime(attention_impl="xla"))).items()}
    for scope in ("gdn", "in_proj", "conv", "state", "gate_norm", "out_proj", "qk_norm",
                  "out_gate", "moe", "router", "experts", "combine", "shared"):
        assert f"{scope}" in text["decode"], scope
    assert "triangular_solve" in text["ragged"] and "triangular_solve" not in text["decode"]


