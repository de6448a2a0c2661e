"""GSPMD sharding layout for the inference backend.

The scaling-model recipe: pick a mesh, annotate param/cache shardings, let
XLA insert the collectives (all-reduce on attention/MLP outputs, all-gather
on logits), profile, iterate.  Axes:

- ``tp`` — tensor parallelism *inside* one model replica: attention heads,
  MLP hidden, and vocab are split over ``tp``; XLA emits psum/all-gathers
  that ride ICI.
- ``dp`` — independent serving replicas: the batch dimension of the KV cache
  and token buffers is split over ``dp``.

Weights that don't divide evenly by the axis (e.g. 4 KV heads on tp=8) fall
back to replication for that tensor — GSPMD remains correct either way, this
just keeps layouts predictable.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from calfkit_tpu.inference.config import ModelConfig

Params = dict[str, Any]
# the leaves of one stacked group of Mamba-2 layers (inference/mamba.py)
MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm", "w_out",
                "mixer_norm")
# the leaves of a latent-attention stack's groups (inference/model.py, moe.py)
LATENT_ATTN_LEAVES = ("wq", "w_kva", "kv_norm", "w_uk", "w_uv", "wo", "attn_norm")
# the leaves of one stacked group of Gated DeltaNet layers (inference/gdn.py)
GDN_LEAVES = ("w_in", "conv_w", "A_log", "dt_bias", "norm", "w_out", "mixer_norm")
KDA_LEAVES = (*GDN_LEAVES, "w_alpha")  # Kimi Delta Attention: the decay's own matrix
# the leaves of one stacked group of gated short convolutions (inference/shortconv.py)
SHORTCONV_LEAVES = ("w_in", "conv_w", "w_out", "mixer_norm")
GATED_ATTN_LEAVES = ("wq", "wk", "wv", "wo", "attn_norm", "q_norm", "k_norm")
MLP_LEAVES = ("w_gate", "w_up", "w_down", "mlp_norm")
MOE_LEAVES = ("router", "router_bias", *MLP_LEAVES)
SHARED_EXPERT_LEAVES = ("s_gate", "s_up", "s_down")
EVA_LEAVES = ("wq", "wk", "wv", "wo", "phi", "mu", "attn_norm", *MLP_LEAVES)


def make_mesh(
    tp: int = 1, dp: int = 1, *, devices: list[jax.Device] | None = None
) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    need = tp * dp
    if len(devices) < need:
        raise ValueError(
            f"mesh needs {need} devices (tp={tp} × dp={dp}), have {len(devices)}"
        )
    grid = np.array(devices[:need]).reshape(dp, tp)
    return Mesh(grid, ("dp", "tp"))


def _spec(mesh: Mesh, dims: list[tuple[int, str | None]]) -> P:
    """Build a PartitionSpec, dropping axis names whose size doesn't divide
    the dim (replicate instead)."""
    parts: list[str | None] = []
    for size, axis in dims:
        if axis is None or size % mesh.shape[axis] != 0:
            parts.append(None)
        else:
            parts.append(axis)
    return P(*parts)


def param_shardings(config: ModelConfig, mesh: Mesh) -> Params:
    """NamedSharding pytree matching :func:`model.init_params` structure."""
    D, H, K, hd, F, V = (
        config.d_model,
        config.n_heads,
        config.n_kv_heads,
        config.head_dim,
        config.d_ff,
        config.vocab_size,
    )

    def ns(dims: list[tuple[int, str | None]]) -> NamedSharding:
        return NamedSharding(mesh, _spec(mesh, dims))

    def attention(L: tuple) -> Params:
        return {
            "wq": ns([L, (D, None), (H, "tp"), (hd, None)]),
            "wk": ns([L, (D, None), (K, "tp"), (hd, None)]),
            "wv": ns([L, (D, None), (K, "tp"), (hd, None)]),
            "wo": ns([L, (H, "tp"), (hd, None), (D, None)]),
            "attn_norm": ns([L, (D, None)]),
        }

    def mlp(L: tuple) -> Params:
        return {
            "w_gate": ns([L, (D, None), (F, "tp")]),
            "w_up": ns([L, (D, None), (F, "tp")]),
            "w_down": ns([L, (F, "tp"), (D, None)]),
            "mlp_norm": ns([L, (D, None)]),
        }

    L = (config.n_layers, None)
    if config.latent:
        # a latent-attention stack (see model.py): every leaf replicated.  One
        # device holds them whole: the engine refuses such a model on a mesh
        # of more than one device until the experts are held by share
        whole = NamedSharding(mesh, P())
        layers = {
            "attn": dict.fromkeys(
                LATENT_ATTN_LEAVES + (("w_z",) if config.attn_output_gate else ()), whole),
            "dense": dict.fromkeys(MLP_LEAVES, whole),
        }
        if config.kda:  # Kimi Delta Attention beside the latent layers, replicated too
            layers["gdn"] = dict.fromkeys(KDA_LEAVES, whole)
        if config.moe:
            layers["moe"] = dict.fromkeys(
                MOE_LEAVES + (SHARED_EXPERT_LEAVES if config.n_shared_experts else ()), whole)
    elif config.eva:
        # an EVA stack (eva.py): every leaf replicated (the engine refuses such a
        # model on a mesh of more than one device: the summary pool, which is
        # COMPUTED from the ring, has no exchange over tp)
        layers = dict.fromkeys(EVA_LEAVES, NamedSharding(mesh, P()))
    elif config.windowed:
        # a window stack (see model.py): every leaf replicated, as the latent
        # stack's are and for its reason (one device holds its SHARE of the
        # experts and of the tied vocabulary whole: the engine refuses such a
        # model on a mesh of more than one device); the parallel block has
        # ONE norm a layer
        whole = NamedSharding(mesh, P())
        layers = {
            "attn": dict.fromkeys(("wq", "wk", "wv", "wo", "attn_norm"), whole),
            "moe": dict.fromkeys(
                ("router", "w_gate", "w_up", "w_down")
                + (() if config.parallel_block else ("mlp_norm",))
                + (SHARED_EXPERT_LEAVES if config.n_shared_experts else ()), whole),
        }
    elif config.shortconv:
        # a short-convolution hybrid (see model.py): every leaf replicated, as
        # the delta-rule hybrid's are and for its reason (the engine refuses such
        # a model on a mesh of more than one device: the conv leaves, the experts
        # and the per-slot tail have no layout over tp)
        whole = NamedSharding(mesh, P())
        layers = {
            "attn": dict.fromkeys(
                GATED_ATTN_LEAVES[: None if config.qk_norm else -2], whole),
            "conv": dict.fromkeys(SHORTCONV_LEAVES, whole),
            "dense": dict.fromkeys(MLP_LEAVES, whole),
            "moe": dict.fromkeys(
                MOE_LEAVES + (SHARED_EXPERT_LEAVES if config.n_shared_experts else ()), whole),
        }
    elif config.gdn:
        # a Gated DeltaNet hybrid (see model.py): every leaf replicated, as
        # the latent stack's are and for its reason (the engine refuses
        # such a model on a mesh of more than one device: a share of the
        # experts is what ONE device holds, config.expert_first)
        whole = NamedSharding(mesh, P())
        layers = {
            "attn": dict.fromkeys(
                GATED_ATTN_LEAVES[: None if config.qk_norm else -2], whole),
            "gdn": dict.fromkeys(GDN_LEAVES, whole),
            "moe": dict.fromkeys(
                ("router", *MLP_LEAVES)
                + (SHARED_EXPERT_LEAVES if config.n_shared_experts else ())
                + (("shared_gate",) if config.shared_expert_gate else ()), whole),
        }
    elif config.layer_types:
        # a hybrid stack (see model.py): the attention and MLP groups keep
        # the dense layout's specs; the Mamba leaves are replicated (one
        # device holds them whole: the engine refuses such a model on a
        # mesh of more than one device until they have a layout over tp)
        layers: Params = {
            "attn": attention((config.n_kv_layers, None)),
            "mamba": dict.fromkeys(MAMBA_LEAVES, NamedSharding(mesh, P())),
            "mlp": mlp(L),
        }
    else:
        layers = {**attention(L), **mlp(L)}
    shardings: Params = {
        "embed": ns([(V, "tp"), (D, None)]),
        "layers": layers,
        "final_norm": ns([(D, None)]),
    }
    if not config.tie_embeddings:
        shardings["lm_head"] = ns([(D, None), (V * config.num_pred_heads, "tp")])
    return shardings


def cache_sharding(config: ModelConfig, mesh: Mesh, batch: int) -> NamedSharding:
    """KV cache [L, B, K, S, hd]: batch over dp, kv heads over tp."""
    return NamedSharding(
        mesh,
        _spec(
            mesh,
            [
                (config.n_kv_layers, None),
                (batch, "dp"),
                (config.cache_heads, "tp"),
                (1, None),
                (1, None),
            ],
        ),
    )


def pool_sharding(config: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Paged KV pool [L, N, K, page / f, f * hd] (as stored): kv heads over tp.

    Pages are NOT split over dp — block tables address the whole pool, and
    proving page locality to GSPMD isn't worth it at current dp targets
    (paged mode exists to fit one big replica; dp replicas each hold a
    pool).
    """
    return NamedSharding(
        mesh,
        _spec(
            mesh,
            [
                (config.n_kv_layers, None),
                (1, None),
                (config.cache_heads, "tp"),
                (1, None),
                (1, None),
            ],
        ),
    )


def batch_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    return NamedSharding(mesh, _spec(mesh, [(batch, "dp")]))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def place_params(params: Params, shardings: Params) -> Params:
    """Device-put the param pytree onto its sharding layout."""
    return jax.tree.map(
        lambda arr, sh: jax.device_put(arr, sh), params, shardings
    )
