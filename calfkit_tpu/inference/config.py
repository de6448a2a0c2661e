"""Model/runtime configuration for the local inference backend.

Pure dataclasses — importable without jax (the Worker/CLI read these before
any device work happens).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace


ATTENTION, MAMBA, GDN, WINDOW, KDA, CONV = "attention", "mamba", "gdn", "window", "kda", "conv"
EVA = "eva"  # EVA attention: an exact ALIGNED window beside pooled summaries of what lies behind it
RECURRENT_KINDS = (MAMBA, GDN, KDA, CONV)
DELTA_RULE_KINDS = (GDN, KDA)  # the gated delta rule: a decay a head, or a key channel
# What a layer of each kind leaves behind of a sequence, i.e. the cache kind
# the engine has to hold for it: "global" = K and V of EVERY token, in pages
# a row keeps for its whole life; "window" = K and V of the last
# ``sliding_window`` tokens, in a ring of pages a row that it writes over
# (gives back) as it grows; "state" = a recurrent state of fixed size a slot
# (of a "conv" layer, a gated short convolution, the conv tail and NOTHING
# else: the pair's matrix side is empty); "window+summaries" = K and V of the
# CURRENT aligned window of ``window_size`` tokens (a ring of pages a row that
# the next window writes over: it empties at the window's edge, it does not
# slide) AND one pooled key and value for every ``chunk_size`` tokens behind
# it, in pages a row keeps for its whole life, computed from the exact ones
# (eva.py) and read with them under one softmax.
# A new kind of layer is a row here and a mixer in model.py.
CACHE_KINDS = {
    ATTENTION: "global", WINDOW: "window", MAMBA: "state", GDN: "state", KDA: "state",
    CONV: "state", EVA: "window+summaries"}


class UnsupportedWithRecurrentLayers(ValueError):
    """A runtime option that a model with recurrent (Mamba-2 or Gated
    DeltaNet) layers cannot be served under yet.  Raised when the engine is built, never later:
    there is no silent fallback to a path that would drop the state."""


class UnsupportedWithWindowLayers(ValueError):
    """A runtime option that a model with sliding-window attention layers
    (K and V pages by cache kind: a ring of pages a row for the window
    layers) cannot be served under yet.  Raised when the engine is built,
    never later: there is no silent fallback to a path that knows no lower
    bound and would attend, or keep, what the window has left behind."""


class UnsupportedWithEvaLayers(UnsupportedWithWindowLayers):
    """A runtime option that a model with EVA layers (an aligned window ring
    beside summary pages that are COMPUTED from it) cannot be served under
    yet.  Raised when the engine is built, never later: there is no silent
    fallback to a path that would read a summary as a key, or keep none."""


class UnsupportedWithLatentAttention(ValueError):
    """A runtime option that a model with latent attention (one latent a
    token in place of K and V per head) and routed experts cannot be served
    under yet.  Raised when the engine is built, never later: there is no
    silent fallback to a path that would read the latent as K and V."""


@dataclass(frozen=True)
class RopeScaling:
    """How a kind of layer's rotary frequencies differ from the plain law
    ``theta^(-2i/d)`` (HF ``rope_parameters`` of one layer kind).  ``yarn`` as
    ``transformers``' ``_compute_yarn_parameters`` has it: the pairs that turn
    more than ``beta_fast`` times over ``original_max_position_embeddings``
    keep their frequency, those that turn less than ``beta_slow`` times have
    it divided by ``factor``, a linear ramp between; cos and sin are both
    multiplied by ``attention_factor`` (None: ``0.1 ln(factor) + 1``)."""

    rope_type: str = "yarn"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None

    def __post_init__(self) -> None:
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(
                f"rope_type {self.rope_type!r}: only 'default' and 'yarn' are described")
        if self.rope_type == "yarn" and not (
                self.factor >= 1.0 and self.original_max_position_embeddings > 0
                and self.beta_fast > self.beta_slow > 0):
            raise ValueError(
                "yarn needs factor >= 1, original_max_position_embeddings and "
                "beta_fast > beta_slow > 0")

    @property
    def scale(self) -> float:
        """What cos and sin are both multiplied by."""
        if self.rope_type == "default":
            return 1.0
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0

    def correction_range(self, dim: int, theta: float) -> tuple[int, int]:
        """``(low, high)``: the pairs of a head of ``dim`` between which the
        ramp runs (below ``low`` the plain frequency, above ``high`` the
        interpolated one), kept in ``[0, dim - 1]``."""
        def pair_of(turns: float) -> float:
            return (dim * math.log(self.original_max_position_embeddings / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        return (max(math.floor(pair_of(self.beta_fast)), 0),
                min(math.ceil(pair_of(self.beta_slow)), dim - 1))


@dataclass(frozen=True)
class ModelConfig:
    """A decoder architecture description.

    Without ``layer_types`` it is the Llama-family decoder it always was
    (RMSNorm, rotary GQA attention, SwiGLU) and builds the same programs.
    With ``layer_types`` (one of ``"attention"`` / ``"mamba"`` per layer)
    it is a hybrid stack in the GraniteMoeHybrid sense: every layer keeps
    the SwiGLU MLP, the mixer before it is attention or Mamba-2, and the
    ``mamba_*`` sizes, the position rule, the attention scale and the four
    multipliers below apply.
    With ``kv_lora_rank`` it is a DeepSeek-V3-style stack: every layer's
    mixer is latent attention (MLA: what a token leaves in the cache is ONE
    latent of ``kv_lora_rank + qk_rope_head_dim`` numbers a layer, not K and
    V per head), and with ``n_routed_experts`` every layer after the first
    ``first_k_dense`` replaces the SwiGLU of ``d_ff`` by routed experts of
    ``moe_d_ff`` and one shared expert of ``n_shared_experts x moe_d_ff``.
    With ``"gdn"`` among ``layer_types`` it is a Qwen3-Next-style hybrid:
    Gated DeltaNet (linear attention: a delta-rule state of ``gdn_d_k x
    gdn_d_v`` numbers a value head, the ``gdn_*`` sizes) beside gated
    attention (``attn_head_dim``, ``qk_norm``, ``partial_rotary_factor``,
    ``attn_output_gate``), norms that multiply by ``1 + w``
    (``norm_plus_one``), and with ``n_routed_experts`` the expert block as
    EVERY layer's FFN.  There ``n_routed_experts`` is the experts THIS
    device holds: ``[expert_first, expert_first + n_routed_experts)`` of the
    ``n_experts_total`` the gate scores (a share of an expert-parallel
    layer; what an absent expert would add to a token is left out).
    With ``"window"`` among ``layer_types`` it is a Cohere2-MoE-style stack:
    sliding-window GQA layers (a query sees the last ``sliding_window``
    keys; their K and V live in a RING of pages a row, ``CACHE_KINDS``)
    beside global ones, the position rule by kind
    (``position_embedding="rope_window"``: rotary on the window layers, none
    on the global; ``"rope"``: both kinds rotate, the window layers by the
    plain law, the global ones by it too or, with ``rope_scaling_global``, by
    that record's: a Mellum-2-style stack), the block the description names
    (``norm``, ``parallel_block``), and the expert block as every layer's
    FFN, held by share as above, its shared experts summed or averaged
    (``shared_expert_combine``).
    With ``"kda"`` among ``layer_types`` it is a Ling-3.0-style hybrid
    (``bailing_hybrid``): Kimi Delta Attention (the gated delta rule with a
    decay a key CHANNEL, bounded below by ``kda_lower_bound``; the ``gdn_*``
    sizes, one gate a head on its output) beside LATENT attention
    (``kv_lora_rank`` and its sizes: the one stack that has a recurrent
    state and a latent pool; ``attn_output_gate``: one sigmoid gate a head
    before ``W_o``), the first ``first_k_dense`` layers with a SwiGLU of
    ``d_ff`` whatever their mixer, the expert block after them, its gate
    choosing by GROUP (``n_group``, ``topk_group``) among all the experts it
    scores and the experts held by share as above.
    With ``"conv"`` among ``layer_types`` it is an LFM2-style hybrid
    (``lfm2_moe``): a gated short convolution (``B | C | x = h W_in``, a
    causal depthwise conv of ``conv_L_cache`` taps over ``B x``, times ``C``,
    ``W_out``: what a sequence leaves behind is the conv's last
    ``conv_L_cache - 1`` inputs a channel and nothing else) beside rotary GQA
    attention with normed heads (``qk_norm``), the first ``first_k_dense``
    layers with a SwiGLU of ``d_ff``, the expert block after them with no
    shared expert (``n_shared_experts`` 0) and the weights of a token's
    experts normalised over ``sum + 1e-6`` (``topk_norm_eps``).
    With ``"eva"`` as EVERY entry of ``layer_types`` it is an EvaByte-style
    decoder (``evabyte``): the pre-norm RMSNorm (``norm_plus_one``) + SwiGLU
    block of ``d_ff`` with a float32 residual stream, whose mixer is EVA
    attention (``eva.py``): a query sees the keys of its OWN aligned window of
    ``window_size`` positions exactly and, of every window before it, one
    pooled key and value a chunk of ``chunk_size`` positions, under one
    softmax; the head has ``num_pred_heads x vocab_size`` rows, of which the
    served token is drawn from the first ``vocab_size``.
    """

    name: str = "debug"
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 22
    n_heads: int = 32
    n_kv_heads: int = 4
    d_ff: int = 5632
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    # ---- hybrid stacks (all defaults = the dense decoder, unchanged) ----
    # one entry per layer, "attention" or "mamba"; () = every layer attends
    layer_types: tuple[str, ...] = ()
    # Mamba-2 mixer sizes (HF names: mamba_n_heads, mamba_d_head,
    # mamba_d_state, mamba_n_groups, mamba_d_conv, mamba_chunk_size);
    # d_inner = mamba_n_heads x mamba_d_head
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256  # the SSD block of the chunked scan
    # the SSM state's type: float32 because the recurrence is carried over
    # the whole sequence and a bfloat16 state rounds at every step
    state_dtype: str = "float32"
    # "rope" | "none" | "rope_window" (rotary on the window layers, none on the global)
    position_embedding: str = "rope"
    # attention scores are scaled by this; None = 1/sqrt(head_dim)
    attention_multiplier: float | None = None
    embedding_multiplier: float = 1.0  # x = embed[tokens] * this
    residual_multiplier: float = 1.0  # x = x + this * block(norm(x))
    logits_scaling: float = 1.0  # logits = head(x) / this
    # ---- latent attention (HF names; all defaults = K and V per head) ----
    # q = h W_q -> n_heads x (qk_nope | qk_rope); [c | k_rope] = h W_kva ->
    # kv_lora_rank | qk_rope, ONE a token; c = rmsnorm(c); [k_nope | v] =
    # c W_kvb -> n_heads x (qk_nope | v_head_dim); rope on the rope parts
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # HF builds kv_a_layernorm with its class default, not rms_norm_eps
    kv_norm_eps: float = 1e-6
    # ---- routed experts (HF names; all defaults = one SwiGLU a layer) ----
    n_routed_experts: int = 0
    n_experts_per_tok: int = 0  # num_experts_per_tok
    n_shared_experts: int = 0  # ONE shared SwiGLU of this many x moe_d_ff
    moe_d_ff: int = 0  # moe_intermediate_size
    first_k_dense: int = 0  # first_k_dense_replace: leading layers of d_ff
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # what a sigmoid gate adds to the sum of a token's chosen scores before
    # dividing by it, as each gate's family publishes it: 1e-20 in DeepSeek-V3
    # and its descendants, 1e-6 in lfm2_moe (whoever describes that gate says so)
    topk_norm_eps: float = 1e-20
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    # the shared expert's output times sigmoid(h . shared_gate) (Qwen3-Next)
    shared_expert_gate: bool = False
    # experts held by share: the gate scores n_experts_total (0 = the
    # n_routed_experts held, i.e. all), this device holds those from
    # expert_first on
    n_experts_total: int = 0
    expert_first: int = 0
    # ---- Gated DeltaNet beside gated attention (all defaults = as before) ----
    # a "gdn" layer: gdn_n_k_heads key heads of gdn_d_k, gdn_n_v_heads value
    # heads of gdn_d_v (HF: linear_num_key_heads, linear_key_head_dim,
    # linear_num_value_heads, linear_value_head_dim, linear_conv_kernel_dim)
    gdn_n_k_heads: int = 0
    gdn_n_v_heads: int = 0
    gdn_d_k: int = 0
    gdn_d_v: int = 0
    gdn_d_conv: int = 4
    gdn_chunk_size: int = 64  # the block of the chunkwise prefill
    attn_head_dim: int = 0  # a head's width where it is not d_model // n_heads
    partial_rotary_factor: float = 1.0  # rotary on the FIRST this share of a head
    qk_norm: bool = False  # RMSNorm over each query and key head before the rotation
    attn_output_gate: bool = False  # W_q gives q | gate a head; out = o * sigmoid(gate)
    norm_plus_one: bool = False  # every RMSNorm of the stack multiplies by (1 + w)
    # ---- window layers beside global ones (all defaults = as before) ----
    # a "window" layer's query at position i sees key j iff i - sliding_window < j <= i
    sliding_window: int = 0
    norm: str = "rms"  # "rms" | "layer" (mean and variance, a weight and no bias)
    parallel_block: bool = False  # x + Attn(h) + FFN(h), h = norm(x): ONE norm a layer
    # how the n_shared_experts' outputs meet: "sum" (one SwiGLU of n x moe_d_ff)
    # or "average" (that sum over n)
    shared_expert_combine: str = "sum"
    # how the GLOBAL layers' rotary frequencies differ from the window layers'
    # plain law (position_embedding "rope" in a window stack); None = they do not
    rope_scaling_global: RopeScaling | None = None
    # ---- Kimi Delta Attention beside latent attention (all defaults = as before) ----
    # a "kda" layer's log-decay, one a key channel: kda_lower_bound x sigmoid(
    # exp(A_log[head]) (a + dt_bias)), in [kda_lower_bound, 0) (FLA's safe gate)
    kda_lower_bound: float = -5.0
    # positions of a sub-block of the chunk form: inside one the decays factor
    # into two products, whose exponents stay under 16 x 5 = 80 (gdn.py)
    kda_sub_block: int = 16
    # expert_swiglu_limit_list / share_expert_swiglu_limit_list of the layers
    # HELD, one entry an expert layer (() = none published).  The clamp's form
    # is not published: a nonzero entry is refused below, no guessed clamp ships
    expert_swiglu_limits: tuple[float, ...] = ()
    shared_expert_swiglu_limits: tuple[float, ...] = ()
    # ---- a gated short convolution beside GQA attention (all defaults = as before) ----
    # a "conv" layer's causal depthwise conv has conv_L_cache taps (HF names:
    # conv_L_cache, conv_bias); its state is the last conv_L_cache - 1 inputs
    conv_L_cache: int = 0
    conv_bias: bool = False
    # ---- EVA attention (all defaults = as before) ----
    # an "eva" layer's query at position t sees the keys of window t // window_size
    # exactly (window_size * (t // window_size) <= j <= t) and one pooled key and
    # value for every chunk of chunk_size positions of the windows before it
    window_size: int = 0
    chunk_size: int = 0
    # the head's rows are num_pred_heads x vocab_size, head h predicting token
    # t + 1 + h; the served token is drawn from head 0
    num_pred_heads: int = 1

    def __post_init__(self) -> None:
        if self.kv_lora_rank:
            if self.layer_types and KDA not in self.layer_types:
                raise ValueError(
                    "latent attention in a hybrid stack is described beside Kimi "
                    'Delta Attention (layer_types with "kda") alone')
            if not (self.qk_nope_head_dim and self.qk_rope_head_dim and self.v_head_dim):
                raise ValueError(
                    "latent attention needs qk_nope_head_dim/qk_rope_head_dim/v_head_dim"
                )
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        elif KDA in self.layer_types:
            raise ValueError(
                "a Kimi Delta Attention hybrid's attention layers are latent "
                "attention (kv_lora_rank): K and V per head are not described")
        elif self.n_routed_experts and not {GDN, WINDOW, CONV} & set(self.layer_types):
            raise ValueError(
                "routed experts are described for the latent-attention stack "
                "(kv_lora_rank), for the Gated DeltaNet hybrid (layer_types "
                'with "gdn"), for the window stack (layer_types with '
                '"window") and for the short-convolution hybrid (layer_types '
                'with "conv") alone'
            )
        if self.n_routed_experts:
            if not (0 < self.n_experts_per_tok <= self.experts_scored and self.moe_d_ff):
                raise ValueError("routed experts need n_experts_per_tok and moe_d_ff")
            if not 0 <= self.first_k_dense < self.n_layers:
                raise ValueError("first_k_dense must leave at least one expert layer")
            if (self.scoring_func, self.topk_method) not in (
                    ("sigmoid", "noaux_tc"), ("softmax", "greedy"), ("sigmoid", "greedy")):
                raise ValueError(
                    f"router {self.scoring_func!r}/{self.topk_method!r}: only "
                    "sigmoid scores with noaux_tc or greedy selection and softmax "
                    "scores with greedy selection are described"
                )
            if (self.n_group, self.topk_group) != (1, 1):
                scored = self.experts_scored
                if (self.scoring_func, self.topk_method) != ("sigmoid", "noaux_tc"):
                    raise ValueError(
                        "group-limited routing (n_group > 1) is described for sigmoid "
                        "scores with noaux_tc selection alone")
                if not (1 <= self.topk_group <= self.n_group and scored % self.n_group == 0
                        and scored // self.n_group >= 2
                        and self.n_experts_per_tok <= self.topk_group * (scored // self.n_group)):
                    raise ValueError(
                        f"group-limited routing: {self.n_group} groups of the {scored} "
                        f"experts scored, {self.topk_group} kept, "
                        f"{self.n_experts_per_tok} a token do not fit (a group's score "
                        "is the sum of its two largest)")
            if not 0 <= self.expert_first <= self.experts_scored - self.n_routed_experts:
                raise ValueError(
                    f"experts held [{self.expert_first}, "
                    f"{self.expert_first + self.n_routed_experts}) are not among "
                    f"the {self.experts_scored} the gate scores"
                )
            if self.layer_types and self.first_k_dense and not (
                    {KDA, CONV} & set(self.layer_types)):
                raise ValueError(
                    "a hybrid stack's expert block is every layer's FFN (leading dense "
                    'layers are described for layer_types with "kda" or "conv" alone)')
            for name, limits in (("expert_swiglu_limits", self.expert_swiglu_limits),
                                 ("shared_expert_swiglu_limits",
                                  self.shared_expert_swiglu_limits)):
                if limits and len(limits) != self.n_moe_layers:
                    raise ValueError(
                        f"{name} names {len(limits)} layers, the expert layers held "
                        f"are {self.n_moe_layers}")
                if any(limits):
                    raise ValueError(
                        f"{name} {limits}: a nonzero swiglu limit in a held layer is "
                        "not described (the published configuration gives the limit "
                        "and not the clamp's form: no guessed clamp is served)")
        elif (self.n_experts_total or self.expert_first or self.shared_expert_gate
              or self.expert_swiglu_limits or self.shared_expert_swiglu_limits
              or self.shared_expert_combine != "sum"):
            raise ValueError(
                "n_experts_total, expert_first, shared_expert_gate, the swiglu limits "
                "and shared_expert_combine belong to routed experts (n_routed_experts)"
            )
        if self.shared_expert_combine not in ("sum", "average"):
            raise ValueError(f"unknown shared_expert_combine {self.shared_expert_combine!r}")
        if self.layer_types:
            if len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"n_layers is {self.n_layers}"
                )
            unknown = set(self.layer_types) - set(CACHE_KINDS)
            if unknown:
                raise ValueError(f"unknown layer types {sorted(unknown)}")
            kinds = set(self.layer_types) & set(RECURRENT_KINDS)
            if not kinds and not {WINDOW, EVA} & set(self.layer_types):
                raise ValueError(
                    "layer_types without a mamba, gdn, kda, conv, window or eva layer is "
                    "the dense decoder: leave it empty"
                )
            if len(kinds) > 1:
                raise ValueError(
                    "mamba, gdn, kda and conv layers in one stack are not described: "
                    "one recurrent kind a stack")
            if EVA in self.layer_types:
                if set(self.layer_types) != {EVA}:
                    raise ValueError(
                        "eva layers beside layers of another kind are not described")
                if not (self.chunk_size >= 1 and self.window_size >= self.chunk_size
                        and self.window_size % self.chunk_size == 0):
                    raise ValueError(
                        "eva layers need chunk_size >= 1 dividing window_size")
                if self.n_routed_experts or self.tie_embeddings or self.num_pred_heads < 1:
                    raise ValueError(
                        "an eva stack's FFN is one SwiGLU of d_ff a layer and its head "
                        "an untied matrix of num_pred_heads x vocab_size rows")
            elif WINDOW in self.layer_types:
                if kinds:
                    raise ValueError(
                        "window layers beside recurrent layers are not described")
                if self.sliding_window < 1:
                    raise ValueError("window layers need sliding_window")
                if not self.n_routed_experts:
                    raise ValueError(
                        "a window stack's FFN is the expert block "
                        "(n_routed_experts): one SwiGLU a layer is not described"
                    )
            elif MAMBA in kinds:
                if not (self.mamba_n_heads and self.mamba_d_head and self.mamba_d_state):
                    raise ValueError("mamba layers need mamba_n_heads/d_head/d_state")
                if self.mamba_n_heads % self.mamba_n_groups:
                    raise ValueError("mamba_n_groups must divide mamba_n_heads")
            elif CONV in kinds:
                if self.conv_L_cache < 2:
                    raise ValueError(
                        "conv layers need conv_L_cache >= 2 (the taps of the causal "
                        "depthwise conv: its state is the last conv_L_cache - 1 inputs)")
                if self.conv_bias:
                    raise ValueError(
                        "conv_bias: biases on a conv layer's in_proj, conv and out_proj "
                        "are not described (the published model has none)")
                if not self.n_routed_experts:
                    raise ValueError(
                        "a short-convolution hybrid's FFN after its leading dense layers "
                        "is the expert block (n_routed_experts): one SwiGLU in every "
                        "layer is not described")
            else:
                if not (self.gdn_n_k_heads and self.gdn_n_v_heads and self.gdn_d_k
                        and self.gdn_d_v):
                    raise ValueError("gdn layers need gdn_n_k_heads/n_v_heads/d_k/d_v")
                if self.gdn_n_v_heads % self.gdn_n_k_heads:
                    raise ValueError("gdn_n_k_heads must divide gdn_n_v_heads")
                if KDA in kinds and (self.gdn_n_k_heads != self.gdn_n_v_heads
                                     or not self.kda_lower_bound < 0
                                     or self.gdn_chunk_size % self.kda_sub_block):
                    raise ValueError(
                        "kda layers need as many key heads as value heads (a decay a "
                        "key channel of its own head), kda_lower_bound < 0 and "
                        "kda_sub_block dividing gdn_chunk_size")
                if not self.n_routed_experts:
                    raise ValueError(
                        "a Gated DeltaNet hybrid's FFN is the expert block "
                        "(n_routed_experts): one SwiGLU a layer is not described"
                    )
        elif (
            self.position_embedding != "rope"
            or self.attention_multiplier is not None
            or (self.embedding_multiplier, self.residual_multiplier,
                self.logits_scaling) != (1.0, 1.0, 1.0)
        ):
            raise ValueError(
                "position_embedding, attention_multiplier and the three "
                "multipliers belong to a hybrid stack (layer_types)"
            )
        if self.position_embedding not in ("rope", "none", "rope_window"):
            raise ValueError(
                f"unknown position_embedding {self.position_embedding!r}"
            )
        if WINDOW not in self.layer_types and (
            self.sliding_window or self.parallel_block or self.norm != "rms"
            or self.position_embedding == "rope_window"
        ):
            raise ValueError(
                "sliding_window, parallel_block, norm='layer' and "
                "position_embedding='rope_window' belong to the window stack "
                '(layer_types with "window")'
            )
        if self.rope_scaling_global is not None and not (
                WINDOW in self.layer_types and self.position_embedding == "rope"):
            raise ValueError(
                "rope_scaling_global belongs to a window stack whose kinds both "
                'rotate (layer_types with "window", position_embedding "rope")')
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if EVA not in self.layer_types and (
                self.window_size or self.chunk_size or self.num_pred_heads != 1):
            raise ValueError(
                'window_size, chunk_size and num_pred_heads belong to layer_types with "eva"')
        if GDN not in self.layer_types and (
            (self.qk_norm and CONV not in self.layer_types)
            or (self.attn_output_gate and KDA not in self.layer_types)
            or (self.norm_plus_one and EVA not in self.layer_types)
            or self.partial_rotary_factor != 1.0
            or (self.attn_head_dim and WINDOW not in self.layer_types)
        ):
            raise ValueError(
                "attn_head_dim, qk_norm, attn_output_gate, norm_plus_one and "
                'partial_rotary_factor belong to the Gated DeltaNet hybrid '
                '(layer_types with "gdn"; attn_head_dim to the window stack too, '
                'norm_plus_one to layer_types with "eva" too, '
                'attn_output_gate to layer_types with "kda" too, qk_norm to '
                'layer_types with "conv" too)'
            )
        if CONV not in self.layer_types and (self.conv_L_cache or self.conv_bias):
            raise ValueError(
                'conv_L_cache and conv_bias belong to layer_types with "conv"')
        if self.rotary_dim % 2:
            raise ValueError("the rotated part of a head must be even (rotary pairs)")

    @property
    def head_dim(self) -> int:
        """Width of a query (and key) head."""
        if self.latent:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def rotary_dim(self) -> int:
        """The leading part of a head the rotary embedding turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent(self) -> bool:
        """Is what a token leaves in the cache one latent (MLA)?"""
        return self.kv_lora_rank > 0

    @property
    def moe(self) -> bool:
        return self.n_routed_experts > 0

    @property
    def experts_scored(self) -> int:
        """Experts the gate scores: all of the layer's, held here or not."""
        return self.n_experts_total or self.n_routed_experts

    @property
    def expert_share(self) -> bool:
        """Does this device hold only some of the experts the gate scores?"""
        return self.experts_scored > self.n_routed_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense if self.moe else 0

    @property
    def n_dense_layers(self) -> int:
        """Layers whose FFN is the one SwiGLU of ``d_ff``."""
        return self.n_layers - self.n_moe_layers

    # What the cache keeps of a token, a layer: two arrays ("sides") of
    # ``cache_heads x cache_dims[side]`` numbers.  K and V per head, or the
    # two parts of the one latent that every head shares: c (kv_lora_rank,
    # key AND value) and k_rope (qk_rope_head_dim), kept apart so that the
    # wide part is whole lane tiles (576 = 4.5 x 128 is not).  The page pool,
    # the decode ring, the prefill scratch and the page accounting read
    # these, never ``n_kv_heads x head_dim``.
    @property
    def cache_heads(self) -> int:
        return 1 if self.latent else self.n_kv_heads

    @property
    def cache_dims(self) -> tuple[int, int]:
        if self.latent:
            return self.kv_lora_rank, self.qk_rope_head_dim
        return self.head_dim, self.head_dim

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        """Bytes one token adds to the cache over all its layers."""
        return self.n_kv_layers * self.cache_heads * sum(self.cache_dims) * itemsize

    @property
    def recurrent(self) -> bool:
        """Does a sequence carry state besides K and V (Mamba-2 or Gated
        DeltaNet layers)?"""
        return any(t in RECURRENT_KINDS for t in self.layer_types)

    @property
    def gdn(self) -> bool:
        """Are the recurrent layers a gated delta rule (Gated DeltaNet, or
        Kimi Delta Attention: ``kda``), else Mamba-2?"""
        return any(t in DELTA_RULE_KINDS for t in self.layer_types)

    @property
    def kda(self) -> bool:
        """Does the delta rule forget by key CHANNEL (Kimi Delta Attention)?"""
        return KDA in self.layer_types

    @property
    def shortconv(self) -> bool:
        """Are the recurrent layers gated short convolutions (``conv``: a
        state that is the conv tail alone)?"""
        return CONV in self.layer_types

    @property
    def expert_hybrid(self) -> bool:
        """Is this the hybrid stack whose FFN is the expert block, its
        attention layers' heads normed where ``qk_norm`` says so: a delta
        rule or a short convolution as the recurrent mixer
        (``model._expert_hybrid_stack``)?"""
        return self.gdn or self.shortconv

    @property
    def recurrent_kind(self) -> str:
        if self.kda:
            return "Kimi Delta Attention"
        if self.shortconv:
            return "gated short convolution"
        return "Gated DeltaNet" if self.gdn else "Mamba-2"

    @property
    def n_mamba_layers(self) -> int:
        return sum(t == MAMBA for t in self.layer_types)

    @property
    def n_recurrent_layers(self) -> int:
        return sum(t in RECURRENT_KINDS for t in self.layer_types)

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep K and V: all of them unless ``layer_types``
        says otherwise."""
        return self.n_layers - self.n_recurrent_layers

    @property
    def eva(self) -> bool:
        """Is every layer's mixer EVA attention (an aligned window ring AND
        summary pages a layer)?"""
        return EVA in self.layer_types

    @property
    def windowed(self) -> bool:
        """Are the pages kept BY CACHE KIND (a ring of pages a row beside
        pages it keeps): sliding-window layers, or EVA layers?"""
        return WINDOW in self.layer_types or self.eva

    @property
    def window_layer_ids(self) -> tuple[int, ...]:
        """The indices, among the layers that keep K and V, of those with a
        RING of pages a row (where their rows lie in the prefill scratch and
        the decode ring): the window layers; every layer of an EVA stack."""
        kv = [t for t in self.layer_types if t not in RECURRENT_KINDS]
        return tuple(i for i, t in enumerate(kv) if t in (WINDOW, EVA))

    @property
    def global_layer_ids(self) -> tuple[int, ...]:
        """Those with pages a row keeps for its whole life: the global
        layers; every layer of an EVA stack too (its summaries)."""
        kv = [t for t in self.layer_types if t not in RECURRENT_KINDS]
        return tuple(i for i, t in enumerate(kv) if t != WINDOW)

    @property
    def n_window_layers(self) -> int:
        return len(self.window_layer_ids)

    @property
    def n_global_layers(self) -> int:
        """Layers with pages a row keeps: K and V of EVERY token, or an EVA
        layer's summaries."""
        return len(self.global_layer_ids)

    @property
    def attention_window(self) -> int:
        """Positions a ring has to hold: the sliding or the aligned window."""
        return self.window_size if self.eva else self.sliding_window

    def summary_entries(self, tokens: int) -> int:
        """Summary entries a row of ``tokens`` positions can come to hold in
        an EVA layer: one a COMPLETE chunk."""
        return tokens // self.chunk_size

    def window_ring_pages(self, page_size: int, ahead: int) -> int:
        """Pages in a row's ring of a window layer: the window, what one
        dispatch writes ``ahead`` of the newest key that is read (its decode
        steps; a prompt's chunks stay in the prefill scratch until they land),
        and one page more, because the window's oldest key and the newest
        write each lie anywhere in their page."""
        return -(-(self.attention_window + ahead) // page_size) + 1

    @property
    def layer_period(self) -> tuple[str, ...]:
        """The shortest pattern ``layer_types`` repeats: the stack scans
        over its repeats, so compile time follows the period, not the depth."""
        types = self.layer_types
        for p in range(1, len(types) + 1):
            if len(types) % p == 0 and types == types[:p] * (len(types) // p):
                return types[:p]
        return types

    @property
    def stack_plan(self) -> tuple[int, tuple[str, ...]]:
        """(layers unrolled at the head of the stack, the period the rest
        repeats): the leading dense layers are unrolled, and as many more as
        leave the FEWEST layers to trace (head + period).  Without leading
        dense layers: ``(0, layer_period)``."""
        types = self.layer_types
        if not (self.moe and self.first_k_dense):
            return 0, self.layer_period
        best = None
        for head in range(self.first_k_dense, len(types)):
            rest = types[head:]
            period = next(rest[:p] for p in range(1, len(rest) + 1)
                          if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
            if best is None or head + len(period) < best[0] + len(best[1]):
                best = (head, period)
        return best

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mamba_d_in_proj(self) -> int:
        """Width of the fused input projection: z | xBC | dt."""
        return self.mamba_d_inner + self.mamba_conv_dim + self.mamba_n_heads

    @property
    def gdn_key_dim(self) -> int:
        return self.gdn_n_k_heads * self.gdn_d_k

    @property
    def gdn_value_dim(self) -> int:
        return self.gdn_n_v_heads * self.gdn_d_v

    @property
    def gdn_conv_dim(self) -> int:
        """Channels the causal conv runs over: q | k | v."""
        return 2 * self.gdn_key_dim + self.gdn_value_dim

    @property
    def gdn_d_in_proj(self) -> int:
        """Width of the fused input projection: q | k | v | z | b | a; of a
        Kimi Delta Attention layer q | k | v | z | b with ONE z a head (the
        decay's ``a``, one a key channel, is a matrix of its own: ``w_alpha``)."""
        if self.kda:
            return self.gdn_conv_dim + 2 * self.gdn_n_v_heads
        return self.gdn_conv_dim + self.gdn_value_dim + 2 * self.gdn_n_v_heads

    def recurrent_state_shapes(self, rows: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """Shapes of the state pair ``rows`` sequences carry: the matrix
        state ``[L, rows, heads, d_head, d_state]`` (Mamba-2's SSM state, or
        the delta rule's ``S`` of d_k x d_v a value head) and the conv tail
        ``[L, d_conv - 1, rows, channels]``.  A short-convolution layer's
        pair has an EMPTY matrix side (no number a layer): its state is the
        tail of ``d_model`` channels alone."""
        if self.shortconv:
            return (
                (self.n_recurrent_layers, rows, 0),
                (self.n_recurrent_layers, self.conv_L_cache - 1, rows, self.d_model),
            )
        if self.gdn:
            return (
                (self.n_recurrent_layers, rows, self.gdn_n_v_heads, self.gdn_d_k, self.gdn_d_v),
                (self.n_recurrent_layers, self.gdn_d_conv - 1, rows, self.gdn_conv_dim),
            )
        return (
            (self.n_mamba_layers, rows, self.mamba_n_heads, self.mamba_d_head,
             self.mamba_d_state),
            (self.n_mamba_layers, self.mamba_d_conv - 1, rows, self.mamba_conv_dim),
        )

    def recurrent_state_bytes(self, rows: int) -> int:
        """Bytes ``rows`` sequences' matrix and conv state take on the device."""
        itemsize = {"float32": 4, "bfloat16": 2}
        matrix, conv = (math.prod(shape) for shape in self.recurrent_state_shapes(rows))
        return matrix * itemsize[self.state_dtype] + conv * itemsize.get(self.dtype, 2)

    @property
    def param_count(self) -> int:
        """Approximate parameter count (for memory planning)."""
        embed = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        if self.kda:
            H, r, Hv = self.n_heads, self.kv_lora_rank, self.gdn_n_v_heads
            mla = (
                self.d_model * H * self.head_dim  # W_q
                + self.d_model * (r + self.qk_rope_head_dim) + r  # W_kva, its norm
                + r * H * (self.qk_nope_head_dim + self.v_head_dim)  # W_kvb
                + H * self.v_head_dim * self.d_model  # W_o
                + (self.d_model * H if self.attn_output_gate else 0)  # W_z
            )
            kda = (
                self.d_model * self.gdn_d_in_proj + self.d_model * self.gdn_key_dim  # W_alpha
                + self.gdn_value_dim * self.d_model + self.gdn_conv_dim * self.gdn_d_conv
                + Hv + self.gdn_key_dim + self.gdn_d_v  # A_log, dt_bias, the gated norm
            )
            expert = 3 * self.d_model * self.moe_d_ff
            moe = (
                self.d_model * self.experts_scored + self.experts_scored  # gate, its bias
                + (self.n_routed_experts + self.n_shared_experts) * expert
            )
            return (
                embed + self.d_model + 2 * self.n_layers * self.d_model
                + self.n_kv_layers * mla + self.n_recurrent_layers * kda
                + self.n_dense_layers * 3 * self.d_model * self.d_ff + self.n_moe_layers * moe
            )
        if self.latent:
            H, r = self.n_heads, self.kv_lora_rank
            attention = (
                self.d_model * H * self.head_dim  # W_q
                + self.d_model * (r + self.qk_rope_head_dim) + r  # W_kva, its norm
                + r * H * (self.qk_nope_head_dim + self.v_head_dim)  # W_kvb
                + H * self.v_head_dim * self.d_model  # W_o
            )
            dense = 3 * self.d_model * self.d_ff
            expert = 3 * self.d_model * self.moe_d_ff
            moe = (
                self.n_routed_experts * (self.d_model + 1)  # gate, its bias
                + (self.n_routed_experts + self.n_shared_experts) * expert
            )
            return (
                embed + self.d_model + self.n_layers * (attention + 2 * self.d_model)
                + self.n_dense_layers * dense + self.n_moe_layers * moe
            )
        attention = (
            # q (and its output gate), k, v, o
            self.d_model * self.n_heads * self.head_dim * (2 if self.attn_output_gate else 1)
            + 2 * self.d_model * self.n_kv_heads * self.head_dim
            + self.n_heads * self.head_dim * self.d_model
            + (2 * self.head_dim if self.qk_norm else 0)
        )
        if self.shortconv:
            mixer = 4 * self.d_model * self.d_model + self.d_model * self.conv_L_cache
            moe = (
                self.d_model * self.experts_scored + self.experts_scored  # gate, its bias
                + (self.n_routed_experts + self.n_shared_experts)
                * 3 * self.d_model * self.moe_d_ff
            )
            return (
                embed + self.d_model + 2 * self.n_layers * self.d_model
                + self.n_kv_layers * attention + self.n_recurrent_layers * mixer
                + self.n_dense_layers * 3 * self.d_model * self.d_ff + self.n_moe_layers * moe
            )
        if self.eva:
            # the seven matrices, the two norms, phi and mu a layer; the final
            # norm, the embedding and the head's num_pred_heads x vocab_size rows
            layer = (attention + 3 * self.d_model * self.d_ff + 2 * self.d_model
                     + 2 * self.n_kv_heads * self.head_dim)
            return (self.n_layers * layer + self.d_model + self.vocab_size * self.d_model
                    + self.num_pred_heads * self.vocab_size * self.d_model)
        if self.windowed:
            expert = 3 * self.d_model * self.moe_d_ff
            ffn = (
                self.d_model * self.experts_scored
                + (self.n_routed_experts + self.n_shared_experts) * expert
            )
            norms = self.d_model * (1 if self.parallel_block else 2)
            return embed + self.d_model + self.n_layers * (attention + ffn + norms)
        if self.gdn:
            mixer = (
                self.d_model * self.gdn_d_in_proj + self.gdn_value_dim * self.d_model
                + self.gdn_conv_dim * self.gdn_d_conv + 2 * self.gdn_n_v_heads + self.gdn_d_v
            )
            expert = 3 * self.d_model * self.moe_d_ff
            ffn = (
                self.d_model * self.experts_scored  # the gate scores every expert
                + (self.n_routed_experts + self.n_shared_experts) * expert
                + (self.d_model if self.shared_expert_gate else 0)
            )
            return (
                embed + self.d_model + self.n_layers * (ffn + 2 * self.d_model)
                + self.n_kv_layers * attention + self.n_recurrent_layers * mixer
            )
        # mlp: gate, up, down; the two norms
        mlp = 3 * self.d_model * self.d_ff + 2 * self.d_model
        total = embed + self.n_layers * mlp + self.n_kv_layers * attention + self.d_model
        if self.recurrent:
            total += self.n_mamba_layers * (
                self.d_model * self.mamba_d_in_proj
                + self.mamba_d_inner * self.d_model
                + self.mamba_conv_dim * (self.mamba_d_conv + 1)
                + 3 * self.mamba_n_heads
                + self.mamba_d_inner
            )
        return total


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs (off unless ``RuntimeConfig.speculative``
    is set).

    Decode is memory-bandwidth-bound: a normal decode step reads every
    weight to emit ONE token per request.  Speculation drafts ``k``
    candidate tokens cheaply, then a single **verify** dispatch scores all
    k+1 positions against the KV cache — the full weight-read is amortized
    over every accepted token.  Greedy output is token-exact vs
    non-speculative greedy; sampled output keeps the target-model
    distribution via rejection sampling (``sampler.spec_accept_slots``).

    Two drafters behind one seam (:mod:`calfkit_tpu.inference.spec`):

    - ``draft is None`` → **n-gram prompt lookup**: propose the
      continuation of the most recent earlier occurrence of the sequence
      tail within prompt + generated history.  No extra weights, no extra
      device work — the agent-serving workload (tool-call JSON, repeated
      instructions, quoted context) is exactly where it hits.
    - ``draft`` set → a second, smaller **draft model** proposes greedily
      from its own KV cache; loaded through the same init/loader/sharding
      path as the target (pass ``draft_params`` to the engine for real
      checkpoints).
    """

    k: int = 4  # drafted tokens per verify wave (verify scores k+1)
    # n-gram lookup: longest/shortest tail length to match (longer tails
    # first: more context, fewer false continuations)
    ngram_max: int = 3
    ngram_min: int = 1
    # the draft-model seam: a second, smaller architecture.  None → n-gram.
    draft: "ModelConfig | None" = None


@dataclass(frozen=True)
class RuntimeConfig:
    """Serving-engine knobs (reference analog: the model config block the
    TPU build adds to the provider, SURVEY.md §5 config notes)."""

    max_batch_size: int = 32
    max_seq_len: int = 2048
    # "dense" = [L, B, K, max_seq, hd] per-slot rows (fastest when B×S fits
    # HBM); "paged" = block-table pool, memory ∝ requested footprints — the
    # layout that fits 128 concurrent 8B streams on one 16 GB chip
    kv_layout: str = "dense"
    page_size: int = 64  # tokens per KV page (pallas paged-attention block)
    max_pages_per_seq: int = 0  # 0 → derived from max_seq_len
    # total pages in the paged pool (incl. the reserved trash page);
    # 0 → max_batch_size × pages_per_seq + 1 (no oversubscription)
    num_kv_pages: int = 0
    tp: int = 1  # tensor-parallel degree (mesh 'tp' axis size)
    dp: int = 1  # data/batch-parallel replicas of the serving engine
    decode_steps_per_dispatch: int = 8  # tokens generated per scheduler tick
    prefill_chunk: int = 512  # prompts pad/bucket to multiples of this
    # admission-wave width cap: more requests per prefill dispatch fills a
    # drained batch in fewer device round trips (burst TTFT), at the cost
    # of a larger prefill scratch (wave x bucket KV) and one extra jit
    # variant per power-of-two step.  Waves stay power-of-two sized.
    max_prefill_wave: int = 8
    # interleave long-prompt prefills with decode: an admission advances one
    # prefill_chunk per scheduler pass instead of blocking decode for the
    # whole bucket (vLLM-style chunked prefill; inter-token latency of
    # active streams stays bounded by one chunk + one tick)
    chunked_prefill: bool = False
    # what the PAGED DECODE READ runs, the one attention computation with a
    # Pallas kernel (all else is XLA): "auto" takes the kernel on a TPU,
    # paged KV, one device, a head and page shape it reads in place, else
    # XLA; "xla" is the reference; "pallas" / "pallas_interpret" are for
    # tests and bring-up and are refused outside that rule
    # (InferenceEngine._resolved_attn_impl)
    attention_impl: str = "auto"
    # long-context lane: prompts that cannot fit a short-lane slot
    # (len >= max_seq_len) are served via sequence-parallel ring prefill
    # over an `sp` mesh of ALL the engine's devices + context-parallel
    # decode against the still-sharded prefix (greedy; one request at a
    # time — the whole mesh cooperates on it)
    long_context: bool = False
    long_new_cap: int = 512  # max new tokens a long request may generate
    long_max_prompt: int = 0  # prompt-length ceiling; 0 → 8 x max_seq_len
    # long-lane budget negotiation: by default a request whose
    # max_new_tokens exceeds long_new_cap FAULTS with a typed error (the
    # caller's budget is a contract, not a suggestion); True restores the
    # explicit opt-in behavior of clamping to the cap with a warning
    long_clamp_new_tokens: bool = False
    # decode attention window buckets (each is one jit specialization);
    # sparse buckets = few compiles, dense = tighter HBM reads
    window_buckets: tuple[int, ...] = (256, 1024, 4096, 16384)
    # persistent XLA compile cache, placed by compile_cache.py's one rule
    # ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); False only
    # switches it off
    compilation_cache: bool = True
    # automatic prefix caching (vLLM-APC analog): requests whose prompt
    # shares a full-page-aligned prefix with an earlier request reuse its
    # KV pages instead of re-prefilling them — the agent-serving win
    # (same instructions/history re-sent every turn).  Requires
    # kv_layout="paged" AND chunked_prefill=True (reuse seeds the chunk
    # lane's scratch and starts at the reused offset).
    prefix_cache: bool = False
    # speculative decoding: None = off (zero change to the decode path);
    # a SpecConfig turns every decode tick into draft-k + one batched
    # verify dispatch scoring k+1 positions per sequence (see SpecConfig)
    speculative: "SpecConfig | None" = None
    # overlapped execution (double-buffered decode dispatch): launch decode
    # dispatch N+1 BEFORE syncing dispatch N's token block, so host-side
    # fan-out / stop scanning / admission prep run while the device is
    # busy and the inter-dispatch device-idle bubble goes to ~zero.  Stop
    # and generation-bound detection move onto the device as a per-row
    # done mask; a row that retires mid-block rides exactly one extra
    # in-flight dispatch (its pad tokens are discarded, its slot/pages
    # free only after that dispatch lands — one-dispatch-late, never
    # early).  False = the lockstep reference path (sync-then-fan-out),
    # byte-identical token streams either way.
    overlap_dispatch: bool = True
    # ragged unified prefill+decode waves (ISSUE 6; the Ragged Paged
    # Attention design, arXiv:2604.15464): the scheduler's admission lane
    # and decode lane collapse into ONE — each tick enqueues a single
    # fused dispatch carrying the active decode rows AND the inflight
    # admission wave's next prefill chunk, so a half-empty decode wave
    # absorbs prefill work in the compute it would otherwise idle.
    # Engages when chunked_prefill=True (the chunk lane is the absorption
    # substrate) and overlap_dispatch=True (ragged launches ride the
    # double-buffered path); otherwise the engine runs the legacy
    # bifurcated schedule, which is also the byte-identical parity oracle
    # (ragged_waves=False).
    ragged_waves: bool = True
    # token budget per ragged dispatch: decode contributes
    # active_rows x decode_steps_per_dispatch query tokens, an absorbed
    # chunk contributes wave_rows x prefill_chunk.  Bounds per-dispatch
    # latency (absorbed prefill stretches the fused dispatch) AND caps
    # admission-wave width at formation (occupancy-driven admission).
    # 0 = auto: max_batch_size x steps + max_prefill_wave x chunk — a
    # budget that never second-guesses the existing admission bounds;
    # set explicitly to trade absorption for steadier inter-token latency.
    ragged_token_budget: int = 0
    # device-side retirement needs each request's stop-token set as a
    # fixed-shape row: the per-slot table holds this many entries.  A
    # short-lane request with more stop tokens than this is rejected when
    # device-side retirement is in use (overlap_dispatch or speculative);
    # the lockstep host path (overlap_dispatch=False, no speculation)
    # keeps scanning arbitrary-size sets on the host.
    max_stop_tokens: int = 8
    # overload protection (ISSUE 5): per-lane bound on QUEUED (not yet
    # admitted) requests — at the bound, generate() sheds the submit with
    # a typed EngineOverloadedError instead of letting queue wait grow
    # silently.  Applied per lane (short `_pending`+carry, long
    # `_long_pending`).  0 = unbounded (the pre-ISSUE-5 behavior).
    max_pending: int = 0
    # per-request token-delivery bound: a consumer that stops draining its
    # stream accumulates whole dispatch-blocks in GenRequest.out forever —
    # past this many undrained queue items the scheduler stall-cancels the
    # request through the ordinary cancellation path (delivery_stalled
    # counter; the consumer sees a typed EngineOverloadedError when it
    # finally resumes).  0 = unbounded.
    max_out_blocks: int = 0
    # engine wedge watchdog (ISSUE 9): with work pending, no dispatch
    # landing for this many seconds (on the cancellation.wall_clock seam)
    # declares the engine WEDGED — the "hung device grant"
    # state, where the decode thread blocks inside a device sync forever
    # and the scheduler loop with it.  Tripping dumps the flight
    # recorder, flips readiness (and the heartbeat advert) false, and
    # faults every pending request with a typed RETRIABLE
    # EngineWedgedError so callers fail over instead of burning their
    # deadlines.  If a landing ever arrives after the trip, the engine
    # un-wedges and resumes serving.  0 = off (the default: a first
    # dispatch legitimately blocks for a whole XLA compile, which can
    # take minutes on cold caches — enable with a threshold comfortably
    # above your worst compile time, or after warmup).
    watchdog_stall_s: float = 0.0
    # flight recorder: capacity (events) of the engine's in-memory ring
    # journal of scheduler events (admission, waves, page alloc/free,
    # spec/overlap dispatches, retirement, faults).  Rounds up to a power
    # of two; dumps to JSONL on engine fault / SIGUSR2 / the /flightrec
    # endpoint; appends are O(1) lock-free (what they cost a dispatch on
    # the chip's host: PERF.md section 6).  0 disables recording entirely.
    flightrec_events: int = 4096
    # capacity observatory (ISSUE 19): capacity (samples) of the engine's
    # occupancy timeline ring — one numeric sample per dispatch landing
    # (pages in use/free, prefix residency, active/pending, tokens per
    # dispatch, analytic HBM bytes/token).  Rounds up to a power of two;
    # dumps to JSONL next to flight-recorder dumps and serves the
    # /capacity endpoint; appends are O(1) lock-free.  0 (the default)
    # disables the sampler entirely — page ATTRIBUTION (the ledger behind
    # stats_snapshot()["capacity"] and the advert's headroom fields) is
    # always on for paged engines: it rides the existing alloc/free/evict
    # sites at O(1).
    capacity_samples: int = 0
    # weight-only quantization: "int8" halves decode HBM traffic and fits
    # Llama-3-8B on one 16 GB chip; "int4" (packed nibbles, group-128
    # scales) halves the weight stream again (~4 GB for 8B — margin for
    # KV pages / batch width); None = native dtype
    quantization: str | None = None

    def pages_per_seq(self) -> int:
        if self.max_pages_per_seq:
            return self.max_pages_per_seq
        return -(-self.max_seq_len // self.page_size)

    def pool_pages(self) -> int:
        """Total pages in the paged pool (page 0 is the trash page)."""
        if self.num_kv_pages:
            return self.num_kv_pages
        return self.max_batch_size * self.pages_per_seq() + 1


# --------------------------------------------------------------------------- #
# presets
# --------------------------------------------------------------------------- #

PRESETS: dict[str, ModelConfig] = {
    # tiny config for unit tests / CI — compiles in seconds on CPU
    "debug": ModelConfig(
        name="debug",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=256,
    ),
    # BASELINE config 2: TinyLlama-1.1B (HF: TinyLlama/TinyLlama-1.1B-Chat)
    "tinyllama-1.1b": ModelConfig(
        name="tinyllama-1.1b",
        vocab_size=32000,
        d_model=2048,
        n_layers=22,
        n_heads=32,
        n_kv_heads=4,
        d_ff=5632,
        rope_theta=10000.0,
        max_seq_len=2048,
    ),
    # BASELINE config 5 / north star: Llama-3-8B (HF: meta-llama/Meta-Llama-3-8B)
    "llama-3-8b": ModelConfig(
        name="llama-3-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        max_seq_len=8192,
    ),
    # IBM Granite 4.0-H Micro (HF: ibm-granite/granite-4.0-h-micro,
    # GraniteMoeHybrid with no routed experts): 36 Mamba-2 layers and 4
    # attention layers without rotary embedding, the shared MLP in each
    "granite-4.0-h-micro": ModelConfig(
        name="granite-4.0-h-micro",
        vocab_size=100352,
        d_model=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        d_ff=8192,
        norm_eps=1e-5,
        max_seq_len=131072,
        tie_embeddings=True,
        layer_types=((MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4,
        mamba_n_heads=64,
        mamba_d_head=64,
        mamba_d_state=128,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_chunk_size=256,
        position_embedding="none",
        attention_multiplier=0.015625,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
    ),
    # Kimi-VL-A3B-Instruct's language decoder (HF: moonshotai/
    # Kimi-VL-A3B-Instruct, text_config: DeepseekV3ForCausalLM at
    # Moonlight's widths): latent attention in every layer, one dense layer,
    # then 64 routed experts with 6 a token and one shared expert of 2 x 1408.
    # Text only: the vision tower is not described here.
    "kimi-vl-a3b-instruct": ModelConfig(
        name="kimi-vl-a3b-instruct",
        vocab_size=163840,
        d_model=2048,
        n_layers=27,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,
        rope_theta=800000.0,
        norm_eps=1e-5,
        max_seq_len=131072,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_routed_experts=64,
        n_experts_per_tok=6,
        n_shared_experts=2,
        moe_d_ff=1408,
        first_k_dense=1,
        routed_scaling_factor=2.446,
    ),
    # Qwen3-Next-80B-A3B-Instruct's language model (HF: Qwen/
    # Qwen3-Next-80B-A3B-Instruct, qwen3_next): 36 Gated DeltaNet layers and
    # 12 gated-attention layers (period L L L A), 512 routed experts with 10
    # a token and one gated shared expert in EVERY layer.  The multi-token
    # prediction module is not described here.  All 512 experts: what one
    # device holds of them is n_routed_experts / expert_first of a share.
    "qwen3-next-80b-a3b-instruct": ModelConfig(
        name="qwen3-next-80b-a3b-instruct",
        vocab_size=151936,
        d_model=2048,
        n_layers=48,
        n_heads=16,
        n_kv_heads=2,
        d_ff=5120,
        rope_theta=10000000.0,
        norm_eps=1e-6,
        max_seq_len=262144,
        layer_types=((GDN,) * 3 + (ATTENTION,)) * 12,
        gdn_n_k_heads=16,
        gdn_n_v_heads=32,
        gdn_d_k=128,
        gdn_d_v=128,
        gdn_d_conv=4,
        attn_head_dim=256,
        partial_rotary_factor=0.25,
        qk_norm=True,
        attn_output_gate=True,
        norm_plus_one=True,
        n_routed_experts=512,
        n_experts_per_tok=10,
        n_shared_experts=1,
        moe_d_ff=512,
        scoring_func="softmax",
        topk_method="greedy",
        shared_expert_gate=True,
    ),
    # the same kind at toy size, for the tests: 2 periods of L L L A, 8
    # experts scored of which this device holds 4 (share 1 of 2)
    "debug-gdn-moe": ModelConfig(
        name="debug-gdn-moe",
        vocab_size=128,
        d_model=32,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        rope_theta=10000000.0,
        norm_eps=1e-6,
        max_seq_len=256,
        dtype="float32",
        layer_types=((GDN,) * 3 + (ATTENTION,)) * 2,
        gdn_n_k_heads=2,
        gdn_n_v_heads=4,
        gdn_d_k=8,
        gdn_d_v=8,
        gdn_d_conv=4,
        gdn_chunk_size=8,
        attn_head_dim=16,
        partial_rotary_factor=0.25,
        qk_norm=True,
        attn_output_gate=True,
        norm_plus_one=True,
        n_routed_experts=4,
        n_experts_total=8,
        expert_first=4,
        n_experts_per_tok=3,
        n_shared_experts=1,
        moe_d_ff=16,
        scoring_func="softmax",
        topk_method="greedy",
        shared_expert_gate=True,
    ),
    # command-a-plus-05-2026's text decoder (HF: CohereLabs/
    # command-a-plus-05-2026, cohere2_moe): 24 sliding-window layers (rotary on
    # interleaved pairs, window 4096) and 8 global layers without positions
    # (period W W W G), one LayerNorm and a parallel block a layer, 128
    # sigmoid-routed experts with 8 a token and 4 shared experts averaged in
    # EVERY layer.  All 128 experts: what one device holds is a share.
    "command-a-plus-05-2026": ModelConfig(
        name="command-a-plus-05-2026",
        vocab_size=262144,
        d_model=4096,
        n_layers=32,
        n_heads=128,
        n_kv_heads=8,
        d_ff=4096,
        rope_theta=50000.0,
        norm_eps=1e-5,
        max_seq_len=131072,
        tie_embeddings=True,
        layer_types=((WINDOW,) * 3 + (ATTENTION,)) * 8,
        position_embedding="rope_window",
        attn_head_dim=128,
        sliding_window=4096,
        norm="layer",
        parallel_block=True,
        n_routed_experts=128,
        n_experts_per_tok=8,
        n_shared_experts=4,
        moe_d_ff=4096,
        scoring_func="sigmoid",
        topk_method="greedy",
        shared_expert_combine="average",
    ),
    # the same kind at toy size, for the tests: 2 periods of W W W G, a window
    # of 24, 8 experts scored of which this device holds 4 (share 0 of 2)
    "debug-window-moe": ModelConfig(
        name="debug-window-moe",
        vocab_size=128,
        d_model=32,
        n_layers=8,
        n_heads=8,
        n_kv_heads=2,
        d_ff=16,
        rope_theta=50000.0,
        norm_eps=1e-5,
        max_seq_len=256,
        dtype="float32",
        tie_embeddings=True,
        layer_types=((WINDOW,) * 3 + (ATTENTION,)) * 2,
        position_embedding="rope_window",
        attn_head_dim=8,
        sliding_window=24,
        norm="layer",
        parallel_block=True,
        n_routed_experts=4,
        n_experts_total=8,
        expert_first=0,
        n_experts_per_tok=3,
        n_shared_experts=2,
        moe_d_ff=16,
        scoring_func="sigmoid",
        topk_method="greedy",
        shared_expert_combine="average",
    ),
    # Ling-3.0-flash-VL's language decoder (HF: inclusionAI/Ling-3.0-flash-VL,
    # bailing_hybrid): 35 Kimi Delta Attention layers and 7 latent-attention
    # layers (layer_group_size 6: K K K K K M), two leading dense layers, then
    # 512 sigmoid-routed experts chosen by group (8 groups, 4 kept, 8 a token)
    # and one shared expert.  Text only: the tower is not described here, nor
    # the extra prediction layer.  All 512 experts: what one device holds is a
    # share (n_routed_experts / expert_first).  The published
    # expert_swiglu_limit_list is nonzero from layer 34 on and the clamp's form
    # is unpublished: this preset carries no limits (random weights); the loader
    # hands a checkpoint's lists over (expert_swiglu_limits) and a nonzero one
    # in a held layer is refused there with that reason.
    "ling-3.0-flash-vl": ModelConfig(
        name="ling-3.0-flash-vl",
        vocab_size=157184,
        d_model=2560,
        n_layers=42,
        n_heads=32,
        n_kv_heads=32,
        d_ff=6144,
        rope_theta=6000000.0,
        norm_eps=1e-6,
        max_seq_len=131072,
        layer_types=((KDA,) * 5 + (ATTENTION,)) * 7,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        attn_output_gate=True,
        gdn_n_k_heads=32,
        gdn_n_v_heads=32,
        gdn_d_k=128,
        gdn_d_v=128,
        gdn_d_conv=4,
        kda_lower_bound=-5.0,
        n_routed_experts=512,
        n_experts_per_tok=8,
        n_shared_experts=1,
        moe_d_ff=768,
        first_k_dense=2,
        routed_scaling_factor=2.5,
        n_group=8,
        topk_group=4,
    ),
    # the same kind at toy size, for the tests: two periods K K M
    # (layer_group_size 3), the first layer's FFN dense (so the first period
    # is the stack's unrolled head, the other its scan); 16 experts scored
    # in 4 groups of which 2 are kept, this device holding group 1 (share 1 of 4)
    "debug-kda-mla-moe": ModelConfig(
        name="debug-kda-mla-moe",
        vocab_size=128,
        d_model=32,
        n_layers=6,
        n_heads=4,
        n_kv_heads=4,
        d_ff=48,
        rope_theta=6000000.0,
        norm_eps=1e-6,
        max_seq_len=256,
        dtype="float32",
        layer_types=(KDA, KDA, ATTENTION) * 2,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=4,
        v_head_dim=8,
        attn_output_gate=True,
        gdn_n_k_heads=4,
        gdn_n_v_heads=4,
        gdn_d_k=8,
        gdn_d_v=8,
        gdn_d_conv=4,
        gdn_chunk_size=8,
        kda_sub_block=4,
        n_routed_experts=4,
        n_experts_total=16,
        expert_first=4,
        n_experts_per_tok=3,
        n_shared_experts=1,
        moe_d_ff=16,
        first_k_dense=1,
        routed_scaling_factor=2.5,
        n_group=4,
        topk_group=2,
    ),
    # LFM2-8B-A1B (HF: LiquidAI/LFM2-8B-A1B, lfm2_moe): 18 gated short
    # convolutions (3 taps: two numbers a channel of state) and 6 rotary GQA
    # layers with normed heads, two leading dense layers of 7,168, then 32
    # sigmoid-routed experts of 1,792 with 4 a token, a bias on the choice alone
    # and NO shared expert; the embedding tied.
    "lfm2-8b-a1b": ModelConfig(
        name="lfm2-8b-a1b",
        vocab_size=65536,
        d_model=2048,
        n_layers=24,
        n_heads=32,
        n_kv_heads=8,
        d_ff=7168,
        rope_theta=1000000.0,
        norm_eps=1e-5,
        max_seq_len=128000,
        tie_embeddings=True,
        layer_types=tuple(
            ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24)),
        conv_L_cache=3,
        qk_norm=True,
        n_routed_experts=32,
        n_experts_per_tok=4,
        moe_d_ff=1792,
        first_k_dense=2,
        routed_scaling_factor=1.0,
        topk_norm_eps=1e-6,
    ),
    # the same kind at toy size, for the tests: the published pattern cut as
    # the cell cuts it (c c A c | c c A c | c c A c: a head of 4, two periods in
    # the scan), two dense layers, 8 experts with 3 a token, float32
    "debug-lfm2-moe": ModelConfig(
        name="debug-lfm2-moe",
        vocab_size=128,
        d_model=32,
        n_layers=12,
        n_heads=4,
        n_kv_heads=2,
        d_ff=48,
        rope_theta=1000000.0,
        norm_eps=1e-5,
        max_seq_len=256,
        dtype="float32",
        tie_embeddings=True,
        layer_types=(CONV, CONV, ATTENTION, CONV) * 3,
        conv_L_cache=3,
        qk_norm=True,
        n_routed_experts=8,
        n_experts_per_tok=3,
        moe_d_ff=16,
        first_k_dense=2,
        routed_scaling_factor=1.0,
        topk_norm_eps=1e-6,
    ),
    # Mellum2-12B-A2.5B-Instruct (HF: JetBrains/Mellum2-12B-A2.5B-Instruct,
    # mellum): 21 sliding-window layers (window 1,024, the plain rotation) and 7
    # global layers whose rotation is YaRN (factor 16 over 8,192 positions),
    # period W W W G, a sequential RMSNorm block with two norms a layer, 64
    # softmax-routed experts with 8 a token and no shared one in EVERY layer,
    # an untied head.  The rotation pairs a head's halves (rotate_half).  Its
    # multi-token-prediction head is not described (skipped at load).
    "mellum2-12b-a2.5b-instruct": ModelConfig(
        name="mellum2-12b-a2.5b-instruct",
        vocab_size=98304,
        d_model=2304,
        n_layers=28,
        n_heads=32,
        n_kv_heads=4,
        d_ff=896,
        rope_theta=500000.0,
        norm_eps=1e-6,
        max_seq_len=131072,
        tie_embeddings=False,
        layer_types=((WINDOW,) * 3 + (ATTENTION,)) * 7,
        position_embedding="rope",
        rope_scaling_global=RopeScaling(
            rope_type="yarn", factor=16.0, original_max_position_embeddings=8192,
            beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782),
        attn_head_dim=128,
        sliding_window=1024,
        n_routed_experts=64,
        n_experts_per_tok=8,
        moe_d_ff=896,
        scoring_func="softmax",
        topk_method="greedy",
    ),
    # the same kind at toy size, for the tests: 2 periods of W W W G, a window
    # of 24 (shorter than the tests' chunk), 8 experts with 3 a token, ALL held,
    # an untied head, YaRN over an original context of 64 (shorter than
    # max_seq_len, so the tests stand on both sides of it)
    "debug-mellum": ModelConfig(
        name="debug-mellum",
        vocab_size=128,
        d_model=32,
        n_layers=8,
        n_heads=8,
        n_kv_heads=2,
        d_ff=16,
        rope_theta=10000.0,
        norm_eps=1e-6,
        max_seq_len=256,
        dtype="float32",
        tie_embeddings=False,
        layer_types=((WINDOW,) * 3 + (ATTENTION,)) * 2,
        position_embedding="rope",
        rope_scaling_global=RopeScaling(
            rope_type="yarn", factor=4.0, original_max_position_embeddings=64,
            beta_fast=8.0, beta_slow=1.0),
        attn_head_dim=8,
        sliding_window=24,
        n_routed_experts=8,
        n_experts_per_tok=3,
        moe_d_ff=16,
        scoring_func="softmax",
        topk_method="greedy",
    ),
    # EvaByte (HF: EvaByte/EvaByte, model_type evabyte): a byte-level decoder of
    # 32 layers whose every mixer is EVA attention (Zheng et al., arXiv:2302.04542):
    # an exact aligned window of 2,048 bytes beside one pooled key and value for
    # every 16 bytes behind it; 32 heads of 128 (one query a KV head), SwiGLU of
    # 11,008, norms that multiply by 1 + w, a vocabulary of 320 and a head of
    # 8 x 320 rows (head h predicts byte t + 1 + h; head 0 is served)
    "evabyte": ModelConfig(
        name="evabyte",
        vocab_size=320,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        d_ff=11008,
        rope_theta=100000.0,
        norm_eps=1e-5,
        max_seq_len=32768,
        tie_embeddings=False,
        layer_types=(EVA,) * 32,
        norm_plus_one=True,
        window_size=2048,
        chunk_size=16,
        num_pred_heads=8,
    ),
    # the same kind at toy size, for the tests: windows of 32 in chunks of 4
    # (8 summaries a window), 2 prediction heads, contexts of 4+ windows
    "debug-evabyte": ModelConfig(
        name="debug-evabyte",
        vocab_size=64,
        d_model=32,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        d_ff=48,
        rope_theta=100000.0,
        norm_eps=1e-5,
        max_seq_len=256,
        dtype="float32",
        tie_embeddings=False,
        layer_types=(EVA,) * 3,
        norm_plus_one=True,
        window_size=32,
        chunk_size=4,
        num_pred_heads=2,
    ),
}


def preset(name: str, **overrides: object) -> ModelConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg
