"""The routed-expert FFN of a DeepSeek-V3-style layer, inside the ``mlp`` scope.

    s = sigmoid(float32(h) W_g)                    E scores a token, float32
    chosen = top-k of (s + b)                      b: e_score_correction_bias
    w = s[chosen] / (sum s[chosen] + eps) * routed_scaling_factor
                                                   eps: 1e-20 here, 1e-6 as lfm2_moe
                                                   publishes it (config.topk_norm_eps)
    y = sum_e w_e E_e(h) + Shared(h)               every E_e a SwiGLU of moe_d_ff

The bias moves the CHOICE and never the weights; the weights are the
unbiased scores, normalised over the chosen (``norm_topk_prob``) and scaled
once.  Every token routed to an expert is computed by it: there is no
capacity and no dropped assignment, under any imbalance.

The expert products take one of two forms, chosen from the shapes alone
(:func:`dense_form`), and the two must agree (a decode step's take a THIRD
where the engine resolved it: the step kernel, below).  Each wins on its own side:
one scan over the 6 expert layers of 64 experts of 2048 x 1408, 6 a token,
on a v5e, in ms a layer (the gate, the products and the shared expert;
PERF.md section 6, PRs 31 and 34):

    tokens      8     64    256    512   1024   2048   4096
    dense    1.55   1.56   1.73   3.23   6.40  12.78  26.80
    grouped  1.80   3.86   5.66   6.07   6.83   8.60  12.41

- *dense* (decode steps, and chunks to the limit :func:`dense_form` gives): every
  expert for every row, times a weight that is zero outside the chosen.
  To some hundreds of rows it costs what reading the experts costs (1.1 GB
  a layer: 1.35 ms at 819 GB/s), and it needs no sort or gather;
- *grouped* (wider chunks): the token-expert pairs sorted by expert and
  ``lax.ragged_dot`` over the groups, so only the pairs routed are
  multiplied (the v5e's compiler lowers it to one kernel over the sorted
  rows), 64/6 times fewer FLOPs than the dense form.  The kernel takes the
  expert STACK, flattened to ``[Lm E, ., .]``, and the groups of every
  layer, all empty but this layer's (:func:`experts_grouped`): a kernel of
  the compiler's own reads no ``dynamic-slice`` in place, so a layer
  sliced out of the stack was written out and read again before every
  product.  The ``grouped`` rows of PRs 31 and 33 (4.91 .. 15.81 here, 2.67
  .. 9.76 below) held that copy, 2.7 and 2.0 ms a layer (1.1 and 0.81 GB
  each way at 819 GB/s) whatever the tokens; the 1,024 or 384 groups cost
  the kernel under 2% against one layer's experts handed to it whole.

Layout (stacked on axis 0 over the expert layers):
    router [Lm, D, E scored]     the gate, in the activations' type; its
                                 product is float32 at HIGHEST precision
    router_bias [Lm, E] float32  e_score_correction_bias (noaux_tc alone)
    w_gate, w_up [Lm, E, D, Fe]; w_down [Lm, E, Fe, D]      routed experts (the E held)
    s_gate, s_up [Lm, D, Fs]; s_down [Lm, Fs, D]            the shared expert
    shared_gate [Lm, D]          the shared expert's sigmoid gate (shared_expert_gate)
    mlp_norm [Lm, D]

A Qwen3-Next-style layer (``scoring_func`` "softmax") differs in the gate and
in the shared expert, and may hold its experts by SHARE:

    p = softmax(float32(h) W_g)                    over ALL the experts scored
    chosen = top-k of p;  w = p[chosen] / sum p[chosen]      (norm_topk_prob)
    y = sum_{e held} w_e E_e(h) + sigmoid(h . w_sg) Shared(h)

The gate scores every expert of the layer (``config.experts_scored``) and a
token's k are chosen among all of them; the products run over the experts
THIS device holds (``[expert_first, expert_first + n_routed_experts)``), so
what an absent expert would add to a token is left out, in both forms: the
other shares' devices add theirs, and the weights are NOT renormalised over
the held.  A Cohere2-MoE-style layer (command-a-plus: ``scoring_func``
"sigmoid" with ``topk_method`` "greedy") is the first gate without its bias
(``s = sigmoid``, the k largest ``s`` chosen, ``w = s / sum of the chosen``),
held by share the same way, and its ``n_shared_experts`` are AVERAGED
(``shared_expert_combine``): the one shared SwiGLU of ``n x moe_d_ff`` times
``1 / n``.  The two forms at Qwen3-Next's shape (128 held of 512 scored,
2048 x 512, 10 a token; one scan over 8 layers on a v5e, ms a layer, as
above; PERF.md section 6, PRs 33 and 34):

    tokens      8     64    256    512   1024   2048   4096
    dense    1.11   1.12   1.28   2.32   4.59   9.13  18.92
    grouped  0.26   1.08   2.68   2.86   3.41   4.35   7.31

Without the copy the two cross near 512 tokens at this shape and near
1,024 at the first (the limits below were set on the PR 31 and 33 rows and
are not moved by this reading: a change of form is a change of program,
timed in its cell).

The dense products are spelled WEIGHTS FIRST (``"edf,td->etf"``), and the
spelling is part of the program (PR 45).  ``jnp.einsum`` lowers the two
spellings to opposite ``dot_general``s: rows first (``"td,edf->etf"``,
through PR 44) to the experts by the rows, ``[E, Fe, T]`` and a transpose,
weights first to the rows by the experts, ``[T, E, Fe]`` and a transpose.
The first the TPU compiler, from 128 rows on (a whole lane tile of rows;
at 64 and 96 it does not, at any shape), compiles as it stands: the experts
its input and the ROWS its kernel, the result with the rows minor, and it
wants the experts with the hidden size minor (``bf16[E,2048,Fe]{1,2,0}``).
Inside a dispatch the layer's experts are a slice of a stack that no step
changes: the change of layout is hoisted out of the scan over layers AND
the loop over steps, into one COPY OF THE WHOLE STACK a dispatch.  That is
what three PRs met as a property of a shape: Qwen3-Next's ragged program with a dense chunk of
1,024 copied ``w_gate`` and ``w_up`` whole (PR 33: 2 x 2.15 GB of
temporaries, ~10 ms of traffic a dispatch), Ling's and LFM2's DECODE
programs at their 128 rows did (PRs 40 and 44: 7.4 and 6.4 GB of
temporaries, programs that do not fit the chip), and Kimi's, Qwen3-Next's
and command-a-plus's decode programs never did, at 64, 64 and 32 rows.
Weights first, the compiled product keeps the rows as its input and the
experts as its kernel in the layout they are stored in (hidden size in,
expert width out; the result ``[E, T, Fe]``): no decode or ragged program
of the five held shapes holds an array of a stack's shape among its
temporaries (``tests/test_tpu_compile.py`` compiles the contrast for the
described v5e at every held shape at 128 rows, so the spelling is not
tidied back; a raw ``dot_general`` of the experts by the rows that keeps
``[E, Fe, T]`` copies as the old einsum did).  Alone on a v5e, nested as a
dispatch nests them, ms a layer at 128 rows of (32, 2048, 1792): rows first
1.04 with its copy, weights first 0.95, the grouped form 2.59; reading the
layer's 0.70 GB takes 0.86.  At 64 and 32 rows the two spellings compile to
one product and time the same (1.478 / 1.477 at Kimi's shape, 1.076 / 1.077
at Qwen3-Next's, 2.255 / 2.255 at command-a-plus's; PERF.md section 6, PR 45).

Which form a shape's products take is then a matter of timing alone:

- to ``_DENSE_MAX_TOKENS`` (512) every shape takes the dense form: from 256
  rows to 512 it wins in both tables, at a decode step's 64 rows it wins
  (2.5x) or ties, and it is every decode step's form where the step kernel
  is not (at 8 rows the grouped form without its copy reads few experts and
  is the faster: not used, a decode step has all its slots' rows);
- beyond it a shape takes the dense form only to the limit its row of
  ``_DENSE_TO_THE_CROSSING`` gives: its two forms TIMED on the chip, in its
  cell, and its programs COMPILED at full depth.

*The step kernel* (:func:`experts_step`, ``pallas_moe.moe_step_pallas``; PR
53) is the dense form's sum over the experts a decode step's REAL rows HIT,
read in place out of the stack.  Of experts held by share a step's rows
spread over every expert the gate scores and hit only some of the held
(in their cells command-a-plus ~2.5 of 16 at ~16 active rows, its seeded routing
skewed; Qwen3-Next ~84 of 128; Ling ~27
of 64 behind its group gate), the dense form streams them ALL, and the
grouped form reads the hit alone behind a sort, a gather, a scatter and a
fixed cost.  The kernel walks the hit list (``stats``' ``tokens > 0``, from
the ``valid`` rows alone: an inactive slot's choices cost no read) by
scalar-prefetched ids and streams each hit expert's matrices in width tiles
through VMEM, every row through every hit expert, masked by its weight (to
128 rows a weight is under the chip's ridge, so the rows are free).  Alone
on a v5e, nested as a dispatch nests them, ms a layer (PERF.md section 6, PR
53; ``hit`` at 819 GB/s is the least time to read the hit experts):

    shape, rows                 hit   dense  grouped   step   hit at 819 GB/s
    (16, 4096, 4096), 32          3   2.158     -     0.438   0.369
                                  9   2.202   1.863   1.224   1.106
                                 16   2.178     -     2.138   1.967
    (128, 2048, 512), 64         85   1.077   0.940   0.724   0.653
                                128   1.077     -     1.077   0.983
    (64, 2560, 768), 128         10   1.041   0.518   0.184   0.144
                                 29   1.033   1.322   0.475   0.418
                                 64   1.025   2.799   1.012   0.922

It streams at 87-92% of the peak at any hit count and ties the dense form
with EVERY expert hit, so its cost follows the hit count by itself.  Who
takes it is decided once, at construction
(``InferenceEngine._resolved_moe_step_impl``, under the one
``attention_impl``): a TPU, one device, experts held by SHARE, at most
``_STEP_MAX_TOKENS`` slots, matrices of whole lane tiles; ``moe_ffn`` takes
the answer as ``step_impl`` from the decode step alone, so chunks keep the
form ``dense_form`` gives them.  Experts held whole are hit whole (Kimi 59
of 64, LFM2 and Mellum2 all) and keep the dense form.

A Ling-3.0-style layer (``n_group`` > 1: DeepSeek-V3's group-limited choice)
is the first gate with one step before its top-k, over ALL the experts
scored whatever share is held: the ``s + b`` of ``n_group`` equal groups, a
group's score the sum of its two largest, the ``topk_group`` best groups
kept, the k largest of THEIR experts chosen; the weights are the unbiased
``s`` of the chosen as before.  Held one group a device, a token's experts
lie on at most ``topk_group`` devices, and a row reaches this one only if a
held group is among its kept (``moe_rows_in_held_groups``).

Counters (``stats``; what :class:`EngineStats` sums as ``moe_*``): a pair
``(counts [Lm, E] int32, hit [] int32)``, with a third ``absent [] int32``
(the real tokens' assignments to experts held elsewhere) when the experts
are held by share, that a dispatch carries through
its steps and returns beside its tokens (and a fourth, ``in_held_groups`` []
int32, where the gate chooses by group: the real rows, summed over layers,
whose kept groups include one held here).  ``counts[m, e]`` is the tokens
layer ``m`` sent to expert ``e``; ``hit`` the distinct experts a call had
to read, summed over layers (and, by the caller, over steps).  Only REAL
tokens count (``valid``): a padded position of a chunk and an inactive row
of a decode step are computed, as padding is everywhere in this program,
and counted nowhere.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from calfkit_tpu.inference.config import ModelConfig

Params = dict[str, Any]
_HI = lax.Precision.HIGHEST  # the gate's float32 product: no bf16 passes
# the dense form's limit in tokens for ANY shape: the decode steps' rows and
# narrow chunks (the module's text)
_DENSE_MAX_TOKENS = 512
# the step kernel's limit in rows: under the chip's ridge (197 TFLOP/s / 819 GB/s =
# 240 rows a weight) every row goes through every hit expert for the price of the
# weights' stream; timed to 128 rows, the most a cell's slots hold (PR 53)
_STEP_MAX_TOKENS = 128
# (experts held, hidden, expert width) -> the limit of a shape whose two forms were
# TIMED on the chip as its cell runs them.  (64, 2048, 1408): the first table's
# crossing, between 1,024 and 2,048.  A shape without a row takes the default,
# LFM2's (32, 2048, 1792) among them (every expert is hit every step there: dense
# 0.95, grouped 2.59; at a chunk's 512 tokens 2.00 / 2.91, at 1,024 4.19 / 3.27, so
# the default's 512 is its crossing too; PERF.md section 6, PR 45)
_DENSE_TO_THE_CROSSING = {(64, 2048, 1408): 1536}


def init_moe_params(config: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """Random expert-layer leaves: every matrix at 1/sqrt(fan_in), the
    norm at 1, ``router_bias`` (``e_score_correction_bias``) zero, as an
    untrained model has it."""
    c = config
    Lm, D, E, Fe = c.n_moe_layers, c.d_model, c.n_routed_experts, c.moe_d_ff
    Fs = c.n_shared_experts * Fe
    keys = jax.random.split(key, 8)

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    out = {
        "router": mat(keys[0], (Lm, D, c.experts_scored), D),
        "w_gate": mat(keys[2], (Lm, E, D, Fe), D),
        "w_up": mat(keys[3], (Lm, E, D, Fe), D),
        "w_down": mat(keys[4], (Lm, E, Fe, D), Fe),
        "mlp_norm": jnp.zeros((Lm, D), dtype) if c.norm_plus_one else jnp.ones((Lm, D), dtype),
    }
    if c.topk_method == "noaux_tc":
        out["router_bias"] = jnp.zeros((Lm, c.experts_scored), jnp.float32)
    if Fs:
        out.update(
            s_gate=mat(keys[5], (Lm, D, Fs), D),
            s_up=mat(keys[6], (Lm, D, Fs), D),
            s_down=mat(keys[7], (Lm, Fs, D), Fs),
        )
    if c.shared_expert_gate:
        out["shared_gate"] = mat(keys[1], (Lm, D), D)
    return out


def moe_stats_init(config: ModelConfig) -> tuple[jax.Array, ...]:
    """Zeroed counters of one dispatch (see the module's text)."""
    zero = jnp.zeros((), jnp.int32)
    counts = jnp.zeros((config.n_moe_layers, config.n_routed_experts), jnp.int32)
    if config.n_group > 1:
        return (counts, zero, zero, zero)
    return (counts, zero, zero) if config.expert_share else (counts, zero)


def kept_groups(pick: jax.Array, config: ModelConfig) -> jax.Array:
    """The group-limited step of the choice: ``pick`` [T, E scored] (the
    scores the choice is made on) -> [T, n_group] bool, the ``topk_group``
    groups whose two largest scores sum highest."""
    with jax.named_scope("groups"):
        T, E = pick.shape
        grouped = pick.reshape(T, config.n_group, E // config.n_group)
        best = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)  # [T, n_group]
        _, kept = lax.top_k(best, config.topk_group)
        return jnp.any(
            kept[..., None] == jnp.arange(config.n_group, dtype=kept.dtype), axis=-2)


def _logits(h: jax.Array, lp: Params) -> jax.Array:
    """The gate's product [T, E scored]: float32 at HIGHEST precision."""
    return jnp.einsum(
        "td,de->te", h.astype(jnp.float32), lp["router"].astype(jnp.float32),
        precision=_HI, preferred_element_type=jnp.float32,
    )


def rows_in_held_groups(h: jax.Array, lp: Params, config: ModelConfig) -> jax.Array:
    """[T] bool: does one of the row's KEPT groups lie (in part) among the
    experts held here?  The gate's scores again, which the compiler shares
    with :func:`route`'s (the same product of the same operands)."""
    c = config
    kept = kept_groups(jax.nn.sigmoid(_logits(h, lp)) + lp["router_bias"].astype(jnp.float32), c)
    per = c.experts_scored // c.n_group
    first, last = c.expert_first // per, (c.expert_first + c.n_routed_experts - 1) // per
    return jnp.any(kept[:, first:last + 1], axis=-1)


def route(
    h: jax.Array,  # [T, D]
    lp: Params,
    config: ModelConfig,
) -> tuple[jax.Array, jax.Array]:
    """The gate → (chosen [T, k] int32, weights [T, k] float32): the
    product, the scores and the top-k in float32, as published."""
    c = config
    logits = _logits(h, lp)
    if c.scoring_func == "softmax":  # over ALL the experts scored; no bias, no scaling
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = lax.top_k(probs, c.n_experts_per_tok)
        if c.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), weights * c.routed_scaling_factor
    scores = jax.nn.sigmoid(logits)
    # "greedy" (Cohere2-MoE): the k largest scores themselves, no bias on the choice
    pick = scores + lp["router_bias"].astype(jnp.float32) if "router_bias" in lp else scores
    if c.n_group > 1:  # only the kept groups' experts stand for the top-k
        pick = jnp.where(
            jnp.repeat(kept_groups(pick, c), c.experts_scored // c.n_group, axis=-1),
            pick, -jnp.inf)
    _, chosen = lax.top_k(pick, c.n_experts_per_tok)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)  # the UNBIASED scores
    if c.norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + c.topk_norm_eps)
    return chosen.astype(jnp.int32), weights * c.routed_scaling_factor


def dense_form(tokens: int, config: ModelConfig) -> bool:
    """Which form the expert products take for ``tokens`` rows: dense to
    ``_DENSE_MAX_TOKENS``, and to where the two measured times cross for a
    shape measured AND compiled (the module's text); grouped beyond."""
    shape = (config.n_routed_experts, config.d_model, config.moe_d_ff)
    return tokens <= _DENSE_TO_THE_CROSSING.get(shape, _DENSE_MAX_TOKENS)


def _swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array) -> jax.Array:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts_dense(h: jax.Array, onehot: jax.Array, weights: jax.Array, lp: Params) -> jax.Array:
    """Every expert on every row, times a weight that is zero outside the
    chosen → [T, D].  The weight goes onto the hidden activation, so the
    down projection contracts experts and width in ONE product."""
    with jax.named_scope("group"):
        gates = jnp.sum(onehot * weights[..., None], axis=1)  # [T, E] float32
    with jax.named_scope("experts"):
        # the WEIGHTS first: rows first, from 128 rows on, a dispatch copies the whole
        # stack (the module's text; tests/test_tpu_compile.py compiles the contrast)
        g = jnp.einsum("edf,td->etf", lp["w_gate"], h)
        u = jnp.einsum("edf,td->etf", lp["w_up"], h)
        act = jax.nn.silu(g) * u  # [E, T, Fe]
    with jax.named_scope("combine"):
        act = (act.astype(jnp.float32) * gates.T[:, :, None]).astype(h.dtype)
        return jnp.einsum("etf,efd->td", act, lp["w_down"])


def experts_step(
    h: jax.Array, onehot: jax.Array, weights: jax.Array, valid: jax.Array | None,
    stack: Params, m: Any, interpret: bool,
) -> jax.Array:
    """A decode step's rows through the experts its REAL rows hit, read in
    place out of the stack (``pallas_moe.moe_step_pallas``) -> [T, D]: the
    dense form's sum with a row that is not ``valid`` weighted zero, so its
    choices cost no read (its output is discarded by the caller)."""
    from calfkit_tpu.inference.pallas_moe import moe_step_pallas

    with jax.named_scope("group"):
        real = onehot if valid is None else onehot & valid.reshape(-1, 1, 1)
        gates = jnp.sum(real * weights[..., None], axis=1)  # [T, E] float32
        hit = jnp.any(real, axis=(0, 1))  # [E]: what ``stats`` counts as hit
    return moe_step_pallas(h, gates, hit, stack, m, interpret=interpret)  # named ``experts``


def _stack_dot(x: jax.Array, stack: jax.Array, sizes_all: jax.Array) -> jax.Array:
    """One ragged product over the FLATTENED stack ``[Lm E, ., .]``: the
    reshape is free and nothing is sliced, so the kernel reads the experts
    where they lie."""
    return lax.ragged_dot(x, stack.reshape(-1, *stack.shape[2:]), sizes_all)


def experts_grouped(
    h: jax.Array, chosen: jax.Array, onehot: jax.Array, weights: jax.Array,
    stack: Params,  # the STACKED leaves: w_gate, w_up [Lm, E, D, Fe]; w_down [Lm, E, Fe, D]
    m: Any,  # this layer's index in the stack (traced)
    share: bool = False,
) -> jax.Array:
    """Only the token-expert pairs routed: sorted by expert, one ragged
    product a projection over the groups, unsorted, weighted, summed over a
    token's experts → [T, D].  A group is as long as its expert's tokens
    are many: nothing is cut to a capacity.  Of experts held by ``share``,
    ``onehot`` [T, k, E held] is all zero for a pair whose expert is held
    elsewhere: those pairs sort behind the last group, which no group's
    product reaches, and are left out of the sum over a token's experts.

    The products take the whole stack and ``m``: the groups are the
    ``Lm E`` experts of every layer, all empty but this layer's ``E``, so
    no array of a layer's experts is made (a slice of the stack handed to
    the compiler's kernel is a COPY: 0.8-1.1 GB a layer written and read
    again, the module's text)."""
    T, k = chosen.shape
    Lm, E = stack["w_gate"].shape[:2]
    with jax.named_scope("group"):
        if share:
            held = jnp.any(onehot, axis=-1)  # [T, k]
            flat = jnp.where(held, jnp.argmax(onehot, axis=-1), onehot.shape[-1]).reshape(T * k)
        else:
            flat = chosen.reshape(T * k)
        order = jnp.argsort(flat, stable=True)  # sorted pair -> flat pair
        sizes = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)  # [E] pairs an expert
        sizes_all = lax.dynamic_update_slice(
            jnp.zeros((Lm * E,), jnp.int32), sizes, (jnp.asarray(m, jnp.int32) * E,))
        rows = h[order // k]  # [T k, D]
    with jax.named_scope("experts"):
        act = jax.nn.silu(_stack_dot(rows, stack["w_gate"], sizes_all)) * _stack_dot(
            rows, stack["w_up"], sizes_all)
        out = _stack_dot(act, stack["w_down"], sizes_all)  # [T k, D], sorted
    with jax.named_scope("combine"):
        back = jnp.argsort(order)  # flat pair -> sorted pair
        out = out[back].reshape(T, k, -1).astype(jnp.float32)
        out = out * weights[..., None]
        if share:
            out = jnp.where(held[..., None], out, 0.0)
        return jnp.sum(out, axis=1).astype(h.dtype)


def moe_ffn(
    h: jax.Array,  # [B, S, D], normed
    lp: Params,  # ONE expert layer's leaves
    config: ModelConfig,
    stats: "tuple[jax.Array, jax.Array] | None" = None,
    valid: jax.Array | None = None,  # [B, S] bool: the real tokens
    m: Any = 0,  # this layer's index among the expert layers (traced)
    stack: Params | None = None,  # the STACKED group ``lp`` is layer ``m`` of
    step_impl: str = "xla",  # a decode step's products (InferenceEngine._resolved_moe_step_impl)
) -> tuple[jax.Array, Any]:
    """``sum_e w_e E_e(h) + Shared(h)`` → ([B, S, D], stats).  The grouped
    products and the step kernel read ``stack`` at ``m`` where it lies
    (:func:`experts_grouped`, :func:`experts_step`); without one, ``lp`` is
    a stack of one layer."""
    B, S, D = h.shape
    E = config.n_routed_experts
    share = config.expert_share
    flat = h.reshape(B * S, D)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            chosen, weights = route(flat, lp, config)
            # one-hot over the HELD experts only: an absent one matches none
            onehot = chosen[..., None] == (  # [T, k, E]
                jnp.arange(E, dtype=jnp.int32) + config.expert_first if share
                else jnp.arange(E, dtype=jnp.int32))
            if stats is not None:
                real = onehot if valid is None else onehot & valid.reshape(-1, 1, 1)
                tokens = jnp.sum(real, axis=(0, 1), dtype=jnp.int32)  # [E]
                counts, hit, *absent = stats
                stats = (counts.at[m].add(tokens), hit + jnp.sum(tokens > 0, dtype=jnp.int32))
                by_group = config.n_group > 1
                if share or by_group:
                    n_real = B * S if valid is None else jnp.sum(valid, dtype=jnp.int32)
                    stats = (*stats, absent[0] + n_real * chosen.shape[-1] - jnp.sum(tokens))
                if by_group:
                    reach = rows_in_held_groups(flat, lp, config)
                    if valid is not None:
                        reach = reach & valid.reshape(-1)
                    stats = (*stats, absent[1] + jnp.sum(reach, dtype=jnp.int32))
        if step_impl.startswith("pallas") and stack is not None and B * S <= _STEP_MAX_TOKENS:
            y = experts_step(
                flat, onehot, weights, valid, stack, m, step_impl == "pallas_interpret")
        elif dense_form(B * S, config):
            y = experts_dense(flat, onehot, weights, lp)
        else:
            if stack is None:
                stack, m = {n: lp[n][None] for n in ("w_gate", "w_up", "w_down")}, 0
            y = experts_grouped(flat, chosen, onehot, weights, stack, m, share)
        if "s_gate" in lp:
            with jax.named_scope("shared"):
                shared = _swiglu(flat, lp["s_gate"], lp["s_up"], lp["s_down"])
                if "shared_gate" in lp:
                    gate = jax.nn.sigmoid(jnp.einsum(
                        "td,d->t", flat.astype(jnp.float32),
                        lp["shared_gate"].astype(jnp.float32), precision=_HI))
                    shared = (shared.astype(jnp.float32) * gate[:, None]).astype(shared.dtype)
                if config.shared_expert_combine == "average":
                    # the n shared experts ARE one SwiGLU of n x moe_d_ff (columns
                    # side by side, the down rows stacked): their mean is one scale
                    shared = (shared.astype(jnp.float32) / config.n_shared_experts).astype(
                        shared.dtype)
                y = y + shared
    return y.reshape(B, S, D), stats
