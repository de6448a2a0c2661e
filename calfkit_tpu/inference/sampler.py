"""Token sampling: greedy / temperature / top-k / top-p, jit-compatible.

Two entry points:

- :func:`sample` — one static ``SamplingParams`` bundle for the whole batch
  (trace-time branching; the cheap path for uniform workloads);
- :func:`sample_slots` — **per-row** temperature/top_k/top_p/key tensors, so
  one continuous-batching decode dispatch serves requests with different
  settings without fragmenting the batch into per-settings jit variants.
  Everything is static-shape; row-wise knobs are data.

Speculative decoding adds :func:`spec_accept_slots` — ragged acceptance of
k drafted tokens per row against the verify dispatch's k+1 logit rows:
exact greedy match for greedy rows, rejection sampling (point-mass
proposals) for sampled rows, both against the SAME filtered target
distribution :func:`filtered_logits` defines.

Overlapped execution adds :func:`retire_mask_slots` — device-side
stop-token and generation-bound classification of a freshly generated
token block, so the engine can launch the NEXT decode dispatch before the
host ever sees this one's tokens (the done mask feeds the next dispatch's
row masking without a host round-trip).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0  # 0 → off
    top_p: float = 1.0  # 1 → off

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0


@jax.named_scope("sample")
def sample(
    logits: jax.Array,  # [B, V] (last-token logits)
    key: jax.Array,
    params: SamplingParams,
) -> jax.Array:
    """→ [B] int32 next tokens."""
    if params.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / params.temperature
    if params.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -params.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # smallest set of tokens whose mass ≥ top_p: keep while cum-prev < p
        keep_sorted = (cumulative - probs) < params.top_p
        threshold = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < threshold, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def filtered_logits(
    logits: jax.Array,  # [B, V]
    temperature: jax.Array,  # [B] f32
    top_k: jax.Array,  # [B] i32; 0 → off
    top_p: jax.Array,  # [B] f32; >= 1 → off
) -> jax.Array:
    """Temperature-scaled logits with top-k/top-p support filtering applied
    (-inf outside the kept set) → [B, V] f32.

    THE definition of the target distribution: ``sample_slots`` draws from
    it directly, and speculative verification (``spec_accept_slots``) must
    accept/resample against the exact same filtered distribution or sampled
    speculative output would drift off the non-speculative distribution.

    One descending sort serves both top-k (rank cutoff) and top-p (nucleus
    mass cutoff); rows with filtering off use rank < V / mass < 1 which keep
    everything.
    """
    V = logits.shape[-1]
    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / safe_temp
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    ranks = jnp.arange(V, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k > 0, top_k, V)[:, None]
    keep = ranks < k_eff
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    keep &= (cumulative - probs) < jnp.minimum(top_p, 1.0)[:, None]
    keep |= ranks == 0  # never filter out every token
    threshold = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(scaled < threshold, -jnp.inf, scaled)


@jax.named_scope("sample")
def sample_slots(
    logits: jax.Array,  # [B, V] (last-token logits)
    keys: jax.Array,  # [B] stacked typed PRNG keys (one stream per slot)
    temperature: jax.Array,  # [B] f32; <= 0 → greedy for that row
    top_k: jax.Array,  # [B] i32; 0 → off
    top_p: jax.Array,  # [B] f32; >= 1 → off
) -> jax.Array:
    """Per-row sampling → [B] int32 next tokens.

    Greedy rows bypass the categorical draw via a final where.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filtered = filtered_logits(logits, temperature, top_k, top_p)
    drawn = jax.vmap(
        lambda k, row: jax.random.categorical(k, row, axis=-1)
    )(keys, filtered).astype(jnp.int32)
    return jnp.where(temperature > 0.0, drawn, greedy)


def retire_mask_slots(
    toks: jax.Array,  # [B, S] the dispatch's generated tokens, row-major
    stop_table: jax.Array,  # [B, n_stop] i32 per-row stop tokens, -1 padded
    bound: jax.Array,  # [B] i32 steps until the row's hard bound (pre-dispatch)
    active: jax.Array,  # [B] bool rows that actually participated
    emitted: "jax.Array | None" = None,  # [B] valid tokens per row (None → S)
) -> tuple[jax.Array, jax.Array]:
    """Per-row retirement classification → (n_valid [B] i32, done [B] bool).

    THE device-side mirror of the engine's host retirement authority
    (``_record_token``): walk each row's token block, deliver tokens up to
    the first stop token (exclusive) or the hard generation bound
    (max_new_tokens / sequence room), whichever comes first.  ``n_valid``
    is how many of the row's tokens the host should deliver; ``done`` is
    whether the row retired inside this block.

    Computing this ON DEVICE is what makes double-buffered dispatch safe:
    the done mask of dispatch N feeds dispatch N+1's row masking as plain
    device dataflow, so N+1 can launch before any host sync of N — a
    retiring row is frozen out of N+1 without the host in the loop.

    ``emitted`` ragged-limits the scan for speculative verify blocks
    (positions past a row's emitted count are padding, and padding zeros
    must never match a stop token).  Inactive rows report (0, False): a
    done mask must never leak onto a slot the host has since re-admitted.
    """
    B, S = toks.shape
    limit = (
        jnp.full((B,), S, jnp.int32) if emitted is None
        else emitted.astype(jnp.int32)
    )
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    within = pos < limit[:, None]
    is_stop = (toks[:, :, None] == stop_table[:, None, :]).any(-1) & within
    stop_any = is_stop.any(axis=1)
    first_stop = jnp.argmax(is_stop, axis=1).astype(jnp.int32)
    n_before = jnp.where(stop_any, first_stop, limit)
    bound = jnp.maximum(bound, 0)
    n_valid = jnp.minimum(n_before, bound)
    done = stop_any | (bound <= limit)
    return jnp.where(active, n_valid, 0), done & active


@jax.named_scope("sample")
def spec_accept_slots(
    logits: jax.Array,  # [B, S, V] verify logits (S = k_spec + 1)
    drafts: jax.Array,  # [B, S-1] i32 drafted candidate tokens
    ndraft: jax.Array,  # [B] i32 valid drafts per row (0..S-1)
    base_lens: jax.Array,  # [B] kv length at dispatch start
    keys: jax.Array,  # [B] per-slot PRNG keys
    temperature: jax.Array,  # [B] f32; <= 0 → greedy (exact-match) rows
    top_k: jax.Array,  # [B] i32
    top_p: jax.Array,  # [B] f32
    *,
    sampled: bool = True,  # static: False → all-greedy batch, no RNG work
) -> tuple[jax.Array, jax.Array]:
    """Ragged speculative acceptance → (out_tokens [B, S], emitted [B]).

    Per row: ``logits[:, j]`` is the target model's distribution for the
    token AFTER fed token j (fed tokens are [last, d_0, .., d_{S-2}]).
    Accept the longest prefix of drafts, then emit ONE correction/bonus
    token at the first rejected (or first undrafted) position — so
    ``emitted = accepted + 1`` and ``out_tokens[b, :emitted[b]]`` are the
    row's new tokens, in order.

    - Greedy rows (temperature <= 0): accept d_j iff it equals
      argmax(logits[:, j]); the correction IS the argmax — output is
      token-exact vs non-speculative greedy decode.
    - Sampled rows: standard rejection sampling against the SAME filtered
      distribution ``sample_slots`` uses.  Drafters propose
      deterministically (point-mass q), so d_j is accepted with
      probability p(d_j) and a rejection resamples from the residual
      p with d_j's mass removed — the emitted marginal is exactly p.
      Each position folds the slot key with its absolute token index
      (``base_lens + 1 + j``), the same per-(request, position) stream
      convention as the non-speculative decode path.
    """
    B, S, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, S]
    j = jnp.arange(S - 1, dtype=jnp.int32)[None, :]
    drafted = j < ndraft[:, None]  # [B, S-1]
    if not sampled:
        # all-greedy batch: acceptance is exact match, the correction IS
        # the argmax — no filtering, keys, or categorical draws traced
        acc = (drafts == greedy[:, : S - 1]) & drafted
        corr = greedy
        return _assemble(drafts, acc, corr, B, S)
    flat = filtered_logits(
        logits.reshape(B * S, V),
        jnp.repeat(temperature, S),
        jnp.repeat(top_k, S),
        jnp.repeat(top_p, S),
    ).reshape(B, S, V)
    probs = jax.nn.softmax(flat, axis=-1)  # [B, S, V]

    # per-(row, position) streams: fold the slot key with the absolute
    # index the emitted token would occupy, then split acceptance vs
    # resample randomness off that stream
    pos = base_lens[:, None] + 1 + jnp.arange(S)[None, :]  # [B, S]
    pos_keys = jax.vmap(
        lambda key, row: jax.vmap(lambda p: jax.random.fold_in(key, p))(row)
    )(keys, pos)  # [B, S] keys
    split = jax.vmap(jax.vmap(lambda k: jax.random.split(k, 2)))(pos_keys)
    u = jax.vmap(jax.vmap(lambda k: jax.random.uniform(k)))(
        split[:, :, 0]
    )  # [B, S] acceptance draws
    resample_keys = split[:, :, 1]

    p_draft = jnp.take_along_axis(
        probs[:, : S - 1], drafts[..., None], axis=-1
    )[..., 0]  # [B, S-1]
    acc_sampled = u[:, : S - 1] < p_draft
    acc_greedy = drafts == greedy[:, : S - 1]
    acc = (
        jnp.where(temperature[:, None] > 0.0, acc_sampled, acc_greedy)
        & drafted
    )

    # correction token per position: a REJECTED drafted position resamples
    # from the residual (p with the draft's mass removed — q is a point
    # mass, so residual ∝ p excluding d); an undrafted position draws
    # plainly from p (this covers the bonus token after full acceptance)
    onehot = jax.nn.one_hot(drafts, V, dtype=bool)  # [B, S-1, V]
    residual = jnp.where(onehot, -jnp.inf, flat[:, : S - 1])
    draw = jax.vmap(jax.vmap(jax.random.categorical))
    corr_residual = draw(resample_keys[:, : S - 1], residual).astype(jnp.int32)
    corr_plain = draw(resample_keys, flat).astype(jnp.int32)  # [B, S]
    corr_sampled = jnp.concatenate(
        [
            jnp.where(drafted, corr_residual, corr_plain[:, : S - 1]),
            corr_plain[:, S - 1 :],
        ],
        axis=-1,
    )  # [B, S]
    corr = jnp.where(temperature[:, None] > 0.0, corr_sampled, greedy)
    return _assemble(drafts, acc, corr, B, S)


def _assemble(
    drafts: jax.Array,  # [B, S-1]
    acc: jax.Array,  # [B, S-1] bool per-position acceptance
    corr: jax.Array,  # [B, S] correction/bonus token per position
    B: int,
    S: int,
) -> tuple[jax.Array, jax.Array]:
    """(out_tokens [B, S], emitted [B]): the leading accepted draft prefix
    followed by ONE correction token at the first non-accepted position."""
    prefix = jnp.cumprod(acc.astype(jnp.int32), axis=-1)
    accepted = jnp.sum(prefix, axis=-1).astype(jnp.int32)  # [B] 0..S-1
    i = jnp.arange(S, dtype=jnp.int32)[None, :]
    pad_drafts = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=-1
    )
    out_tokens = jnp.where(
        i < accepted[:, None],
        pad_drafts,
        jnp.where(i == accepted[:, None], corr, 0),
    ).astype(jnp.int32)
    return out_tokens, accepted + 1
