"""Ragged unified prefill+decode wave math (ISSUE 6).

Pure host-side helpers for the engine's ragged wave scheduler: per-row
query descriptors for the unified attention law, and the token-budget
arithmetic that decides how much pending prefill a half-empty decode wave
may absorb.  No jax imports — these run at wave-formation time on the
event loop and inside the dispatch-thread packing loop, both of which
``scripts/lint_hotpath.py`` keeps free of device syncs and formatting;
keeping the module dependency-free also keeps it trivially typeable
(it sits under the real mypy gate with the rest of ``inference.*``).

The descriptor vocabulary mirrors Ragged Paged Attention (PAPERS.md,
arXiv:2604.15464): one invocation consumes a batch whose rows mix

- ``decode`` rows — q_len = 1, one fresh query at position ``start``;
- ``prefill`` rows — q_len = chunk, queries at ``start .. start+chunk``;
- ``verify`` rows — q_len = k+1, the speculative multi-query read.

All three share ONE masking law: query ``j`` of a row attends kv
positions ``< min(kv_len, start + j + 1)`` — causal within the row's own
fresh span, bounded by the row's valid cache length.

:class:`RaggedRow` / :func:`build_descriptors` are the SPEC vocabulary:
tests pin the attention sources' mask law against descriptors built here
(``tests/test_ragged_waves.py`` — the executable definition of what a
mixed wave means), and formation-time tooling can reason in rows.  The
engine's hot path ships the ``(q_starts, q_lens, kv_lens)`` arrays
directly (decode/verify rows derive them from ``lens``/``base_lens``
inside the jit — building python objects per dispatch would be
allocation on the packing loop).  The budget functions below ARE the
hot-path consumers: the engine calls them at formation and absorption
time every tick.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable

KIND_DECODE = 0
KIND_PREFILL = 1
KIND_VERIFY = 2

_KIND_NAMES = {KIND_DECODE: "decode", KIND_PREFILL: "prefill",
               KIND_VERIFY: "verify"}


@dataclass(frozen=True)
class RaggedRow:
    """One row of a ragged wave: what kind of work it carries, where its
    queries start (absolute cache position of query 0), how many queries
    it contributes, and how much cache is valid for it."""

    kind: int  # KIND_DECODE | KIND_PREFILL | KIND_VERIFY
    start: int  # absolute position of the row's first query
    q_len: int  # 1 (decode) | chunk (prefill) | k+1 (verify)
    kv_len: int  # valid kv length the row may attend (before its span)

    @property
    def kind_name(self) -> str:
        return _KIND_NAMES.get(self.kind, "?")

    def tokens(self) -> int:
        """Query tokens the row contributes to the wave's budget."""
        return self.q_len


def build_descriptors(
    rows: "Iterable[RaggedRow]",
) -> "tuple[list[int], list[int], list[int]]":
    """Flatten rows into the (q_starts, q_lens, kv_lens) arrays the
    unified attention entry points take (the ``kind`` is not shipped to
    the device — the mask law above is kind-agnostic by construction)."""
    starts: list[int] = []
    q_lens: list[int] = []
    kv_lens: list[int] = []
    for row in rows:
        starts.append(row.start)
        q_lens.append(row.q_len)
        kv_lens.append(row.kv_len)
    return starts, q_lens, kv_lens


def token_budget(
    configured: int, max_batch_size: int, steps: int, chunk: int,
    max_prefill_wave: int,
) -> int:
    """Resolve the wave token budget (``RuntimeConfig.ragged_token_budget``;
    0 = auto).

    Auto is deliberately generous: a full decode wave plus a full-width
    prefill wave — admission is already bounded by free slots and
    ``max_prefill_wave``, so the default budget never second-guesses it.
    Set an explicit budget to bound per-dispatch latency instead: the
    fused dispatch's compute grows with the absorbed chunk tokens, so a
    tighter budget trades prefill absorption for steadier inter-token
    latency (see the knob table in docs/inference.md)."""
    if configured > 0:
        return configured
    return max_batch_size * steps + max_prefill_wave * chunk


def fits_budget(
    budget: int, active_rows: int, steps: int, chunk_rows: int, chunk: int
) -> bool:
    """May a dispatch carrying ``active_rows`` decode rows absorb a
    ``chunk_rows``-wide prefill chunk?  Token accounting: decode
    contributes ``active_rows * steps`` query tokens (the scan), the
    chunk contributes ``chunk_rows * chunk``."""
    return active_rows * steps + chunk_rows * chunk <= budget


def wave_width_cap(
    budget: int, active_rows: int, steps: int, chunk: int
) -> int:
    """Widest prefill wave the budget lets a dispatch absorb alongside
    ``active_rows`` decode rows — never below 1 (the wave head always
    forms; a head that can't absorb simply advances in its own
    invocation until decode slack opens up)."""
    slack = budget - active_rows * steps
    return max(1, slack // chunk)
