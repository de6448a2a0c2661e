"""Shared device-stub math for the overhead microbenches.

The engine retires rows on the DEVICE's verdict (``sampler.retire_mask_slots``
inside the decode/verify jits), so every script that stubs the jit boundary
must mirror that contract or its engine never finishes a request.  One numpy
copy here instead of one per script — a change to the retirement semantics
updates a single reference implementation, and the committed artifacts
(SCHED_OVERHEAD_r*.json, OVERLAP.json, SPEC_DECODE.json)
cannot silently keep passing against a contract the engine dropped.

These benches configure no stop tokens, so only the hard-bound half of
``retire_mask_slots`` is mirrored (tests/test_overlap_dispatch.py pins the
full stop-token math against the real jnp implementation).
"""

from __future__ import annotations

import numpy as np


def stub_retire_block(
    active, done_prev, lens, hard_end, steps: int
) -> "tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]":
    """A plain decode dispatch's retirement verdict (no stop tokens):
    → (act, n_valid, done, new_lens), matching the jit contract — rows
    masked by ``done_prev`` freeze, live rows deliver up to the hard
    bound and retire when it falls inside the block."""
    act = np.asarray(active) & ~np.asarray(done_prev)
    lens = np.asarray(lens)
    bound = np.asarray(hard_end) - lens
    n_valid = np.where(act, np.clip(bound, 0, steps), 0).astype(np.int32)
    done = act & (bound <= steps)
    new_lens = np.where(act, lens + steps, lens).astype(np.int32)
    return act, n_valid, done, new_lens


def stub_retire_emitted(
    active, lens, hard_end, emitted
) -> "tuple[np.ndarray, np.ndarray]":
    """A verify (speculative) dispatch's verdict over per-row ragged
    ``emitted`` counts (no stop tokens): → (n_valid, done)."""
    act = np.asarray(active)
    bound = np.maximum(np.asarray(hard_end) - np.asarray(lens), 0)
    emitted = np.asarray(emitted)
    n_valid = np.minimum(emitted, bound).astype(np.int32)
    done = act & (bound <= emitted)
    return n_valid, done


def stub_prefill_lens(lens, slots, true_lens) -> np.ndarray:
    """The prefill jit scatters each wave row's true length into ``lens``;
    the decode stub's bound math reads it, so prefill stubs must mirror
    the scatter."""
    lens = np.asarray(lens).copy()
    lens[np.asarray(slots)] = np.asarray(true_lens)
    return lens
