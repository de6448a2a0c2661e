"""The grouped expert products over the STACK (PR 34): one reading shared by
``tests/test_mla_moe.py`` (every expert held) and ``tests/test_gdn_moe.py``
(experts held by share).

``experts_grouped`` takes the stacked leaves and the layer's index and must
give, for EVERY layer of a stack of three, what the parent's form gave on the
layer sliced out (the slice as a stack of one: the parent's kernel call) and
what the dense form gives: under ``jit``, inside a ``lax.scan`` with the index traced, as
``model.py``'s stacks call it.  The routings are made by hand, so that a group
is exactly as empty or as full as the case says.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from calfkit_tpu.inference import moe

LAYERS, TOKENS = 3, 40
ROUTINGS = ("even", "one_expert_empty", "first_expert_all", "last_expert_all")


def routing(case: str, key: int, scored: int, k: int, first: int, held: int):
    """(chosen [T, k] int32 among ``scored`` experts, k distinct a token;
    weights [T, k] float32): ``first`` and ``held`` say which of them this
    device holds."""
    order = np.stack([
        np.random.default_rng((key, t)).permutation(scored) for t in range(TOKENS)])
    if case == "one_expert_empty":  # the second held expert is nobody's choice
        order = np.stack([row[row != first + 1] for row in order])
    elif case in ("first_expert_all", "last_expert_all"):  # ... is EVERY token's first choice
        full = first if case == "first_expert_all" else first + held - 1
        order = np.stack([np.concatenate([[full], row[row != full]]) for row in order])
    elif case == "every_pair_absent":  # a share alone: all choices fall on experts held elsewhere
        away = [e for e in range(scored) if not first <= e < first + held]
        order = np.stack([np.random.default_rng((key, t)).permutation(away) for t in range(TOKENS)])
    else:
        assert case == "even", case
    weights = np.random.default_rng(key).uniform(0.1, 1.0, (TOKENS, k)).astype(np.float32)
    return jnp.asarray(order[:, :k], jnp.int32), jnp.asarray(weights)


@functools.lru_cache(maxsize=None)
def three_forms(config, case: str):
    """→ (over the stack, the parent's on the slice, dense), each
    ``[LAYERS, TOKENS, D]``, and the held pairs of the routing."""
    c = config
    share, first, E = c.expert_share, c.expert_first, c.n_routed_experts
    stack = jax.tree.map(lambda a: a[:LAYERS], moe.init_moe_params(c, jax.random.key(3), jnp.float32))
    assert stack["w_gate"].shape[:2] == (LAYERS, E)
    h = jax.random.normal(jax.random.key(4), (TOKENS, c.d_model))
    chosen, weights = routing(case, 5, c.experts_scored, c.n_experts_per_tok, first, E)
    onehot = chosen[..., None] == jnp.arange(E, dtype=jnp.int32) + first

    @jax.jit
    def over_layers(stack, h, chosen, weights):
        def body(_, m):
            lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False), stack)
            return None, (
                moe.experts_grouped(h, chosen, onehot, weights, stack, m, share),
                # the parent's products: ``lax.ragged_dot`` on the SLICE with the layer's own
                # sizes, which is what a stack of that one layer compiles to
                moe.experts_grouped(
                    h, chosen, onehot, weights,
                    {n: lp[n][None] for n in ("w_gate", "w_up", "w_down")}, 0, share),
                moe.experts_dense(h, onehot, weights, lp),
            )
        return lax.scan(body, None, jnp.arange(LAYERS, dtype=jnp.int32))[1]

    forms = tuple(np.asarray(a) for a in over_layers(stack, h, chosen, weights))
    return forms, np.asarray(jnp.sum(onehot, axis=(0, 1)))


def check(config, case: str, m: int) -> None:
    (stacked, parent, dense), pairs = three_forms(config, case)
    k, E = config.n_experts_per_tok, config.n_routed_experts
    if case == "one_expert_empty":
        assert pairs[1] == 0 and pairs.sum() > 0
    elif case == "first_expert_all":
        assert pairs[0] == TOKENS
    elif case == "last_expert_all":
        assert pairs[E - 1] == TOKENS
    elif case == "every_pair_absent":
        assert pairs.sum() == 0 and not stacked[m].any() and not dense[m].any()
    if not config.expert_share:
        assert pairs.sum() == TOKENS * k  # every expert held: no pair behind the last group
    # the same products on the same operands: the parent's kernel call, bit for bit
    assert np.array_equal(stacked[m], parent[m])
    # float32 sums in two orders, outputs of order 1 (the file's other form tests: 5e-7)
    assert np.abs(stacked[m] - dense[m]).max() < 1e-5
    if pairs.sum():  # an offset wrong by ONE layer is another layer's experts
        for other in range(LAYERS):
            if other != m:
                assert np.abs(stacked[m] - dense[other]).max() > 1e-2
