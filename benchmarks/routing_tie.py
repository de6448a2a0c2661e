"""The routing-tie rule of an expert model's reference, in one place.

A top-k is not continuous: where the last expert a token's gate chooses
leads the first one left out by less than the rounding of the served
(bfloat16) stream, the program may rightly choose the other one, and the
margin rule of ``benchmarks/reference.py`` alone then fails a correct
program (PERF.md section 6, PR 31).  An architecture file whose reference
has routed experts follows, for each position it decides, EVERY choice of
experts within ``agreement.routing_tie`` of its own top k through all later
layers, and

- accepts the served token if it is the argmax, by more than the margin, of
  ONE of those routings (``forward_top2`` then returns it with that margin);
- fails it if every routing decides by more than the margin and none gives it;
- leaves the position undecided (margin 0) otherwise.

What is the architecture's own stays in its file: the walk through its
layers for a handful of streams that stand at given positions of a row
(attention over the row's earlier keys, a recurrent state as the earlier
positions left it).  Here is what no architecture changes: the choices within
a tie (:func:`routings`), the bounds that keep their number small
(:func:`bounded`), the padding to few shapes (:func:`padded`, :func:`room`)
and the verdict (:func:`decide`).  ``deepseek-mla-moe.py`` (PR 31) keeps the
copy it was accepted with: a file the benchmark has is not edited.
"""

from __future__ import annotations

import itertools
import math

NODES_AT_LEAST = 128  # a position's routings are padded to a power of two from here
ROUTINGS_A_LAYER = 6  # more choices than this within the tie at one layer, or
ROUTINGS_A_POSITION = 48  # than this in all: the position is left undecided


def routings(scores, k: int, tie: float, held: tuple[int, int] | None = None):
    """Every choice of k experts within ``tie`` of the top k of ``scores``
    [N, E] -> (parent [M] the token of each choice, chosen [M, E] of 0 and 1,
    first [M] bool: the top k itself, crowded [N] bool: a token with more
    choices than ``ROUTINGS_A_LAYER``, which keeps its top k alone).  An
    expert inside the top k is in doubt if it leads the first one outside by
    less than the tie, one outside if the last one inside leads it by less;
    the experts in doubt take the places of those inside in every way.
    ``held`` = (first, count) names the experts this device holds, where it
    holds a share: a doubt among experts that are ALL held elsewhere moves
    this device's sum only through the weights' common denominator, by less
    than the tie, and opens no choice."""
    import numpy as np

    N, E = scores.shape
    order = np.argsort(-scores, axis=-1, kind="stable")
    ranked = np.take_along_axis(scores, order, axis=-1)
    inside = ranked[:, :k] - ranked[:, k:k + 1] < tie  # [N, k]
    outside = ranked[:, k - 1:k] - ranked[:, k:] < tie  # [N, E - k]
    parent, chosen, first = [], [], []
    crowded = np.zeros(N, bool)
    top = np.zeros((N, E), np.float32)
    np.put_along_axis(top, order[:, :k], 1.0, axis=-1)
    for n in range(N):
        parent.append(n)
        chosen.append(top[n])
        first.append(True)
        if not outside[n].any():
            continue
        doubt = [*order[n, :k][inside[n]], *order[n, k:][outside[n]]]
        if held is not None and not any(held[0] <= e < held[0] + held[1] for e in doubt):
            continue
        places = int(inside[n].sum())
        if math.comb(len(doubt), places) > ROUTINGS_A_LAYER:
            crowded[n] = True
            continue
        sure = top[n].copy()
        sure[doubt] = 0.0
        for take in itertools.combinations(doubt, places):
            if set(take) == set(order[n, :k][inside[n]]):
                continue  # the top k itself
            other = sure.copy()
            other[list(take)] = 1.0
            parent.append(n)
            chosen.append(other)
            first.append(False)
    return np.asarray(parent), np.stack(chosen), np.asarray(first), crowded


def bounded(position, first, given_up, parent, chosen, top, crowded):
    """One layer's ``routings`` of the streams that stand at ``position``
    (``first``: those still on the reference's own routing) -> (parent,
    chosen, position, first) of the streams that go on, with ``given_up``
    marked in place: a crowded token's position, and a position with more
    than ``ROUTINGS_A_POSITION`` streams, which keeps the reference's own
    routing alone."""
    import numpy as np

    given_up[position[crowded]] = True
    many = np.bincount(position[parent], minlength=len(given_up)) > ROUTINGS_A_POSITION
    if many.any():
        given_up |= many
        keep = ~many[position[parent]] | (top & first[parent])
        parent, chosen, top = parent[keep], chosen[keep], top[keep]
    return parent, chosen, position[parent], first[parent] & top


def room(n: int) -> int:
    """The padded count for ``n`` streams: a power of two, so that the
    layers compile for few shapes."""
    return max(NODES_AT_LEAST, 1 << (n - 1).bit_length())


def padded(a, n: int):
    import numpy as np

    a = np.asarray(a)
    return np.concatenate([a, np.repeat(a[:1], n - len(a), axis=0)]) if n > len(a) else a


def decide(position, first, given_up, arg, gap, served, margin: float):
    """The verdict for positions 0 .. len(served) - 1: ``arg``, ``gap`` [M]
    are the argmax and top-1 margin under each followed routing, ``position``
    [M] the position it belongs to, ``first`` [M] the reference's own ->
    (argmax [P], margin [P], what was seen, counted)."""
    import numpy as np

    out_arg, out_gap = np.zeros(len(served), arg.dtype), np.zeros(len(served), gap.dtype)
    seen = {"routings": len(position), "positions_with_a_routing_tie": 0,
            "positions_given_up_for_their_many_routings": int(given_up.sum()),
            "accepted_under_another_routing_than_the_reference's": 0,
            "served_token_under_no_admitted_routing": 0}
    for p in range(len(served)):
        mine = position == p
        own = int(np.flatnonzero(mine & first)[0])  # the reference's own routing
        seen["positions_with_a_routing_tie"] += int(mine.sum() > 1)
        hits = mine & (arg == served[p]) & (gap > margin)
        out_arg[p] = arg[own]
        if hits.any():
            out_arg[p], out_gap[p] = served[p], gap[hits].max()
            seen["accepted_under_another_routing_than_the_reference's"] += int(not hits[own])
        elif not given_up[p] and (gap[mine] > margin).all():
            out_gap[p] = gap[own]  # decided, and the served token is none of them
            seen["served_token_under_no_admitted_routing"] += 1
    return out_arg, out_gap, seen
