"""Pair law `zipf`: payees are the well-connected nodes.

The largest component's nodes are ranked by degree (channels at the
node, either direction), largest first, ties by node index; the payee
is the node of rank r with P(r) ~ r^-`exponent`.  The payer is uniform
over the component and never the payee; the amount is log-uniform as
the uniform law's.  The cell gives `exponent` under
`params.pairs_params` (no public source gives a payee distribution: a
cell lists its value under `assumed`).
"""
from __future__ import annotations

import itertools
import math
import random

import numpy as np


def ranked(g, nodes: list[int]) -> list[int]:
    """The component's nodes, by degree descending, then by index."""
    degree = np.bincount(g.node1, minlength=g.n_nodes) \
        + np.bincount(g.node2, minlength=g.n_nodes)
    return sorted(nodes, key=lambda v: (-int(degree[v]), v))


def rank_weights(n: int, exponent: float) -> list[float]:
    return [r ** -exponent for r in range(1, n + 1)]


def pairs(g, nodes: list[int], n: int, seed: int, *, amount_min_msat: int,
          amount_max_msat: int, exponent: float
          ) -> list[tuple[int, int, int]]:
    rng = random.Random(seed)
    lo, hi = math.log(amount_min_msat), math.log(amount_max_msat)
    cum = list(itertools.accumulate(rank_weights(len(nodes), exponent)))
    payees = rng.choices(ranked(g, nodes), cum_weights=cum, k=n)
    out = []
    for b in payees:
        a = b
        while a == b:
            a = rng.choice(nodes)
        out.append((a, b, int(math.exp(rng.uniform(lo, hi)))))
    return out
