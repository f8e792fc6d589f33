"""Endpoint law `powerlaw`: a hub graph, as mainnet's is.

Every node gets a degree from P(k) ~ k^-`degree_exponent` on
k_min <= k <= k_max, and the channels are the pairs of a configuration
model over those degrees.

* k_max is `hub_share_max` x channels: the largest node's share of all
  channels.
* k_min is not a knob: it is the integer at which the truncated law's
  mean lies nearest the mean degree the graph has, 2 x channels /
  nodes (2 at 25,000 / 6,000 with exponent 2.1 and k_max 1,000: mean
  8.2 against 8.33).
* The degrees are the law's quantiles at (i + 1/2) / nodes, not random
  draws: every seed gets the same degree sequence, and so the same
  largest hub and the same count of doubling steps in the route
  program; the seed draws which node has which degree and who is
  joined to whom.
* What the quantiles leave short of, or over, exactly 2 x channels is
  made up by adding (or taking) single stubs at nodes drawn from the
  rng, none pushed outside k_min..k_max.
* The stubs are shuffled and paired in order.  A pair that joins a
  node to itself is re-drawn (one of its ends exchanged with another
  pair's); parallel channels between one pair of nodes stay, as
  mainnet has them.

Reads `graph.channels`, `graph.nodes`, `graph.degree_exponent`,
`graph.hub_share_max`.  The law moves endpoints and nothing else.
"""
from __future__ import annotations

import numpy as np


def degree_sequence(graph: dict) -> tuple[np.ndarray, int, int]:
    """(degrees by quantile, ascending; k_min; k_max), before the
    remainder is made up."""
    nodes, channels = graph["nodes"], graph["channels"]
    gamma = float(graph["degree_exponent"])
    k_max = max(1, int(graph["hub_share_max"] * channels))
    k = np.arange(1, k_max + 1, dtype=np.float64)
    w = k ** -gamma
    # mean of the law truncated to k_min..k_max, for every k_min
    mean = np.cumsum((w * k)[::-1])[::-1] / np.cumsum(w[::-1])[::-1]
    k_min = int(np.abs(mean - 2.0 * channels / nodes).argmin()) + 1
    cdf = np.cumsum(w[k_min - 1:])
    cdf /= cdf[-1]
    at = (np.arange(nodes) + 0.5) / nodes
    deg = k_min + np.searchsorted(cdf, at, side="left")
    return deg.astype(np.int64), k_min, k_max


def endpoints(rng: np.random.Generator, graph: dict
              ) -> tuple[np.ndarray, np.ndarray]:
    nodes, channels = graph["nodes"], graph["channels"]
    deg, k_min, k_max = degree_sequence(graph)
    if not nodes * k_min <= 2 * channels <= nodes * k_max:
        raise ValueError(
            f"powerlaw: {nodes} nodes of degree {k_min}..{k_max} cannot "
            f"hold {channels} channels")
    deg = deg[rng.permutation(nodes)]
    while (short := 2 * channels - int(deg.sum())) != 0:
        step = 1 if short > 0 else -1
        room = np.nonzero(deg < k_max if step > 0 else deg > k_min)[0]
        deg[rng.choice(room, min(abs(short), len(room)),
                       replace=False)] += step
    stubs = np.repeat(np.arange(nodes), deg)
    rng.shuffle(stubs)
    a, b = stubs[0::2].copy(), stubs[1::2].copy()
    while len(loops := np.nonzero(a == b)[0]):
        other = rng.integers(0, channels, len(loops))
        for i, j in zip(loops.tolist(), other.tolist()):
            # exchange only where neither pair comes out a loop
            if a[i] != b[j] and a[j] != b[i]:
                b[i], b[j] = b[j], b[i]
    return a, b
