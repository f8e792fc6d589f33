"""Endpoint law `uniform` (the default): both ends of every channel
uniform over the nodes, never the same node twice, as
`lightning_tpu/gossip/synth.py` draws them.  Reads `graph.channels`
and `graph.nodes`."""
from __future__ import annotations

import numpy as np


def endpoints(rng: np.random.Generator, graph: dict
              ) -> tuple[np.ndarray, np.ndarray]:
    nodes, channels = graph["nodes"], graph["channels"]
    a = rng.integers(0, nodes, channels)
    b = (a + 1 + rng.integers(0, nodes - 1, channels)) % nodes
    return a, b
