"""Pair law `uniform` (the default): payer and payee uniform over the
largest component, the amount log-uniform (gen/queries.py `pairs`).
The graph is not looked at."""
from __future__ import annotations

from . import queries


def pairs(g, nodes: list[int], n: int, seed: int, *, amount_min_msat: int,
          amount_max_msat: int) -> list[tuple[int, int, int]]:
    return queries.pairs(nodes, n, seed, amount_min_msat=amount_min_msat,
                         amount_max_msat=amount_max_msat)
