"""Query generators: who pays whom how much, from the seed.

`cell_pairs` is a cell's query list by the pair law its file names:
gen/pairs_<law>.py `pairs(g, nodes, n, seed, *, amount_min_msat,
amount_max_msat, **params.pairs_params)`, `params.pairs` the law
(default `uniform`).

`pairs`, the uniform law, draws (source, destination, amount_msat) with
both ends in the graph's largest component (so nearly every query has a
route; one that has none is answered "no route", and the reference must
say the same), uniformly, and the amount log-uniform between two bounds.

`pacing` says when each caller of a closed loop asks: its think times
after a reply and the delay before its first request.  Every caller of
every seed gets the same set of think times, `THINKS` values evenly
spaced over mean * (1 -+ spread), and the callers' first requests are
evenly spaced over `start_spread_s`; the seed only draws the orders.
So two seeds offer the same load, differently interleaved.
"""
from __future__ import annotations

import importlib
import math
import random


def pair_law(params: dict) -> str:
    """The law a cell's `params` name, `uniform` where none."""
    return params.get("pairs", "uniform")


def cell_pairs(g, nodes: list[int], params: dict, seed: int
               ) -> list[tuple[int, int, int]]:
    """`params.queries` queries over the reference graph `g`, whose
    largest component is `nodes`."""
    law = importlib.import_module(f".pairs_{pair_law(params)}", __package__)
    return law.pairs(g, nodes, params["queries"], seed,
                     amount_min_msat=params["amount_min_msat"],
                     amount_max_msat=params["amount_max_msat"],
                     **params.get("pairs_params", {}))


def pairs(nodes: list[int], n: int, seed: int, *, amount_min_msat: int,
          amount_max_msat: int) -> list[tuple[int, int, int]]:
    rng = random.Random(seed)
    lo, hi = math.log(amount_min_msat), math.log(amount_max_msat)
    out = []
    for _ in range(n):
        a, b = rng.sample(nodes, 2)
        out.append((a, b, int(math.exp(rng.uniform(lo, hi)))))
    return out


THINKS = 16     # think times per caller before the list wraps round


def pacing(callers: int, seed: int, *, think_mean_s: float,
           think_spread: float, start_spread_s: float
           ) -> tuple[list[list[float]], list[float]]:
    """(think seconds per caller, seconds before each caller's first
    request)."""
    rng = random.Random(seed * 2 + 1)
    grid = [think_mean_s * (1 - think_spread
                            + 2 * think_spread * (k + 0.5) / THINKS)
            for k in range(THINKS)]
    think = []
    for _ in range(callers):
        row = grid[:]
        rng.shuffle(row)
        think.append(row)
    start = [start_spread_s * (k + 0.5) / callers for k in range(callers)]
    rng.shuffle(start)
    return think, start
