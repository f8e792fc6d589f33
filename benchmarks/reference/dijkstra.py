"""Frozen host `getroute`: cheapest path by fee + risk.

A copy of `lightning_tpu/routing/dijkstra.py` as of PR 23 (upstream
`common/dijkstra.c` + `common/route.c`'s cost model), over the
reference's own graph.  It runs backward from the destination,
carrying the amount each hop must receive, so compounding fees are
exact.  The program's copy is also its fallback path; this one is the
yardstick and is not edited by later PRs.

`compound=False` is the control: it prices every hop for the amount
the payee receives, the shortcut a batched solver is tempted by.
"""
from __future__ import annotations

import heapq

RISKFACTOR = 10
BLOCKS_PER_YEAR = 52596
FINAL_CLTV = 18
MAX_HOPS = 20


class NoRoute(Exception):
    pass


def hop_fee_msat(base_msat: int, ppm: int, amount_msat: int) -> int:
    return base_msat + amount_msat * ppm // 1_000_000


def risk_msat(amount_msat: int, delay: int, riskfactor: int) -> int:
    return 1 + amount_msat * delay * riskfactor // (BLOCKS_PER_YEAR * 100)


def getroute(g, src: int, dst: int, amount_msat: int, *,
             final_cltv: int = FINAL_CLTV, riskfactor: int = RISKFACTOR,
             max_hops: int = MAX_HOPS, compound: bool = True):
    """[(next node, chan, dir, amount_msat, delay)] src -> dst, and
    its cost.  Raises NoRoute."""
    if src == dst:
        raise NoRoute("source is destination")
    inf = float("inf")
    n = g.n_nodes
    dist = [inf] * n
    amount = [0] * n
    delay = [0] * n
    nxt = [-1] * n
    via = [None] * n
    hops = [0] * n
    dist[dst] = 0
    amount[dst] = amount_msat
    delay[dst] = final_cltv
    pq = [(0, dst)]
    while pq:
        d_v, v = heapq.heappop(pq)
        if d_v > dist[v]:
            continue
        if v == src:
            break
        if hops[v] >= max_hops:
            continue
        amt_v = amount[v] if compound else amount_msat
        for u, c, d in g.into[v]:
            if not g.enabled[d, c]:
                continue
            fee = hop_fee_msat(int(g.fee_base_msat[d, c]),
                               int(g.fee_ppm[d, c]), amt_v)
            if amt_v < int(g.htlc_min_msat[d, c]):
                continue
            hmax = int(g.htlc_max_msat[d, c])
            if hmax and amt_v > hmax:
                continue
            cd = int(g.cltv_delta[d, c])
            cost = dist[v] + fee + risk_msat(amt_v, cd, riskfactor)
            if cost < dist[u]:
                dist[u] = cost
                amount[u] = amount[v] + fee
                delay[u] = delay[v] + cd
                nxt[u] = v
                via[u] = (c, d)
                hops[u] = hops[v] + 1
                heapq.heappush(pq, (cost, u))
    if dist[src] == inf:
        raise NoRoute("no route")
    route = []
    u = src
    while u != dst:
        v = nxt[u]
        route.append((v, via[u][0], via[u][1], amount[v], delay[v]))
        u = v
    return route, dist[src]


def route_cost(g, hops, riskfactor: int = RISKFACTOR) -> int:
    """The cost model over a route as answered: hops are
    (chan, dir, amount_msat the hop delivers)."""
    cost = 0
    for c, d, amt in hops:
        cost += hop_fee_msat(int(g.fee_base_msat[d, c]),
                             int(g.fee_ppm[d, c]), amt)
        cost += risk_msat(amt, int(g.cltv_delta[d, c]), riskfactor)
    return cost


def check_path(g, src: int, dst: int, amount_msat: int, final_cltv: int,
               hops) -> None:
    """A path is valid when it walks enabled channels src -> dst in the
    stated directions, delivers amount_msat with final_cltv, keeps
    every hop inside the channel's htlc window, and every hop's amount
    and delay are the next hop's plus that channel's exact fee and
    delta.  hops: [(next node, chan, dir, amount_msat, delay)].
    Raises ValueError with the reason."""
    if not hops:
        raise ValueError("empty path")
    at = src
    for node, c, d, amt, _dly in hops:
        if not g.has_update[d, c] or g.ends(c, d) != (at, node):
            raise ValueError(f"hop over channel {c}/{d} does not join")
        if not g.enabled[d, c]:
            raise ValueError(f"channel {c}/{d} is disabled")
        hmax = int(g.htlc_max_msat[d, c])
        if amt < int(g.htlc_min_msat[d, c]) or (hmax and amt > hmax):
            raise ValueError(f"amount outside channel {c}/{d}'s window")
        at = node
    if at != dst:
        raise ValueError("path does not end at the destination")
    if hops[-1][3] != amount_msat or hops[-1][4] != final_cltv:
        raise ValueError("last hop does not deliver the asked amount")
    for (_, _, _, amt, dly), (_, c, d, namt, ndly) in zip(hops, hops[1:]):
        fee = hop_fee_msat(int(g.fee_base_msat[d, c]),
                           int(g.fee_ppm[d, c]), namt)
        if amt != namt + fee or dly != ndly + int(g.cltv_delta[d, c]):
            raise ValueError(f"amount/delay do not compound over {c}/{d}")
