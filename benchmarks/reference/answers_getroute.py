"""What a `getroute` reply has to say, against the reference solver.

`request` says how the method is asked, `FAMILY` which of the
program's counter families serves it.  An answer is a route or error 205 ("no route"); anything else
(TRY_AGAIN, an internal error) is no answer.  A route is right when it
is a valid path (reference/dijkstra.py `check_path`: enabled channels
joined src -> dst, the asked amount and final cltv delivered, every
hop's amount and delay compounding the next channel's exact fee and
delta) **and** its cost by the reference's cost model equals the
cheapest path's, which the reference's own Dijkstra finds.  "No route"
is right when the reference finds none either.  Ties in cost are free
to differ in path.
"""
from __future__ import annotations

from . import dijkstra as DJ
from .graph import parse_scid

ROUTE_NOT_FOUND = 205
FAMILY = "route"        # the program's counters: clntpu_<FAMILY>_*


def request(g, query) -> dict:
    """The method's parameters for one (source, destination, amount)."""
    src, dst, amount = query
    return {"id": g.node_ids[dst].hex(), "fromid": g.node_ids[src].hex(),
            "amount_msat": amount}


def is_answer(reply: dict) -> bool:
    if "result" in reply:
        return "route" in reply["result"]
    return reply.get("error", {}).get("code") == ROUTE_NOT_FOUND


def check(g, query, reply: dict, *, solver=DJ.getroute) -> None:
    """Raises ValueError with the reason when the reply is wrong.
    `solver` lets the control stand in the reference's place."""
    src, dst, amount = query
    try:
        _want_route, want_cost = solver(g, src, dst, amount)
    except DJ.NoRoute:
        want_cost = None
    if "result" not in reply:
        if reply.get("error", {}).get("code") != ROUTE_NOT_FOUND:
            raise ValueError(f"no answer: {str(reply)[:200]}")
        if want_cost is not None:
            raise ValueError("said no route; the reference finds one at "
                             f"cost {want_cost}")
        return
    if want_cost is None:
        raise ValueError("answered a route; the reference finds none")
    try:
        hops = [(g.node_index(bytes.fromhex(h["id"])),
                 g.channel_index(parse_scid(h["channel"])),
                 int(h["direction"]), int(h["amount_msat"]),
                 int(h["delay"]))
                for h in reply["result"]["route"]]
    except (KeyError, ValueError, TypeError) as e:
        raise ValueError(f"malformed route: {e!r}")
    DJ.check_path(g, src, dst, amount, DJ.FINAL_CLTV, hops)
    got = DJ.route_cost(g, [(c, d, amt) for _, c, d, amt, _ in hops])
    if got != want_cost:
        raise ValueError(f"cost {got}, the cheapest path costs {want_cost}")


def control_reply(g, query) -> dict:
    """The control's answer: the reference solver with compounding
    left out (every hop priced for the amount the payee receives), in
    the reply's own shape."""
    src, dst, amount = query
    try:
        route, _ = DJ.getroute(g, src, dst, amount, compound=False)
    except DJ.NoRoute:
        return {"error": {"code": ROUTE_NOT_FOUND, "message": "no route"}}
    return {"result": {"route": [
        {"id": g.node_ids[v].hex(), "channel": int(g.scids[c]),
         "direction": d, "amount_msat": amt, "delay": dly}
        for v, c, d, amt, dly in route]}}
