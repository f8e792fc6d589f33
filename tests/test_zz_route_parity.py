"""Host/device route parity: the vmapped Bellman-Ford solver
(routing/device.py) must price routes bit-identically to
dijkstra.getroute over randomized synth gossmaps — ragged degree,
disabled channels, excluded scids, htlc min/max edges, unreachable
destinations — and the RouteService front-end must coalesce, fall back
and meter as documented (doc/routing.md).

All graphs here pad to the SAME quantized planes shape (n_pad 64,
e_pad 256) and every batch uses Q=8, so the suite compiles the route
program once per step count its graphs have (3, 4, 5; the hub case of
the label tests has a shape of its own); the power-law graphs at the
end of the file stay in that family and add one key, `steps` 6.
"""
from __future__ import annotations

import asyncio

import numpy as np
import pytest

from lightning_tpu.gossip import gossmap, store as gstore, synth
from lightning_tpu.routing import device as RD
from lightning_tpu.routing import dijkstra as DJ
from lightning_tpu.routing.planes import RoutePlanes

Q = 8   # one device query bucket for the whole file (one compile)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 120))


def _net(tmp_path, n_channels, n_nodes, seed):
    p = str(tmp_path / f"net{n_channels}_{seed}.gs")
    synth.make_network_store(p, n_channels=n_channels, n_nodes=n_nodes,
                             updates_per_channel=2, seed=seed, sign=False)
    g = gossmap.from_store(gstore.load_store(p))
    assert g.n_nodes <= 64 and 2 * g.n_channels <= 256, \
        "test graph exceeds the shared planes shape"
    return g


def _host(g, q: RD.RouteQuery):
    try:
        return ("ok",) + tuple(DJ.getroute(
            g, q.source, q.destination, q.amount_msat,
            final_cltv=q.final_cltv, riskfactor=q.riskfactor,
            excluded_scids=q.excluded_scids, with_source=True))
    except DJ.NoRoute:
        return ("noroute",)


def _assert_parity(g, queries, results):
    for q, res in zip(queries, results):
        exp = _host(g, q)
        assert res[0] == exp[0], (res, exp)
        if res[0] != "ok":
            continue
        droute, dsrc = res[1], res[2]
        hroute, hsrc = exp[1], exp[2]
        dcost = RD.route_cost_msat(g, droute, q.riskfactor)
        hcost = RD.route_cost_msat(g, hroute, q.riskfactor)
        assert dcost == hcost, (dcost, hcost)
        # route internal consistency: exact fee compounding + cltv
        assert droute[-1].amount_msat == q.amount_msat
        assert droute[-1].delay == q.final_cltv
        for i in range(len(droute) - 1):
            h, nxt = droute[i], droute[i + 1]
            c = g.channel_index(nxt.scid)
            d = nxt.direction
            fee = DJ.hop_fee_msat(int(g.fee_base_msat[d, c]),
                                  int(g.fee_ppm[d, c]), nxt.amount_msat)
            assert h.amount_msat == nxt.amount_msat + fee
            assert h.delay == nxt.delay + int(g.cltv_delta[d, c])
            # every hop honors the per-direction HTLC window
            assert nxt.amount_msat >= int(g.htlc_min_msat[d, c])
            hmax = int(g.htlc_max_msat[d, c])
            assert not hmax or nxt.amount_msat <= hmax
        # equal-cost tie-breaks may pick different hops, but the cost
        # AT THE SOURCE (what the payer funds) must then agree too
        if [h.scid for h in droute] == [h.scid for h in hroute]:
            assert dsrc == hsrc


def test_randomized_corpus_parity(tmp_path):
    """Randomized graphs × randomized queries: identical outcomes and
    total cost, including disabled channels, htlc_min floors, tight
    htlc_max caps and amounts spanning 4 orders of magnitude."""
    rng = np.random.default_rng(42)
    for seed in (3, 11, 29):
        g = _net(tmp_path, 100, 40, seed)
        # ragged constraints: disable some channels, floor/cap others
        nc = g.n_channels
        off = rng.integers(0, nc, nc // 10)
        g.enabled[:, off] = False
        floor = rng.integers(0, nc, nc // 8)
        g.htlc_min_msat[:, floor] = 50_000
        cap = rng.integers(0, nc, nc // 8)
        g.htlc_max_msat[:, cap] = 80_000
        planes = RoutePlanes.build(g)
        queries = []
        for _ in range(Q):
            a, b = rng.integers(0, g.n_nodes, 2)
            if a == b:
                b = (b + 1) % g.n_nodes
            queries.append(RD.RouteQuery(
                bytes(g.node_ids[a]), bytes(g.node_ids[b]),
                int(rng.integers(1_000, 10_000_000)),
                final_cltv=int(rng.integers(9, 40)),
                riskfactor=int(rng.choice([1, 10, 100]))))
        _assert_parity(g, queries, RD.solve_batch(planes, queries, batch=Q))


# ---------------------------------------------------------------------------
# The dense program against the frozen scatter form, label for label


_EDGE_PLANES = ("edge_src", "edge_dst", "edge_base", "edge_ppm",
                "edge_cltv", "edge_hmin", "edge_hmax")


def _raw_planes(n_real, src, dst, *, rng, enabled=None, **params):
    """RoutePlanes over bare arrays (no gossmap): edges in the CSR's
    destination-major order, padded like `build` pads them."""
    from lightning_tpu.routing.planes import (_MIN_EDGE_PAD,
                                              _MIN_NODE_PAD, _pow2_pad)

    by_dst = np.argsort(dst, kind="stable")
    e_real = len(src)
    n_pad = _pow2_pad(n_real, _MIN_NODE_PAD)
    e_pad = _pow2_pad(e_real, _MIN_EDGE_PAD)

    def plane(vals, dtype):
        out = np.zeros(e_pad, dtype)
        out[:e_real] = np.asarray(vals)[by_dst]
        return out

    draw = {"base": rng.integers(0, 5_000, e_real),
            "ppm": rng.integers(0, 10_000, e_real),
            "cltv": rng.integers(6, 145, e_real),
            "hmin": np.zeros(e_real, np.int64),
            "hmax": np.zeros(e_real, np.int64)}
    draw.update(params)
    return RoutePlanes(
        g=None, topo_version=0, params_version=0, n_real=n_real,
        n_pad=n_pad, e_real=e_real, e_pad=e_pad,
        edge_src=plane(src, np.int32), edge_dst=plane(dst, np.int32),
        edge_chan=plane(np.arange(e_real) // 2, np.int32),
        edge_dir=plane(np.arange(e_real) % 2, np.int8),
        edge_base=plane(draw["base"], np.int64),
        edge_ppm=plane(draw["ppm"], np.int64),
        edge_cltv=plane(draw["cltv"], np.int64),
        edge_hmin=plane(draw["hmin"], np.int64),
        edge_hmax=plane(draw["hmax"], np.int64),
        edge_enabled=plane(np.ones(e_real, bool) if enabled is None
                           else enabled, bool),
        edge_cap_sat=np.zeros(e_pad, np.float32))


def _both_ways(a, b):
    """Channels a[i]—b[i] as directed edges, both directions."""
    return np.concatenate([a, b]), np.concatenate([b, a])


def _lanes(rng, n_real, lanes=Q, amount=None, rf=None):
    """Per-lane operands in the oracle's order: src, dst, amount,
    final_cltv, riskfactor.  The program takes all but final_cltv."""
    src = rng.integers(0, n_real, lanes).astype(np.int32)
    dst = ((src + 1 + rng.integers(0, n_real - 1, lanes))
           % n_real).astype(np.int32)
    if amount is None:
        amount = (10 ** rng.uniform(3, 9, lanes)).astype(np.int64)
    if rf is None:
        rf = np.full(lanes, 10, np.int64)
    return (src, dst, np.asarray(amount, np.int64),
            rng.integers(9, 40, lanes).astype(np.int64),
            np.asarray(rf, np.int64))


def _case_corpus(seed):
    """The randomized corpus's graphs and constraints, from gossmaps."""
    def build(tmp_path):
        rng = np.random.default_rng(42 + seed)
        g = _net(tmp_path, 100, 40, seed)
        nc = g.n_channels
        g.enabled[:, rng.integers(0, nc, nc // 10)] = False
        g.htlc_min_msat[:, rng.integers(0, nc, nc // 8)] = 50_000
        g.htlc_max_msat[:, rng.integers(0, nc, nc // 8)] = 80_000
        planes = RoutePlanes.build(g)
        lanes = _lanes(rng, g.n_nodes,
                       amount=rng.integers(1_000, 10_000_000, Q),
                       rf=rng.choice([1, 10, 100], Q))
        return planes, np.tile(planes.edge_enabled, (Q, 1)), lanes
    return build


def _case_hub(tmp_path):
    """One node with 120 channels among nodes of degree 2-3: the
    doubling steps follow the hub (7), the other segments are short."""
    rng = np.random.default_rng(5)
    n = 200
    ring = np.arange(n)
    chords = rng.choice(n, 60, replace=False)
    a = np.concatenate([ring, chords, np.full(120, 7)])
    b = np.concatenate([(ring + 1) % n, (chords + 17) % n,
                        rng.choice(np.delete(ring, 7), 120, replace=False)])
    planes = _raw_planes(n, *_both_ways(a, b), rng=rng)
    assert RD.edge_order(planes).steps == 7
    return planes, np.tile(planes.edge_enabled, (Q, 1)), _lanes(rng, n)


def _case_ties(tmp_path):
    """Uniform fees and every channel laid twice: masses of equal-cost
    candidates that only the RoutePlanes edge index tells apart."""
    rng = np.random.default_rng(6)
    n = 24
    a = rng.integers(0, n, 50)
    b = (a + 1 + rng.integers(0, n - 1, 50)) % n
    src, dst = _both_ways(np.tile(a, 2), np.tile(b, 2))
    e = len(src)
    planes = _raw_planes(n, src, dst, rng=rng, base=np.full(e, 1000),
                         ppm=np.full(e, 100), cltv=np.full(e, 6))
    return (planes, np.tile(planes.edge_enabled, (Q, 1)),
            _lanes(rng, n, amount=np.full(Q, 123_456)))


def _case_excluded(tmp_path):
    """Each lane masks edges of its own (excluded scids, disabled
    channels): the per-flush mask reaches the device in its order."""
    rng = np.random.default_rng(7)
    n = 40
    a = rng.integers(0, n, 110)
    b = (a + 1 + rng.integers(0, n - 1, 110)) % n
    planes = _raw_planes(n, *_both_ways(a, b), rng=rng,
                         enabled=rng.random(220) < 0.9)
    ok = np.tile(planes.edge_enabled, (Q, 1))
    ok &= rng.random(ok.shape) < 0.85
    return planes, ok, _lanes(rng, n)


def _case_padding(e_real):
    """`e_real` edges in an `e_pad` of 256: none, one and many padding
    rows behind the sorted edges."""
    def build(tmp_path):
        rng = np.random.default_rng(8)
        n = 50
        src = rng.integers(0, n, e_real)
        dst = (src + 1 + rng.integers(0, n - 1, e_real)) % n
        planes = _raw_planes(
            n, src, dst, rng=rng,
            hmin=rng.integers(0, 2_000, e_real),
            hmax=rng.integers(0, 3, e_real) * rng.integers(
                1, 1 << 40, e_real))
        assert (planes.e_real, planes.e_pad) == (e_real, 256)
        return planes, np.tile(planes.edge_enabled, (Q, 1)), _lanes(rng, n)
    return build


def _case_two_riskfactors(tmp_path):
    rng = np.random.default_rng(9)
    n = 40
    a = rng.integers(0, n, 100)
    b = (a + 1 + rng.integers(0, n - 1, 100)) % n
    planes = _raw_planes(n, *_both_ways(a, b), rng=rng)
    return (planes, np.tile(planes.edge_enabled, (Q, 1)),
            _lanes(rng, n, rf=np.array([1, 1000] * (Q // 2))))


def _case_overflow(tmp_path):
    """One lane's amount passes the int64 guard on the first hop; its
    flag rises and the other lanes' labels do not move for it."""
    rng = np.random.default_rng(10)
    n = 30
    a = rng.integers(0, n, 80)
    b = (a + 1 + rng.integers(0, n - 1, 80)) % n
    planes = _raw_planes(n, *_both_ways(a, b), rng=rng,
                         ppm=np.full(160, 10_000))
    amount = (10 ** rng.uniform(3, 9, Q)).astype(np.int64)
    amount[2] = RD.OVF_LIMIT // 10_000 + 1
    return (planes, np.tile(planes.edge_enabled, (Q, 1)),
            _lanes(rng, n, amount=amount))


def _block_planes(degrees, dst, rng, **params):
    """Planes whose device order lays node u's out-edges out as one run
    of `degrees[u]` rows from row sum(degrees[:u]) on, so a case puts
    runs where it wants them against the segmented minimum's aligned
    32-row blocks (`RD.FOLD_STEPS`)."""
    n = len(degrees)
    src = np.repeat(np.arange(n), degrees)
    planes = _raw_planes(n, src, dst, rng=rng, **params)
    assert np.array_equal(RD.edge_order(planes).seg_last[:n],
                          np.cumsum(degrees) - 1)
    return planes


def _random_far_ends(rng, degrees, *, avoid=None):
    """Each edge's receiving node, drawn from the nodes but its own and
    `avoid`."""
    n = len(degrees)
    src = np.repeat(np.arange(n), degrees)
    pool = np.setdiff1d(np.arange(n), [] if avoid is None else [avoid])
    dst = rng.choice(pool, len(src))
    clash = dst == src
    while clash.any():
        dst[clash] = rng.choice(pool, clash.sum())
        clash = dst == src
    return dst


# rows 0-4, 5-35 (starts mid-block, crosses into the next), 36-67 (32),
# 68-100 (33), 101-163 (63), 164-227 (64), 228-292 (65), 293-383 (ends
# on a block end), 384-447 (two whole blocks); then 191 runs of 2: the
# hub case's key, (n_pad, e_pad, steps) = (256, 1024, 7)
_BLOCK_RUNS = [5, 31, 32, 33, 63, 64, 65, 91, 64] + [2] * 191


def _case_block_runs(tmp_path):
    """Runs of 31, 32, 33, 63, 64 and 65 rows against the aligned
    blocks, at `steps` 7: passes 6 and 7 run over the block winners."""
    rng = np.random.default_rng(11)
    degrees = np.array(_BLOCK_RUNS)
    planes = _block_planes(degrees, _random_far_ends(rng, degrees), rng)
    seg_last = RD.edge_order(planes).seg_last
    assert RD.edge_order(planes).steps == 7
    assert (seg_last[1] // 32, (seg_last[1] - 30) // 32) == (1, 0)
    assert seg_last[7] % 32 == 31 and seg_last[8] == 447
    return planes, np.tile(planes.edge_enabled, (Q, 1)), _lanes(rng, 200)


def _case_block_runs_6(tmp_path):
    """The same at the power-law family's key (64, 256, 6): runs of 40
    (rows 2-41), 33 (42-74) and 64 rows (75-138, neither end on a block
    edge)."""
    rng = np.random.default_rng(14)
    degrees = np.array([2, 40, 33, 64] + [2] * 56)
    planes = _block_planes(degrees, _random_far_ends(rng, degrees), rng)
    assert RD.edge_order(planes).steps == 6
    return planes, np.tile(planes.edge_enabled, (Q, 1)), _lanes(rng, 60)


def _case_block_padding(tmp_path):
    """The longest run last among the real edges (rows 447-546, from a
    block's last row to the middle of a block) and 477 padding rows
    behind it."""
    rng = np.random.default_rng(13)
    degrees = np.array([3] * 149 + [100])
    planes = _block_planes(degrees, _random_far_ends(rng, degrees), rng)
    assert RD.edge_order(planes).steps == 7
    assert (planes.e_real, planes.e_pad) == (547, 1024)
    return planes, np.tile(planes.edge_enabled, (Q, 1)), _lanes(rng, 150)


# the block-ties case: hub 7's 91 edges (rows 293-383, one to each of
# nodes 9-99 in that order) and the four of its neighbours with an edge
# to the payee, at rows 303 (block 9), 313 (block 9), 353 and 368
# (block 11, the run's last block)
_TIES_HUB, _TIES_PAYEE, _TIES_FAN = 7, 199, (19, 29, 69, 84)


def _case_block_ties(tmp_path):
    """One long run whose equal-cost candidates lie in different
    blocks: uniform fees, and the hub reaches the payee through any of
    four neighbours at the same cost, so the earliest RoutePlanes index
    among them must win across the second level's blocks."""
    rng = np.random.default_rng(12)
    degrees = np.array(_BLOCK_RUNS)
    dst = _random_far_ends(rng, degrees, avoid=_TIES_PAYEE)
    first = np.cumsum(degrees) - degrees
    dst[first[_TIES_HUB]:first[_TIES_HUB] + 91] = np.arange(9, 100)
    dst[first[list(_TIES_FAN)]] = _TIES_PAYEE
    e = len(dst)
    planes = _block_planes(degrees, dst, rng, base=np.full(e, 1000),
                           ppm=np.full(e, 100), cltv=np.full(e, 6))
    src = rng.choice(np.arange(9, 199), Q, replace=False).astype(np.int32)
    src[:2] = _TIES_HUB
    lanes = _lanes(rng, 200, amount=np.full(Q, 123_456))
    dst_lane = np.where(np.arange(Q) < 6, _TIES_PAYEE, (src + 50) % 199)
    return (planes, np.tile(planes.edge_enabled, (Q, 1)),
            (src, dst_lane.astype(np.int32)) + lanes[2:])


_LABEL_CASES = {
    "corpus-3": _case_corpus(3), "corpus-11": _case_corpus(11),
    "corpus-29": _case_corpus(29), "hub": _case_hub, "ties": _case_ties,
    "excluded": _case_excluded, "no-padding": _case_padding(256),
    "one-padding-row": _case_padding(255),
    "padding": _case_padding(150),
    "two-riskfactors": _case_two_riskfactors, "overflow": _case_overflow,
    "block-runs": _case_block_runs, "block-runs-steps-6": _case_block_runs_6,
    "block-padding": _case_block_padding, "block-ties": _case_block_ties,
}


def _oracle_and_program(planes, ok, lanes):
    """Both programs on one batch: the oracle with all five per-lane
    operands, the dense program without `final_cltv` (it has no such
    operand).  Holds the three outputs equal and returns them."""
    import jax.numpy as jnp
    from jax import enable_x64

    import route_scatter_oracle as oracle

    order = RD.edge_order(planes)
    src, dst, amount, _, rf = lanes
    with enable_x64():
        want = oracle.jit_scatter_route(planes.n_pad, RD.DEFAULT_MAX_HOPS)(
            *(jnp.asarray(getattr(planes, name)) for name in _EDGE_PLANES),
            jnp.asarray(ok), *map(jnp.asarray, lanes))
        plane_args, _, _ = RD._device_plane_args(planes)
        got = RD._jit_route(planes.n_pad, RD.DEFAULT_MAX_HOPS, order.steps)(
            *plane_args, jnp.asarray(ok[:, order.perm]),
            *map(jnp.asarray, (src, dst, amount, rf)))
    for name, w, g in zip(("dist_src", "via", "ovf"), want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert (w.shape, w.dtype) == (g.shape, g.dtype), name
        assert np.array_equal(w, g), (name, np.argwhere(w != g)[:5])
    return tuple(np.asarray(x) for x in want)


@pytest.mark.parametrize("case", list(_LABEL_CASES))
def test_labels_equal_the_scatter_form(tmp_path, case):
    """`(dist[src], via, ovf)` of the dense program, label for label
    equal to the program it replaced (tests/route_scatter_oracle.py),
    `via` in RoutePlanes edge indices for every node of every lane."""
    planes, ok, lanes = _LABEL_CASES[case](tmp_path)
    dist_src, via, ovf = _oracle_and_program(planes, ok, lanes)
    # the case exercises what it names
    assert (dist_src < RD.INF_COST).sum() >= Q // 2 or case == "overflow"
    assert (via >= 0).any()
    assert ovf.any() == (case == "overflow")


def test_block_ties_the_earliest_edge_wins(tmp_path):
    """The block-ties case, read out: for every lane paying the payee
    the hub's `via` is its edge to node 19, the lowest RoutePlanes index
    of four at one cost, which stands in the run's first block; the
    other three lie in that block and in the run's last, whose row the
    node reads first."""
    planes, ok, lanes = _case_block_ties(tmp_path)
    order = RD.edge_order(planes)
    hub_edges = np.nonzero(planes.edge_src[:planes.e_real] == _TIES_HUB)[0]
    fan = hub_edges[np.isin(planes.edge_dst[hub_edges], _TIES_FAN)]
    assert [order.inv[e] // 32 for e in fan] == [9, 9, 11, 11]
    assert order.seg_last[_TIES_HUB] // 32 == 11
    _, via, _ = _oracle_and_program(planes, ok, lanes)
    pays = lanes[1] == _TIES_PAYEE
    assert pays.sum() == 6
    assert (via[pays, _TIES_HUB] == fan.min()).all()
    assert planes.edge_dst[fan.min()] == _TIES_FAN[0]


@pytest.mark.parametrize("seed", [3, 29])
def test_lanes_that_differ_only_in_final_cltv(tmp_path, seed):
    """A label carries no delay: lanes of one batch that differ only in
    `final_cltv` (9, 18, 144) get one `dist_src` and one `via` from the
    program, which has no such operand, as from the oracle, which is
    handed the three values; `solve_batch` still returns each query its
    own delays, the host Dijkstra's, summed by `_reconstruct`."""
    g = _net(tmp_path, 100, 40, seed)
    planes = RoutePlanes.build(g)
    rng = np.random.default_rng(seed)
    pairs = []
    while len(pairs) < 3:
        a, b = (int(x) for x in rng.choice(g.n_nodes, 2, replace=False))
        q = _q(g, a, b, rng.integers(1_000, 10_000_000))
        if _host(g, q)[0] == "ok":
            pairs.append((a, b, q.amount_msat))
    groups = [(0, 1, 2), (3, 4, 5), (6, 7)]
    cltvs = [9, 18, 144, 9, 18, 144, 9, 144]
    of_lane = [pairs[i] for i, grp in enumerate(groups) for _ in grp]
    src, dst, amount = (np.array(col) for col in zip(*of_lane))
    lanes = (src.astype(np.int32), dst.astype(np.int32),
             amount.astype(np.int64), np.array(cltvs, np.int64),
             np.full(Q, 10, np.int64))
    dist_src, via, ovf = _oracle_and_program(
        planes, np.tile(planes.edge_enabled, (Q, 1)), lanes)
    assert not ovf.any() and (dist_src < RD.INF_COST).all()
    for first, *rest in groups:
        for i in rest:
            assert dist_src[i] == dist_src[first]
            assert np.array_equal(via[i], via[first])
    queries = [_q(g, a, b, amt, final_cltv=cltv, riskfactor=10)
               for (a, b, amt), cltv in zip(of_lane, cltvs)]
    results = RD.solve_batch(planes, queries, batch=Q)
    _assert_parity(g, queries, results)
    for grp in groups:
        # (amount, delay) at the source: the host's, and a group's
        # delays apart by exactly what its lanes' final_cltv are
        assert [results[i][2] for i in grp] == \
            [_host(g, queries[i])[2] for i in grp]
        over = {results[i][2][1] - cltvs[i] for i in grp}
        assert len(over) == 1 and over.pop() > 0


def test_excluded_scids_and_unreachable(tmp_path):
    g = _net(tmp_path, 60, 16, seed=7)
    planes = RoutePlanes.build(g)
    a, b = bytes(g.node_ids[0]), bytes(g.node_ids[g.n_nodes - 1])
    base = DJ.getroute(g, a, b, 500_000)
    used = {h.scid for h in base}
    # isolate one node entirely: every query to it must be noroute
    iso = g.n_nodes // 2
    iso_chans = np.nonzero((g.node1 == iso) | (g.node2 == iso))[0]
    g.enabled[:, iso_chans] = False
    planes = RoutePlanes.build(g)
    queries = [
        RD.RouteQuery(a, b, 500_000, excluded_scids=used),
        RD.RouteQuery(a, bytes(g.node_ids[iso]), 10_000),
        RD.RouteQuery(a, b, 500_000),
        RD.RouteQuery(a, a, 500_000),   # src==dst: NoRoute, never "ok"
    ]
    results = RD.solve_batch(planes, queries, batch=Q)
    assert results[1][0] == "noroute"
    assert results[3] == ("noroute", "source is destination")
    queries, results = queries[:3], results[:3]
    _assert_parity(g, queries, results)
    if results[0][0] == "ok":
        assert used.isdisjoint({h.scid for h in results[0][1]})


def test_tie_break_deterministic(tmp_path):
    """Uniform fees create masses of equal-cost candidates; the stated
    rule (lowest CSR edge index wins, labels only replaced when
    strictly cheaper) must give a deterministic result that still
    prices identically to the host solver."""
    g = _net(tmp_path, 80, 20, seed=13)
    for d in (0, 1):
        g.fee_base_msat[d, :] = 1000
        g.fee_ppm[d, :] = 100
        g.cltv_delta[d, :] = 6
        g.htlc_max_msat[d, :] = 0
        g.htlc_min_msat[d, :] = 0
    planes = RoutePlanes.build(g)
    rng = np.random.default_rng(1)
    queries = []
    for _ in range(Q):
        a, b = rng.integers(0, g.n_nodes, 2)
        if a == b:
            b = (b + 1) % g.n_nodes
        queries.append(RD.RouteQuery(bytes(g.node_ids[a]),
                                     bytes(g.node_ids[b]), 123_456))
    r1 = RD.solve_batch(planes, queries, batch=Q)
    r2 = RD.solve_batch(planes, queries, batch=Q)
    for x, y in zip(r1, r2):
        assert x[0] == y[0]
        if x[0] == "ok":
            assert [(h.scid, h.direction) for h in x[1]] == \
                [(h.scid, h.direction) for h in y[1]]
    _assert_parity(g, queries, r1)


def test_overflow_flags_fall_back(tmp_path):
    """Amounts whose fee/risk products exceed the int64 guard must come
    back as explicit fallbacks, never as silently-wrapped routes."""
    g = _net(tmp_path, 40, 10, seed=5)
    g.htlc_max_msat[:, :] = 0          # uncapped: amount reaches pricing
    g.fee_ppm[:, :] = 10_000
    planes = RoutePlanes.build(g)
    huge = RD.OVF_LIMIT // 10_000 + 1  # a_v * ppm would pass 2^61
    queries = [RD.RouteQuery(bytes(g.node_ids[0]),
                             bytes(g.node_ids[g.n_nodes - 1]), huge),
               RD.RouteQuery(bytes(g.node_ids[0]),
                             bytes(g.node_ids[g.n_nodes - 1]), 10_000)]
    res = RD.solve_batch(planes, queries, batch=Q)
    assert res[0] == ("fallback", RD.R_OVERFLOW)
    assert res[1][0] in ("ok", "noroute")
    _assert_parity(g, queries[1:], res[1:])


def test_planes_version_refresh(tmp_path):
    """Param-only gossip updates refresh planes in place; a direction's
    FIRST update is a topology change and rebuilds them."""
    g = _net(tmp_path, 40, 10, seed=9)
    planes = RoutePlanes.build(g)
    assert RoutePlanes.current(g, planes) is planes     # fresh → reused
    scid = int(g.scids[0])
    ts = int(g.timestamps[0, 0])
    assert g.apply_channel_update(
        scid, 0, timestamp=ts + 1, disabled=False, cltv_delta=144,
        htlc_min_msat=7, htlc_max_msat=0, fee_base_msat=99_999,
        fee_ppm=77)
    p2 = RoutePlanes.current(g, planes)
    # param-only bump: NEW object (an in-flight solve keeps its own
    # consistent revision) sharing the topology arrays
    assert p2 is not planes
    assert p2.edge_src is planes.edge_src
    assert p2.topo_version == planes.topo_version
    e = p2.edges_of_channel(0)
    sel = e[p2.edge_dir[e] == 0]
    assert p2.edge_base[sel[0]] == 99_999
    assert p2.edge_hmin[sel[0]] == 7
    # the cached revision the solve thread holds is untouched
    assert planes.edge_base[sel[0]] != 99_999
    # stale timestamp refused
    assert not g.apply_channel_update(
        scid, 0, timestamp=ts, disabled=False, cltv_delta=1,
        htlc_min_msat=0, htlc_max_msat=0, fee_base_msat=1, fee_ppm=1)
    # wipe a direction then re-apply: first update = topology rebuild
    g.timestamps[1, 3] = 0
    g._build_adjacency()
    p3 = RoutePlanes.current(g, p2)
    assert p3 is not p2
    assert g.apply_channel_update(
        int(g.scids[3]), 1, timestamp=ts + 2, disabled=False,
        cltv_delta=6, htlc_min_msat=0, htlc_max_msat=0,
        fee_base_msat=1, fee_ppm=1)
    assert RoutePlanes.current(g, p3) is not p3
    # a patch reaches the device planes at the rows the edge order
    # gives the touched edges (the sort moved them), the enabled mask
    # included
    base = RoutePlanes.build(g)
    q = [RD.RouteQuery(bytes(g.node_ids[0]),
                       bytes(g.node_ids[g.n_nodes - 1]), 250_000)]
    RD.solve_batch(base, q, batch=Q)                # uploads the planes
    for c in (1, 2, 5):
        assert g.apply_channel_update(
            int(g.scids[c]), 0, timestamp=int(g.timestamps[0, c]) + 1,
            disabled=(c == 2),
            cltv_delta=40 + c, htlc_min_msat=c, htlc_max_msat=0,
            fee_base_msat=1_000 + c, fee_ppm=c)
    patched = RoutePlanes.current(g, base)
    patch, order = patched.patch_idx, RD.edge_order(patched)
    assert patch is not None and len(patch) == 3
    assert (order.inv[patch] != patch).any()        # the sort moved them
    plane_args, edge_ok, _ = RD._device_plane_args(patched)
    assert patched.patch_idx is None
    for name, dev in zip(RD._PLANE_ORDER[:7], plane_args):
        assert np.array_equal(np.asarray(dev),
                              getattr(patched, name)[order.perm]), name
    assert np.array_equal(edge_ok, patched.edge_enabled[order.perm])
    assert not np.array_equal(edge_ok, base.dev["edge_ok"])
    _assert_parity(g, q, RD.solve_batch(patched, q, batch=Q))
    # the refreshed planes still price identically to the host
    g2 = g
    planes = RoutePlanes.current(g2, None)
    q = [RD.RouteQuery(bytes(g2.node_ids[0]),
                       bytes(g2.node_ids[g2.n_nodes - 1]), 250_000)]
    _assert_parity(g2, q, RD.solve_batch(planes, q, batch=Q))


def test_route_service_coalesces_and_falls_back(tmp_path):
    """The flush-loop front-end: concurrent queries coalesce into one
    device dispatch; single queries and inexpressible ones take the
    host dijkstra with a metered reason."""
    from lightning_tpu import obs

    g = _net(tmp_path, 60, 16, seed=21)
    rng = np.random.default_rng(2)

    def _counter(name, **labels):
        fam = obs.snapshot()["metrics"].get(name, {})
        for s in fam.get("samples", ()):
            if s.get("labels", {}) == labels:
                return s["value"]
        return 0.0

    async def scenario():
        svc = RD.RouteService(lambda: g, flush_ms=5.0, batch=Q,
                              host_max=1)
        svc.start()
        try:
            pairs = []
            for _ in range(Q):
                a, b = rng.integers(0, g.n_nodes, 2)
                if a == b:
                    b = (b + 1) % g.n_nodes
                pairs.append((bytes(g.node_ids[a]), bytes(g.node_ids[b])))
            dev0 = _counter("clntpu_route_queries_total",
                            path="device", outcome="ok")
            got = await asyncio.gather(
                *(svc.getroute(a, b, 1_000_000) for a, b in pairs),
                return_exceptions=True)
            for (a, b), res in zip(pairs, got):
                try:
                    exp = DJ.getroute(g, a, b, 1_000_000)
                except DJ.NoRoute:
                    assert isinstance(res, DJ.NoRoute)
                    continue
                assert not isinstance(res, BaseException), res
                assert RD.route_cost_msat(g, res, 10) == \
                    RD.route_cost_msat(g, exp, 10)
            assert _counter("clntpu_route_queries_total",
                            path="device", outcome="ok") > dev0
            # single below-occupancy query → host path, metered reason
            h0 = _counter("clntpu_route_fallback_total",
                          reason=RD.R_BELOW_OCCUPANCY)
            a, b = pairs[0]
            await svc.getroute(a, b, 1_000_000)
            assert _counter("clntpu_route_fallback_total",
                            reason=RD.R_BELOW_OCCUPANCY) == h0 + 1
            # custom max_hops is planes-inexpressible → host, metered;
            # ride a filler so the flush clears the occupancy floor
            m0 = _counter("clntpu_route_fallback_total",
                          reason=RD.R_MAX_HOPS)
            res = await asyncio.gather(
                svc.getroute(a, b, 1_000_000, max_hops=3),
                *(svc.getroute(*p, 1_000_000) for p in pairs[1:3]),
                return_exceptions=True)
            assert _counter("clntpu_route_fallback_total",
                            reason=RD.R_MAX_HOPS) == m0 + 1
            if not isinstance(res[0], BaseException):
                assert len(res[0]) <= 3
            # unknown node raises KeyError (dijkstra parity)
            with pytest.raises((KeyError, DJ.NoRoute)):
                await svc.getroute(b"\x02" + b"\xee" * 32, b, 1_000)
            # with_source returns the payer-side (amount, delay) pair
            route, (src_amt, src_dly) = await svc.getroute(
                a, b, 1_000_000, with_source=True)
            _, (exp_amt, exp_dly) = DJ.getroute(g, a, b, 1_000_000,
                                                with_source=True)
            assert (src_amt, src_dly) == (exp_amt, exp_dly)
        finally:
            await svc.close()
        # post-close queries must not hang on a dead flush loop: they
        # solve inline on the host (metered as reason=not_running)
        n0 = _counter("clntpu_route_fallback_total",
                      reason=RD.R_NOT_RUNNING)
        route = await svc.getroute(*pairs[0], 1_000_000)
        assert route
        assert _counter("clntpu_route_fallback_total",
                        reason=RD.R_NOT_RUNNING) == n0 + 1

    _run(scenario())


def _hist_count(name: str) -> float:
    from lightning_tpu import obs

    fam = obs.snapshot()["metrics"].get(name, {})
    return sum(s.get("count", 0) for s in fam.get("samples", ()))


def _hist_sum(name: str) -> float:
    from lightning_tpu import obs

    fam = obs.snapshot()["metrics"].get(name, {})
    return sum(s.get("sum", 0.0) for s in fam.get("samples", ()))


def _flush_stage_scenario(g, n_queries: int):
    """n_queries concurrent getroute calls through one device flush;
    returns (span records, the route flight records)."""
    from lightning_tpu.obs import flight
    from lightning_tpu.utils import trace

    rng = np.random.default_rng(2)
    records: list[dict] = []
    trace.add_tap(records.append)
    flight.reset_for_tests()

    async def scenario():
        svc = RD.RouteService(lambda: g, flush_ms=20.0, batch=Q,
                              host_max=1)
        svc.start()
        try:
            pairs = []
            for _ in range(n_queries):
                a, b = rng.integers(0, g.n_nodes, 2)
                if a == b:
                    b = (b + 1) % g.n_nodes
                pairs.append((bytes(g.node_ids[a]), bytes(g.node_ids[b])))
            return await asyncio.gather(
                *(svc.getroute(a, b, 1_000_000) for a, b in pairs),
                return_exceptions=True)
        finally:
            await svc.close()

    try:
        answers = _run(scenario())
    finally:
        trace.remove_tap(records.append)
    return records, flight.recent("route"), answers


def test_route_flush_stage_spans_and_clocks(tmp_path):
    """A device flush of Q queries leaves route/pack, route/device,
    route/reconstruct (worker thread) and route/resolve (loop) whose
    parent chains end at route/flush, one queue-wait observation per
    query, and a flight record with its stage fields filled."""
    g = _net(tmp_path, 60, 16, seed=21)
    waits0 = _hist_count("clntpu_route_queue_wait_seconds")
    records, flights, _ = _flush_stage_scenario(g, Q)
    assert _hist_count("clntpu_route_queue_wait_seconds") == waits0 + Q

    by_id = {r["span_id"]: r for r in records}

    def chain(rec):
        names = []
        while rec is not None:
            names.append(rec["name"])
            rec = by_id.get(rec["parent_id"])
        return names

    flush = [r for r in records if r["name"] == "route/flush"]
    assert len(flush) == 1
    for name in ("route/pack", "route/device", "route/reconstruct"):
        recs = [r for r in records if r["name"] == name]
        assert len(recs) == 1, name
        assert chain(recs[0]) == [name, "route/dispatch", "route/flush"]
        assert recs[0]["tid"] != flush[0]["tid"]       # worker thread
    resolve = [r for r in records if r["name"] == "route/resolve"]
    assert len(resolve) == 1
    assert chain(resolve[0]) == ["route/resolve", "route/flush"]
    assert resolve[0]["tid"] == flush[0]["tid"]        # on the loop
    disp = [r for r in records if r["name"] == "route/dispatch"]
    assert len(disp) == 1                 # each dispatch shows once
    stage_ns = sum(r["duration_ns"] for r in records if r["name"] in (
        "route/pack", "route/device", "route/reconstruct"))
    assert stage_ns <= disp[0]["duration_ns"] <= flush[0]["duration_ns"]

    assert len(flights) == 1
    rec = flights[0]
    assert rec["outcome"] == "ok" and rec["n_real"] == Q
    assert rec["dispatch_id"] == disp[0]["dispatch_id"]
    pack = next(r for r in records if r["name"] == "route/pack")
    recon = next(r for r in records if r["name"] == "route/reconstruct")
    assert rec["prep_ms"] == pytest.approx(pack["duration_ns"] / 1e6,
                                           abs=2e-3)
    assert rec["readback_ms"] == pytest.approx(
        recon["duration_ns"] / 1e6, abs=2e-3)
    assert rec["prep_ms"] > 0 and rec["readback_ms"] > 0
    # the flusher's own coalescing wait: at most the 20 ms flush window
    # (the batch fills at once here), never a query's wait
    assert 0 <= rec["queue_wait_ms"] < 1_000
    # serial family: the stages add up to the flush's wall time
    total = rec["prep_ms"] + rec["dispatch_ms"] + rec["readback_ms"]
    assert total == pytest.approx(flush[0]["duration_ns"] / 1e6, abs=0.01)


def test_route_perf_window_fits_wall_span_back_to_back(
        tmp_path, monkeypatch):
    """Flushes back to back, each query waiting out the flush before
    its own: that wait goes to the histogram, not onto the flusher's
    serial path, so getperf's route section keeps window_s within
    wall_span_s and its rate is the one the callers saw."""
    import time

    from lightning_tpu.obs import attribution, flight

    g = _net(tmp_path, 60, 16, seed=21)
    real = RD.solve_batch

    def slow(*a, **kw):
        time.sleep(0.05)
        return real(*a, **kw)

    monkeypatch.setattr(RD, "solve_batch", slow)
    rng = np.random.default_rng(3)
    pairs = []
    for _ in range(2 * Q):
        a, b = rng.integers(0, g.n_nodes, 2)
        if a == b:
            b = (b + 1) % g.n_nodes
        pairs.append((bytes(g.node_ids[a]), bytes(g.node_ids[b])))
    flight.reset_for_tests()
    waits0 = _hist_count("clntpu_route_queue_wait_seconds")
    sum0 = _hist_sum("clntpu_route_queue_wait_seconds")

    async def scenario():
        svc = RD.RouteService(lambda: g, flush_ms=1.0, batch=Q,
                              host_max=1)
        svc.start()

        async def caller(i, pair):
            # the second half arrives while the first flush runs
            await asyncio.sleep(0.01 * (i // Q))
            for _ in range(3):
                try:
                    await svc.getroute(*pair, 1_000_000)
                except DJ.NoRoute:
                    pass

        t0 = time.perf_counter()
        try:
            await asyncio.gather(*(caller(i, p)
                                   for i, p in enumerate(pairs)))
            return time.perf_counter() - t0
        finally:
            await svc.close()

    wall = _run(scenario())
    records = flight.recent("route")
    assert len(records) >= 3
    n = _hist_count("clntpu_route_queue_wait_seconds") - waits0
    assert n == 6 * Q
    mean_wait = (_hist_sum("clntpu_route_queue_wait_seconds") - sum0) / n
    assert mean_wait > 0.02            # queries did wait under a flush
    sec = attribution.attribute_family("route", records)
    assert sec["items"] == 6 * Q
    # ... and the flusher did not: its own wait is a sliver of that
    assert sec["stages"]["queue_wait_s"] / len(records) < mean_wait / 2
    assert sec["window_s"] <= sec["wall_span_s"] + 1e-3
    assert sec["wall_span_s"] <= wall + 1e-3
    assert sec["throughput_per_s"] >= 6 * Q / wall


def test_forced_reconstruct_fallback_leaves_host_solve_span(
        tmp_path, monkeypatch):
    """A device route that fails its own reconstruction is re-solved on
    the loop inside a route/host_solve span that names the reason."""
    g = _net(tmp_path, 60, 16, seed=21)
    real = RD._reconstruct
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("predecessor walk diverged")
        return real(*a, **kw)

    monkeypatch.setattr(RD, "_reconstruct", flaky)
    records, flights, answers = _flush_stage_scenario(g, Q)
    assert calls["n"] >= 1
    host = [r for r in records if r["name"] == "route/host_solve"]
    assert len(host) == 1
    assert host[0]["attributes"] == {"reason": RD.R_RECONSTRUCT}
    assert host[0]["parent"] == "route/flush"
    flush = next(r for r in records if r["name"] == "route/flush")
    assert host[0]["tid"] == flush["tid"]              # on the loop
    # every caller still got its answer
    assert not any(isinstance(a, BaseException)
                   and not isinstance(a, DJ.NoRoute) for a in answers)


def test_div_const_is_floor_division():
    """The sweep's quotients by 10^6 and by the risk denominator are a
    multiply-high by a magic number: exact on [0, 2^62), the range the
    overflow guard leaves a priced edge's products in."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    rng = np.random.default_rng(3)
    for d in (1_000_000, RD._RISK_DENOM):
        top = (1 << 62) - 1
        vals = [0, 1, d - 1, d, d + 1, top, top - 1, RD.OVF_LIMIT,
                RD.OVF_LIMIT - 1, RD.OVF_LIMIT + 1]
        vals += [k * d + o for k in (2, 12_345, (1 << 41) - 3, top // d)
                 for o in (-1, 0, 1) if k * d + o <= top]
        vals += rng.integers(0, 1 << 62, 50_000).tolist()
        vals += rng.integers(0, 1 << 40, 50_000).tolist()
        with enable_x64():
            got = np.asarray(jax.jit(lambda x, d=d: RD._div_const(x, d))(
                jnp.asarray(np.array(vals, np.int64))))
        assert got.dtype == np.int64
        assert got.tolist() == [v // d for v in vals]


def _lowered_toy_program(steps: int = 3):
    import jax
    from jax import enable_x64

    e_pad, n_pad = 256, 64
    with enable_x64():
        return RD._jit_route(n_pad, RD.DEFAULT_MAX_HOPS, steps).lower(
            *RD.program_operands(Q, n_pad, e_pad, jax.ShapeDtypeStruct))


def test_route_program_carries_named_scopes():
    """The route program's phases ride the ops' metadata under stable
    names (a device trace shows them whatever number XLA gives the
    while loop); the module keeps the name the benchmark matches."""
    text = _lowered_toy_program().as_text(debug_info=True)
    assert "route_relax" in text and "route_extract" in text
    assert "module @jit_single" in text


def test_route_program_carries_two_label_columns():
    """The loop carries a `[n_pad, 2, lanes]` table of (cost, amount)
    and the doubling passes a `[2, lanes, e_pad]` stack; no int64 array
    of the program has an axis of three, so a delay column (which no
    output reads: `_reconstruct` sums the delays on the host) cannot
    come back unnoticed."""
    import re

    text = _lowered_toy_program().as_text()
    loops = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    assert any(f"tensor<64x2x{Q}xi64>" in ln for ln in loops)
    assert f"tensor<2x{Q}x256xi64>" in text
    assert not re.search(r"tensor<(\d+x)*3x(\d+x)*i64>", text)


def test_steps_5_program_runs_no_second_level():
    """Up to `FOLD_STEPS` the segmented minimum is one level: the
    lowered `steps`-5 program holds no op of the second level (none
    under its scope `route_segmin_blocks`, no `[e_pad / 32, 2, lanes]`
    array of block winners), so a graph of short runs runs the passes
    it ran before the second level existed; at `steps` 6 both are
    there."""
    assert RD.FOLD_STEPS == 5 and RD.segmin_block_passes(5) == 0
    blocks = f"tensor<{256 // 32}x2x{Q}xi64>"
    for steps, engaged in ((5, False), (6, True)):
        text = _lowered_toy_program(steps).as_text(debug_info=True)
        assert ("route_segmin_blocks" in text) == engaged
        assert (blocks in text) == engaged


def test_route_program_holds_no_scatter():
    """A sweep's only indexed operations are row fetches: the per-source
    minimum is a dense segmented reduction, so nothing in the program
    lowers to a scatter of any width (the frozen oracle does)."""
    import jax
    from jax import enable_x64

    import route_scatter_oracle as oracle

    text = _lowered_toy_program().as_text()
    assert "scatter" not in text
    assert "stablehlo.gather" in text
    with enable_x64():
        e32 = jax.ShapeDtypeStruct((256,), "int32")
        e64 = jax.ShapeDtypeStruct((256,), "int64")
        b32 = jax.ShapeDtypeStruct((Q,), "int32")
        b64 = jax.ShapeDtypeStruct((Q,), "int64")
        old = oracle.jit_scatter_route(64, RD.DEFAULT_MAX_HOPS).lower(
            e32, e32, e64, e64, e64, e64, e64,
            jax.ShapeDtypeStruct((Q, 256), "bool"), b32, b32, b64, b64, b64)
    assert "stablehlo.scatter" in old.as_text()   # the check can see one


# ---------------------------------------------------------------------------
# A power-law graph: one hub among nodes of degree 2-8 (the shape of the
# benchmark's `mainnet-tenth-hubs`, at the file's shared planes shape)


def _hub_net(tmp_path, hub_degree, *, bridge=False):
    """A gossmap of 100 channels over 47 nodes: one hub of exactly
    `hub_degree` channels, one to each of its neighbours, and the other
    channels' ends drawn with weights that follow P(k) ~ k^-2.1 on 2..8
    (the law's quantiles, as benchmarks/gen/endpoints_powerlaw.py takes
    them), parallel channels allowed, every channel updated in both
    directions.  `bridge`: the other nodes are two parts joined by no
    channel, so the hub is the only way across.  Returns (gossmap, hub
    index, (part A, part B))."""
    g = _net(tmp_path, 100, 48, seed=31)
    n = g.n_nodes
    assert hub_degree < n <= 48
    rng = np.random.default_rng(31)
    k = np.arange(2, 9, dtype=np.float64)
    cdf = np.cumsum(k ** -2.1)
    cdf /= cdf[-1]
    others = rng.permutation(n)
    hub, others = int(others[0]), others[1:]
    weight = 2.0 + np.searchsorted(cdf, (np.arange(n - 1) + 0.5) / (n - 1))
    parts = (others[0::2], others[1::2])
    a = np.full(g.n_channels, hub)
    b = np.resize(others, g.n_channels)     # the hub's neighbours first
    for c in range(hub_degree, g.n_channels):
        pool = np.arange(c % 2, n - 1, 2) if bridge else np.arange(n - 1)
        x, y = rng.choice(pool, 2, replace=False,
                          p=weight[pool] / weight[pool].sum())
        a[c], b[c] = others[x], others[y]
    g.node1[:], g.node2[:] = np.minimum(a, b), np.maximum(a, b)
    g.htlc_max_msat[:, :] = 0
    g._build_adjacency()
    return g, hub, parts


def _q(g, a, b, amount, **kw):
    return RD.RouteQuery(bytes(g.node_ids[a]), bytes(g.node_ids[b]),
                         int(amount), **kw)


def _hub_queries(g, hub, parts, placement, rng):
    """Q queries with the hub where `placement` puts it."""
    amounts = (10 ** rng.uniform(3, 8, Q)).astype(np.int64)
    others = np.concatenate(parts)
    ends = rng.choice(others, Q, replace=False)
    if placement == "payee":        # every lane pays the hub
        return [_q(g, v, hub, amt) for v, amt in zip(ends, amounts)]
    if placement == "payer":
        return [_q(g, hub, v, amt) for v, amt in zip(ends, amounts)]
    # across: payer in one part, payee in the other
    return [_q(g, rng.choice(parts[i % 2]), rng.choice(parts[1 - i % 2]),
               amt) for i, amt in enumerate(amounts)]


def _service_results(g, queries, *, warm=False):
    """The queries through one RouteService flush, as solve_batch's
    result tuples (for `_assert_parity`)."""
    async def scenario():
        svc = RD.RouteService(lambda: g, flush_ms=20.0, batch=Q,
                              host_max=1)
        if warm:
            await svc.warmup()
        svc.start()
        try:
            got = await asyncio.gather(
                *(svc.getroute(q.source, q.destination, q.amount_msat,
                               with_source=True) for q in queries),
                return_exceptions=True)
        finally:
            await svc.close()
        out = []
        for res in got:
            if isinstance(res, DJ.NoRoute):
                out.append(("noroute", str(res)))
            else:
                assert not isinstance(res, BaseException), res
                out.append(("ok", *res))
        return out

    return _run(scenario())


def _metric(name: str, **labels) -> float:
    """A counter's or gauge's value at exactly these labels."""
    from lightning_tpu import obs

    fam = obs.snapshot()["metrics"].get(name, {})
    return sum(s["value"] for s in fam.get("samples", ())
               if s["labels"] == labels)


@pytest.mark.parametrize("placement,bridge", [
    ("payee", False), ("payer", False), ("across", True)])
def test_power_law_graph_parity_by_hub_placement(tmp_path, placement,
                                                 bridge):
    """One flush of Q lanes through RouteService on the hub graph: the
    hub as every lane's payee (at different amounts), as every lane's
    payer, and as the only bridge between payer and payee; each priced
    as dijkstra prices it.  The steps gauge reads what the planes have,
    the hop histogram what the routes returned hold."""
    g, hub, parts = _hub_net(tmp_path, 24, bridge=bridge)
    steps = RD.edge_order(RoutePlanes.build(g)).steps
    assert steps == 5                   # the hub's 24 channels, not 8
    rng = np.random.default_rng(17)
    queries = _hub_queries(g, hub, parts, placement, rng)
    n0 = _hist_count("clntpu_route_path_hops")
    sum0 = _hist_sum("clntpu_route_path_hops")
    dev0 = _metric("clntpu_route_queries_total", path="device",
                   outcome="ok")
    results = _service_results(g, queries)
    _assert_parity(g, queries, results)
    routes = [r[1] for r in results if r[0] == "ok"]
    assert len(routes) >= Q - 1
    # all of them from the device path, none re-solved on the host
    assert _metric("clntpu_route_queries_total", path="device",
                   outcome="ok") - dev0 == len(routes)
    assert _metric("clntpu_route_segmin_steps") == steps
    assert _hist_count("clntpu_route_path_hops") - n0 == len(routes)
    assert _hist_sum("clntpu_route_path_hops") - sum0 == \
        sum(len(r) for r in routes)
    if placement == "across":
        hub_id = bytes(g.node_ids[hub])
        assert all(hub_id in [h.node_id for h in r[:-1]] for r in routes)


@pytest.mark.parametrize("hub_degree,steps", [(16, 4), (17, 5), (32, 5),
                                              (33, 6)])
def test_steps_rule_at_its_edge(tmp_path, hub_degree, steps):
    """`2^steps >= largest out-degree`, at equality and one past it: a
    run of exactly 2^k rows takes k doubling passes, one row more takes
    k + 1, and both price as dijkstra does, the hub on either end."""
    g, hub, parts = _hub_net(tmp_path, hub_degree)
    planes = RoutePlanes.build(g)
    assert RD.edge_order(planes).steps == steps
    rng = np.random.default_rng(hub_degree)
    for placement in ("payee", "payer"):
        queries = _hub_queries(g, hub, parts, placement, rng)
        results = RD.solve_batch(planes, queries, batch=Q)
        assert sum(r[0] == "ok" for r in results) >= Q - 1
        _assert_parity(g, queries, results)
    assert _metric("clntpu_route_segmin_steps") == steps
    assert _metric("clntpu_route_segmin_block_passes") == max(steps - 5, 0)


def test_hub_crossing_a_power_of_two_retraces_once(tmp_path):
    """The hazard, pinned as it is: the hub's 17th channel gets its
    first channel_update in the hub's direction, the largest out-degree
    crosses 2^4, `steps` goes 4 -> 5 and the next flush runs a program
    the warm-up never saw: answered correctly, and
    clntpu_retrace_total{program="route"} moves once."""
    from lightning_tpu.obs import attribution

    def retraces():
        return _metric("clntpu_retrace_total", program="route")

    g, hub, parts = _hub_net(tmp_path, 17)
    # the hub's last channel, as yet without an update from the hub
    c = int(np.nonzero((g.node1 == hub) | (g.node2 == hub))[0][-1])
    d = 0 if g.node1[c] == hub else 1
    ts = int(g.timestamps[d, c])
    g.timestamps[d, c] = 0
    g._build_adjacency()
    assert RD.edge_order(RoutePlanes.build(g)).steps == 4
    rng = np.random.default_rng(23)
    queries = _hub_queries(g, hub, parts, "payer", rng)
    attribution.reset_for_tests()       # this test's keys are first sights
    r0 = retraces()
    _assert_parity(g, queries, _service_results(g, queries, warm=True))
    assert _metric("clntpu_route_segmin_steps") == 4
    assert _metric("clntpu_route_segmin_block_passes") == 0
    assert retraces() == r0             # the warmed program
    assert g.apply_channel_update(
        int(g.scids[c]), d, timestamp=ts + 1, disabled=False,
        cltv_delta=6, htlc_min_msat=0, htlc_max_msat=0,
        fee_base_msat=1, fee_ppm=1)
    results = _service_results(g, queries)
    _assert_parity(g, queries, results)
    assert _metric("clntpu_route_segmin_steps") == 5
    assert _metric("clntpu_route_segmin_block_passes") == 0
    assert retraces() == r0 + 1
    # the new channel is the cheapest way out of the hub for its peer
    peer = int(g.node2[c] if d == 0 else g.node1[c])
    direct = _service_results(g, [_q(g, hub, peer, 1_000)] * 2)
    assert [h.scid for h in direct[0][1]] == [int(g.scids[c])]
    assert retraces() == r0 + 1         # seen now: silent


@pytest.mark.parametrize("steps", [5, 6])
def test_block_passes_gauge_after_warmup_and_flush(tmp_path, steps):
    """`clntpu_route_segmin_block_passes` reads max(steps - 5, 0) after
    a warm-up at the key and after a flush of a graph whose hub sets
    `steps` (5 in the uniform cell, 9 and 5 in the hub cells), and the
    flush's `route` flight record carries it beside `steps`."""
    from lightning_tpu.obs import flight

    RD.warmup(Q, 64, 256, steps=11 - steps)         # the other value
    assert _metric("clntpu_route_segmin_block_passes") == \
        RD.segmin_block_passes(11 - steps)
    RD.warmup(Q, 64, 256, steps=steps)
    assert _metric("clntpu_route_segmin_block_passes") == steps - 5
    g, hub, parts = _hub_net(tmp_path, {5: 32, 6: 33}[steps])
    RD.warmup(Q, 64, 256, steps=11 - steps)
    flight.reset_for_tests()
    queries = _hub_queries(g, hub, parts, "payee",
                           np.random.default_rng(steps))
    _assert_parity(g, queries, _service_results(g, queries))
    assert _metric("clntpu_route_segmin_steps") == steps
    assert _metric("clntpu_route_segmin_block_passes") == steps - 5
    rec = flight.recent("route")[-1]
    assert (rec["steps"], rec["block_passes"]) == (steps, steps - 5)
