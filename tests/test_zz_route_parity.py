"""Host/device route parity: the vmapped Bellman-Ford solver
(routing/device.py) must price routes bit-identically to
dijkstra.getroute over randomized synth gossmaps — ragged degree,
disabled channels, excluded scids, htlc min/max edges, unreachable
destinations — and the RouteService front-end must coalesce, fall back
and meter as documented (doc/routing.md).

All graphs here pad to the SAME quantized planes shape (n_pad 64,
e_pad 256) and every batch uses Q=8, so the suite compiles the route
program exactly once (tests/conftest's read-only jax cache serves it
after the out-of-band warmup).
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


def test_route_program_carries_named_scopes():
    """The route program's phases ride the ops' metadata under stable
    names (a device trace shows them whatever number XLA gives the
    while loop); the module keeps the name the benchmark matches."""
    import jax.numpy as jnp
    from jax import enable_x64

    e_pad, n_pad = 256, 64
    with enable_x64():
        zeros = jnp.zeros((e_pad,), jnp.int64)
        lowered = RD._jit_route(n_pad, RD.DEFAULT_MAX_HOPS).lower(
            jnp.zeros((e_pad,), jnp.int32), jnp.zeros((e_pad,), jnp.int32),
            zeros, zeros, zeros, zeros, zeros,
            jnp.zeros((Q, e_pad), bool), jnp.zeros((Q,), jnp.int32),
            jnp.zeros((Q,), jnp.int32), jnp.ones((Q,), jnp.int64),
            jnp.zeros((Q,), jnp.int64), jnp.ones((Q,), jnp.int64))
    text = lowered.as_text(debug_info=True)
    assert "route_relax" in text and "route_extract" in text
    assert "module @jit_single" in text
