"""Every control, at a size a test run can hold, comes out as not
correct; the same comparison passes the reference itself."""
import importlib
import os

import pytest

import controls
from gen import queries as gen_queries
from gen import store as gen_store
from reference import dijkstra as DJ
from reference import graph as ref_graph

SEEDS = (3, 2_147_483_659, 40_000_000_001)


@pytest.mark.parametrize("seed", SEEDS)
def test_crashboot_control_fails_through_check(tmp_path, seed):
    store = str(tmp_path / "gs")
    truth = gen_store.make_store(store, graph={"channels": 96, "nodes": 24},
                                 seed=seed, sign=True, bad_records=24)
    # a flipped signature on each of a channel_announcement's positions
    assert sorted(truth["bad_ca_sig"].values()) == [0, 1, 2, 3]
    looked_at, compared = controls.driver_module("crashboot").control_on(
        store, truth, seed, {"sample_records": 9})
    got = {name: v for name, v, _ in compared}
    # the control misses the flips in the second, third and fourth
    assert looked_at > 0 and got["bits_mismatch"] == 3
    assert got["invalid_gap"] == 3
    assert any(abs(v) > lim for _, v, lim in compared)


@pytest.mark.parametrize("seed", SEEDS)
def test_rpc_control_fails_and_reference_passes(tmp_path, seed):
    store = str(tmp_path / "gs")
    gen_store.make_store(store, graph={"channels": 400, "nodes": 100},
                         seed=seed, sign=False)
    params = {"queries": 200, "amount_min_msat": 10**6,
              "amount_max_msat": 10**9}
    looked_at, compared = controls.driver_module(
        "rpc_closed_loop").control_on("getroute", store, seed, params, 40)
    assert looked_at == 40 and compared[0][1] > 0
    # the reference's own answers pass the comparison they are put to
    answers = importlib.import_module("reference.answers_getroute")
    g = ref_graph.from_store(store)
    qs = gen_queries.pairs(ref_graph.largest_component(g), 20, seed,
                           amount_min_msat=params["amount_min_msat"],
                           amount_max_msat=params["amount_max_msat"])
    for q in qs:
        try:
            route, _ = DJ.getroute(g, *q)
        except DJ.NoRoute:
            continue
        reply = {"result": {"route": [
            {"id": g.node_ids[v].hex(), "channel": int(g.scids[c]),
             "direction": d, "amount_msat": a, "delay": dl}
            for v, c, d, a, dl in route]}}
        answers.check(g, q, reply)


def test_pacing_is_the_same_load_for_every_seed():
    a = gen_queries.pacing(10, 3, think_mean_s=2.0, think_spread=0.25,
                           start_spread_s=3.0)
    b = gen_queries.pacing(10, 2**31 + 5, think_mean_s=2.0,
                           think_spread=0.25, start_spread_s=3.0)
    assert a != b
    assert sorted(a[1]) == sorted(b[1]) and max(a[1]) < 3.0
    for row_a, row_b in zip(a[0], b[0]):
        assert sorted(row_a) == sorted(row_b)
        assert abs(sum(row_a) / len(row_a) - 2.0) < 1e-9
        assert min(row_a) > 1.5 and max(row_a) < 2.5


def test_generator_is_deterministic(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    for p in (a, b):
        gen_store.make_store(p, graph={"channels": 40, "nodes": 12},
                             seed=2**31 + 11, sign=True, bad_records=3)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        assert fa.read() == fb.read()
    assert os.path.getsize(a) == 1 + 40 * 444 + 80 * 150 + 12 * 154
