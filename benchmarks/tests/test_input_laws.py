"""The inputs' laws: the default laws make the parent's bytes, the two
laws the hub-graph deployment needs keep what they promise, and a
traffic shape that tests/ has never heard of passes the fixtures.

The golden digests were taken from commit 30c0a9f (PR 26), before
gen/ knew a law by name, with that commit's `make_store` and `pairs`.
"""
import hashlib
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import controls
from conftest import build_tree, cells_on, rehearse
from gen import pairs_zipf
from gen import queries as gen_queries
from gen import store as gen_store
from reference import graph as ref_graph

AMOUNTS = {"amount_min_msat": 10**6, "amount_max_msat": 10**9}
GOLDEN = {
    3: {"signed_bad24": "d59f37113eb7fa15df027dfcd2a314ef"
                        "bab8006b7d89a1372b6b92ae738f40f0",
        "signed_bad0": "c8643e920d4a94d9dd5a8f57475fad3e"
                       "74f24fe6f7a6994863c6ff002bf50786",
        "plain": "01f8500c03a73233c9442b900098cf68"
                 "195fbe7d408ae7572a6c75989bc33631",
        "pairs": "f2160dce3fb4fe104c41e1489a838cf9"
                 "ebbcf867b8bb3cd09997c4ebe45ec474"},
    2_147_483_659: {
        "signed_bad24": "db964c683d1951dfd7b36e1cfb37cc6a"
                        "e53b19a694e54422befa41d3ab76c312",
        "signed_bad0": "170f0688e80954644bda72fed9eea687"
                       "2f9857ef7084c0586a67f7a990e2e5b8",
        "plain": "ef1e28c95dcf66e738f072daedb628c3"
                 "7a2102f3d53ef90ad30cf08096cccd6f",
        "pairs": "3c03bed0e97f8dc2c85c9fe1591e6913"
                 "65a8e635538498d7aba4fe8799baa636"},
}
# the sizes the drivers' TINY use
STORES = {"signed_bad24": (96, 24, {"sign": True, "bad_records": 24}),
          "signed_bad0": (96, 24, {"sign": True, "bad_records": 0}),
          "plain": (400, 100, {"sign": False})}
HUBS = {"endpoints": "powerlaw", "degree_exponent": 2.1,
        "hub_share_max": 0.04}
SIZES = {"cell": {"channels": 25000, "nodes": 6000},
         "tiny": {"channels": 400, "nodes": 100}}


def _sha(path) -> str:
    with open(path, "rb") as f:
        return hashlib.file_digest(f, "sha256").hexdigest()


# -- golden inputs -----------------------------------------------------


@pytest.mark.parametrize("named", [False, True],
                         ids=["default", "uniform_named"])
@pytest.mark.parametrize("kind", sorted(STORES))
@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_store_is_the_parents_bytes(tmp_path, seed, kind, named):
    channels, nodes, how = STORES[kind]
    path = str(tmp_path / "gs")
    if named:       # as a configuration's `graph` that names the law
        gen_store.make_store(path, seed=seed, graph={
            "channels": channels, "nodes": nodes, "endpoints": "uniform",
            "channel_updates_per_channel": 2}, **how)
    else:           # the two sizes alone, as the parent's callers gave
        gen_store.make_store(path, seed=seed, graph={
            "channels": channels, "nodes": nodes}, **how)
    assert _sha(path) == GOLDEN[seed][kind]
    assert gen_store.sha256_16(path) == GOLDEN[seed][kind][:16]


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_pairs_are_the_parents_draws(tmp_path, seed):
    path = str(tmp_path / "gs")
    gen_store.make_store(path, graph=SIZES["tiny"], seed=seed, sign=False)
    g = ref_graph.from_store(path)
    nodes = ref_graph.largest_component(g)
    want = GOLDEN[seed]["pairs"]
    qs = gen_queries.pairs(nodes, 300, seed, **AMOUNTS)
    assert hashlib.sha256(json.dumps(qs).encode()).hexdigest() == want
    for params in ({}, {"pairs": "uniform"},
                   {"pairs": "uniform", "pairs_params": {}}):
        qs = gen_queries.cell_pairs(
            g, nodes, dict(params, queries=300, **AMOUNTS), seed)
        assert hashlib.sha256(json.dumps(qs).encode()).hexdigest() == want


def test_an_unknown_law_is_an_error():
    with pytest.raises(ModuleNotFoundError):
        gen_store.endpoints(np.random.default_rng(1), {
            "channels": 4, "nodes": 3, "endpoints": "no_such_law"})
    with pytest.raises(ModuleNotFoundError):
        gen_queries.cell_pairs(None, [0, 1], dict(
            queries=1, pairs="no_such_law", **AMOUNTS), 1)


# -- the powerlaw endpoint law ---------------------------------------------


@pytest.fixture(scope="module")
def hub_store(tmp_path_factory):
    """size -> (graph dict, reference graph, component) of one unsigned
    powerlaw store."""
    out = {}
    for size, shape in SIZES.items():
        graph = dict(shape, **HUBS)
        path = str(tmp_path_factory.mktemp("hubs") / "gs")
        truth = gen_store.make_store(path, graph=graph, seed=11, sign=False)
        assert truth["records"] == 3 * shape["channels"] + shape["nodes"]
        g = ref_graph.from_store(path)
        out[size] = graph, g, ref_graph.largest_component(g), path
    return out


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_powerlaw_degrees(size, seed):
    graph = dict(SIZES[size], **HUBS)
    channels, nodes = graph["channels"], graph["nodes"]
    a, b = gen_store.endpoints(np.random.default_rng(seed), graph)
    assert len(a) == len(b) == channels
    assert not (a == b).any()                       # no self-loop
    deg = np.bincount(a, minlength=nodes) + np.bincount(b, minlength=nodes)
    assert deg.sum() == 2 * channels and len(deg) == nodes
    assert deg.min() >= 1                           # every node has a channel
    k_max = graph["hub_share_max"] * channels
    assert 0.75 * k_max <= deg.max() <= k_max
    # one seed, one graph; another seed, another
    a2, b2 = gen_store.endpoints(np.random.default_rng(seed), graph)
    assert (a == a2).all() and (b == b2).all()
    a3, _ = gen_store.endpoints(np.random.default_rng(seed + 1), graph)
    assert (a != a3).any()
    if size == "cell":
        # the route program's doubling steps: 5 on uniform endpoints
        assert math.ceil(math.log2(deg.max())) >= 9
        # complementary degree distribution, log-log, k = 4 .. 256 (the
        # tiny size's law ends at k = 16: no such range to fit there)
        ks = 2 ** np.arange(2, 9)
        ccdf = np.array([(deg >= k).mean() for k in ks])
        slope = np.polyfit(np.log(ks), np.log(ccdf), 1)[0]
        assert abs(slope - (1 - graph["degree_exponent"])) <= 0.3


@pytest.mark.parametrize("size", sorted(SIZES))
def test_powerlaw_store_reads_as_a_graph(hub_store, size):
    graph, g, comp, _ = hub_store[size]
    assert g.n_nodes == graph["nodes"]
    assert len(g.scids) == graph["channels"]
    assert g.has_update.all()
    assert len(comp) >= 0.85 * graph["nodes"]
    deg = np.bincount(g.node1, minlength=g.n_nodes) \
        + np.bincount(g.node2, minlength=g.n_nodes)
    assert deg.max() >= 0.75 * graph["hub_share_max"] * graph["channels"]


def test_powerlaw_moves_endpoints_and_nothing_else(tmp_path):
    """Record shapes, counts and the laws of the fee and htlc_maximum
    draws are the uniform law's (their values come later in the one
    stream, so they differ): `signed_bytes_per_signature` and the
    crash-boot truth stay valid."""
    from reference import storefile

    shape = SIZES["tiny"]
    pu, ph = str(tmp_path / "u"), str(tmp_path / "h")
    tu = gen_store.make_store(pu, graph=dict(shape), seed=5, sign=False)
    th = gen_store.make_store(ph, graph=dict(shape, **HUBS), seed=5,
                              sign=False)
    assert tu == th and os.path.getsize(pu) == os.path.getsize(ph)
    mu, mh = storefile.read_alive(pu), storefile.read_alive(ph)
    for kind in ("ca", "cu", "na"):
        assert [len(m) for m in mu[kind]] == [len(m) for m in mh[kind]]
    assert mu["na"] == mh["na"]
    # a channel_update but for its three drawn fields
    assert [m[66:122] for m in mu["cu"]] == [m[66:122] for m in mh["cu"]]
    for m in mh["cu"]:
        assert int.from_bytes(m[122:126], "big") < 5000
        assert int.from_bytes(m[126:130], "big") < 10000
        assert 1 <= int.from_bytes(m[130:138], "big") < 1 << 40
    # a channel_announcement up to its keys
    assert [m[:300] for m in mu["ca"]] == [m[:300] for m in mh["ca"]]
    assert mu["ca"] != mh["ca"]


def test_powerlaw_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError):
        gen_store.endpoints(np.random.default_rng(1), dict(
            HUBS, channels=4000, nodes=100, hub_share_max=0.001))


# -- the zipf pair law ---------------------------------------------------


@pytest.mark.parametrize("size,exponent", [("cell", 1.0), ("tiny", 1.0),
                                           ("cell", 0.5)])
def test_zipf_pairs(hub_store, size, exponent):
    _, g, comp, _ = hub_store[size]
    params = dict(queries=20000, pairs="zipf",
                  pairs_params={"exponent": exponent}, **AMOUNTS)
    qs = gen_queries.cell_pairs(g, comp, params, 2**31 + 17)
    assert len(qs) == 20000
    members = set(comp)
    assert all(a != b and a in members and b in members for a, b, _ in qs)
    assert all(10**6 <= amt <= 10**9 for _, _, amt in qs)
    # the top 1 % of ranks draw the share of payees the law says
    rank = pairs_zipf.ranked(g, comp)
    top = set(rank[:max(1, len(comp) // 100)])
    w = pairs_zipf.rank_weights(len(comp), exponent)
    law = sum(w[:len(top)]) / sum(w)
    got = sum(b in top for _, b, _ in qs) / len(qs)
    assert abs(got - law) <= 0.02, (got, law)
    # ranked by degree, ties by index
    deg = np.bincount(g.node1, minlength=g.n_nodes) \
        + np.bincount(g.node2, minlength=g.n_nodes)
    keys = [(-int(deg[v]), v) for v in rank]
    assert keys == sorted(keys) and sorted(rank) == sorted(comp)
    # payers are spread over the component, not over the hubs
    assert sum(a in top for a, _, _ in qs) / len(qs) <= 0.03
    # one seed twice gives one list, two seeds differ
    assert qs == gen_queries.cell_pairs(g, comp, params, 2**31 + 17)
    assert qs != gen_queries.cell_pairs(g, comp, params, 2**31 + 18)


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 40_000_000_001])
def test_rpc_control_fails_on_a_hub_graph(tmp_path, seed):
    """Compounding left out is caught on powerlaw endpoints under zipf
    pairs too."""
    store = str(tmp_path / "gs")
    gen_store.make_store(store, graph=dict(SIZES["tiny"], **HUBS),
                         seed=seed, sign=False)
    params = dict(queries=200, pairs="zipf",
                  pairs_params={"exponent": 1.0}, **AMOUNTS)
    looked_at, compared = controls.driver_module(
        "rpc_closed_loop").control_on("getroute", store, seed, params, 40)
    assert looked_at == 40 and compared[0][1] > 0
    assert any(abs(v) > lim for _, v, lim in compared)


# -- a traffic shape of a later PR -----------------------------------------


THIRD = '''"""A traffic shape tests/ has never heard of."""
from drivers.rpc_closed_loop import (check, control, setup,  # noqa: F401
                                     teardown, window)

TINY = {"graph": {"channels": 300, "nodes": 80},
        "params": {"callers": 4, "think_mean_s": 0.05,
                   "start_spread_s": 0.2, "queries": 200,
                   "ramp_seconds": 0.5, "ready_programs": 0, "sample": 20,
                   "trace_seconds": 0.5, "pairs": "zipf",
                   "pairs_params": {"exponent": 1.0}},
        "env": {}, "argv": ["--cpu", "--gossip-store", "gossip_store",
                            "--rpc-file", "lightning-rpc"]}
'''


def _add_third_driver(t) -> None:
    """What a later PR adds: a driver file, a configuration, a cell on
    both, and their entries; nothing that is there is edited but
    BENCHMARK.json."""
    bench_dir = t / "benchmarks"
    (bench_dir / "drivers" / "third_shape.py").write_text(THIRD)
    like = json.loads(
        (bench_dir / "workloads" / "mainnet-tenth.getroute.json").read_text())
    like["driver"] = "third_shape"
    (bench_dir / "workloads" / "hubs.third.json").write_text(
        json.dumps(like))
    cfg = json.loads(
        (bench_dir / "configs" / "mainnet-tenth.json").read_text())
    cfg["name"] = "hubs"
    cfg["graph"].update(HUBS)
    (bench_dir / "configs" / "hubs.json").write_text(json.dumps(cfg))
    bench = json.loads((t / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="hubs",
                                 file="benchmarks/configs/hubs.json"))
    bench["workloads"].append({
        "name": "hubs.third", "config": "hubs", "traffic": "third",
        "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mainnet-tenth.getroute" in m.get("workloads", []):
            m["workloads"].append("hubs.third")
    (t / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_third_driver_passes_the_fixtures(tmp_path):
    tree = build_tree(tmp_path / "t", add=_add_third_driver)
    # the fixture cut the new cell to the size its own driver brings,
    # and left the configuration's law alone
    with open(os.path.join(tree, "benchmarks", "workloads",
                           "hubs.third.json"), encoding="utf8") as f:
        w = json.load(f)
    assert w["params"]["callers"] == 4 and w["params"]["pairs"] == "zipf"
    with open(os.path.join(tree, "benchmarks", "configs", "hubs.json"),
              encoding="utf8") as f:
        c = json.load(f)
    assert c["graph"]["channels"] == 300
    assert c["graph"]["endpoints"] == "powerlaw"
    assert cells_on("third_shape", tree) == ["hubs.third"]
    assert "hubs.third" not in cells_on("rpc_closed_loop", tree)
    assert cells_on("crashboot", tree) == cells_on("crashboot")
    # the control finds the shape's own `control` and reads not correct
    # (exit code 0: every seed's control came out as not correct)
    proc = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "tests",
                                      "controls.py"),
         "--workload", "hubs.third", "--seeds", "5", str(2**31 + 9)],
        capture_output=True, text=True, timeout=300, cwd=tree)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert [r["correct"] for r in rows] == [False, False]
    assert all(r["compared"]["wrong_answers"]["value"] > 0 for r in rows)
    # and run.py drives the cell: laws by name, driver by name
    out = rehearse(tree, "hubs.third", seconds=2)
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert out["compared"]["never_answered"]["value"] == 0
    assert out["attempted"] > 5
    notes = [json.loads(ln) for ln in out["_stdout"].splitlines()[:-1]]
    inputs = next(n["inputs"] for n in notes if "inputs" in n)
    assert inputs["endpoints"] == "powerlaw" and inputs["pairs"] == "zipf"
    assert len(inputs["store_sha256"]) == len(inputs["queries_sha256"]) == 16
