"""The hub-graph deployment (PR 28): `mainnet-tenth-hubs` is
`mainnet-tenth` with the endpoint law changed and nothing else, its
cell the getroute cell with the pair law changed and nothing else; the
two readers the PR brings, by hand and through a traced rehearsal on
the CPU backend."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT, rehearse
from gen import store as gen_store
from reference import graph as ref_graph
from test_stage_metrics import FakeRun, reader

BASE, HUBS = "mainnet-tenth", "mainnet-tenth-hubs"
CELL, BASE_CELL = HUBS + ".getroute", BASE + ".getroute"


def _json(*parts):
    with open(os.path.join(*parts), encoding="utf8") as f:
        return json.load(f)


def _differing(a: dict, b: dict) -> set:
    return {k for k in set(a) | set(b) if a.get(k) != b.get(k)}


def test_configuration_is_the_accepted_one_but_for_the_endpoint_law():
    base = _json(BENCH, "configs", BASE + ".json")
    hubs = _json(BENCH, "configs", HUBS + ".json")
    assert _differing(base, hubs) == {"name", "source", "deployment",
                                      "graph", "assumed"}
    assert _differing(base["graph"], hubs["graph"]) == {
        "endpoints", "degree_exponent", "hub_share_max"}
    assert hubs["graph"]["endpoints"] == "powerlaw"
    # every number of the law is listed as assumed, with the law itself
    assert _differing(base["assumed"], hubs["assumed"]) == {
        "endpoints", "degree_exponent", "hub_share_max"}
    assert hubs["reduced"] == ["scale"] and len(hubs["source"]) <= 200


@pytest.mark.parametrize("seed", [3, 2_800_000_011])
def test_generator_makes_the_counts_the_file_states(tmp_path, seed):
    """The store at its full size (unsigned: host work, seconds): the
    counts and bytes the configuration's file gives, the same largest
    hub on every seed, and so 10 doubling steps by the route program's
    rule (2^steps >= the largest out-degree)."""
    cfg = _json(BENCH, "configs", HUBS + ".json")
    store = str(tmp_path / "gs")
    truth = gen_store.make_store(store, graph=cfg["graph"], seed=seed,
                                 sign=False)
    assert truth["records"] == cfg["records"]
    assert os.path.getsize(store) == cfg["store_bytes"]
    assert 4 * truth["channels"] + truth["channel_updates"] \
        + truth["node_announcements"] == cfg["signatures"]
    g = ref_graph.from_store(store)
    degree = np.bincount(g.node1, minlength=g.n_nodes) \
        + np.bincount(g.node2, minlength=g.n_nodes)
    assert degree.max() == 916 and int(degree.max() - 1).bit_length() == 10
    assert len(ref_graph.largest_component(g)) > 0.99 * g.n_nodes


def test_cell_is_the_getroute_cell_but_for_the_pair_law():
    base = _json(BENCH, "workloads", BASE_CELL + ".json")
    hubs = _json(BENCH, "workloads", CELL + ".json")
    assert _differing(base, hubs) == {"why", "params", "assumed"}
    assert _differing(base["params"], hubs["params"]) == {"pairs",
                                                          "pairs_params"}
    assert hubs["params"]["pairs"] == "zipf"
    assert hubs["params"]["pairs_params"] == {"exponent": 1.0}
    assert "pairs_params.exponent" in hubs["assumed"]


def test_the_cell_reports_what_the_getroute_cell_reports():
    bench = _json(ROOT, "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        assert (BASE_CELL in cells) == (CELL in cells), m["name"]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("segmin_steps.route", "path_hops.route"):
        assert per_layer[name]["workloads"] == [BASE_CELL, CELL]
        assert per_layer[name]["source"] == "program_counter"
    assert per_layer["segmin_steps.route"]["moves"] == "route_answers_per_s"
    assert per_layer["path_hops.route"]["moves"] == "route_p95_ms"


def _steps(value):
    return {"clntpu_route_segmin_steps": {"kind": "gauge", "samples": [
        {"labels": {}, "value": value}]}}


def _hops(counts: dict) -> dict:
    """The histogram's snapshot after `counts[hops]` routes of each
    length (bounds 1..20, cumulative)."""
    cum, buckets = 0, []
    for bound in range(1, 21):
        cum += counts.get(bound, 0)
        buckets.append([float(bound), cum])
    return {"clntpu_route_path_hops": {"kind": "histogram", "samples": [
        {"labels": {}, "buckets": buckets, "count": cum,
         "sum": float(sum(h * n for h, n in counts.items()))}]}}


def test_segmin_steps_is_the_gauge_at_the_close():
    read = reader("segmin_steps.route").read
    assert read(FakeRun(_steps(5), _steps(10))) == 10
    # a parent without the gauge, a program that ran no route flush
    assert read(FakeRun({}, {})) is None
    assert read(FakeRun(_steps(0), _steps(0))) is None


def test_path_hops_is_the_windows_mean():
    run = FakeRun(_hops({2: 10, 3: 5}), _hops({2: 30, 3: 25, 7: 5}))
    # in the window: 20 routes of 2 hops, 20 of 3, 5 of 7
    assert reader("path_hops.route").read(run) == pytest.approx(135 / 45)
    assert run.notes == {"route_paths": 45,
                         "route_paths_by_hops": {2: 20, 3: 20, 7: 5}}
    assert reader("path_hops.route").read(FakeRun({}, {})) is None
    same = _hops({4: 9})
    assert reader("path_hops.route").read(FakeRun(same, same)) is None


def test_traced_rehearsal_reads_both_from_the_program(tree):
    """The cell at its tiny size with the device path on the CPU
    backend: the gauge reads the tiny hub graph's steps (a largest
    degree of 16 of 400 channels: 4), the histogram routes of a few
    hops."""
    path = os.path.join(tree, "benchmarks", "workloads", CELL + ".json")
    w = _json(path)
    w["argv"] = [a for a in w["argv"] if a != "--cpu"]
    w["env"] = {"LIGHTNING_TPU_MCF_DEVICE": "0",
                "LIGHTNING_TPU_ROUTE_BATCH": "8"}
    w["params"].update(callers=12, ready_programs=1)
    with open(path, "w", encoding="utf8") as f:
        json.dump(w, f)
    out = rehearse(tree, CELL, seconds=3, trace=1)
    m = out["metrics"]
    assert m["segmin_steps.route"] == {"value": 4, "unit": "steps"}
    assert 1 <= m["path_hops.route"]["value"] <= 20
    assert m["device_path.route"]["value"] > 0
    assert '"endpoints": "powerlaw", "pairs": "zipf"' in out["_stdout"]
