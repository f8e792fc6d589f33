"""The stage readers (PR 25): each on a `Delta` made by hand, value
counted by hand and None on an empty one; then through a traced
rehearsal of each cell on the CPU, where the new metrics have to be
reported, not zero, beside the old ones.

The getroute rehearsal of conftest.py runs the daemon with `--cpu`
(host solvers), where no flush reaches the device stage; this file
takes `--cpu` off in its own tree, so that the route program runs on
the CPU backend and `route/pack`, `route/device`, `route/reconstruct`
are there to read.
"""
import importlib.util
import json
import os

import pytest

from conftest import BENCH, ROOT, rehearse
from lib import counters

SPANS = "clntpu_span_duration_seconds"
NEW = {
    "mainnet-tenth.getroute": ("queue_wait_ms.route", "flush_host_pct.route",
                               "pack_ms.route", "reconstruct_ms.route"),
    "mainnet-tenth.crashboot": ("host_only_pct.replay",),
}


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_"),
        os.path.join(BENCH, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeRun:
    def __init__(self, before: dict, after: dict):
        self.delta = counters.Delta(before, after)
        self.notes: dict = {}

    def note(self, **row):
        self.notes.update(row)


def spans(**by_name) -> dict:
    """{span name: (seconds, count)} as the collector's histogram."""
    return {SPANS: {"samples": [
        {"labels": {"name": n.replace("__", "/")}, "sum": s, "count": c}
        for n, (s, c) in by_name.items()]}}


def test_every_new_reader_reads_none_on_an_empty_delta():
    for names in NEW.values():
        for name in names:
            assert reader(name).read(FakeRun({}, {})) is None, name
            # a program without the spans, other counters moving
            other = {"clntpu_route_flush_seconds": {"samples": [
                {"labels": {}, "sum": 3.0, "count": 3}]}}
            assert reader(name).read(FakeRun({}, other)) is None, name


def test_queue_wait_is_the_histograms_mean():
    def snap(s, c):
        return {"clntpu_route_queue_wait_seconds": {"samples": [
            {"labels": {}, "sum": s, "count": c}]}}
    run = FakeRun(snap(10.0, 20), snap(40.0, 80))
    # 30 s over 60 queries
    assert reader("queue_wait_ms.route").read(run) == pytest.approx(500.0)
    assert run.notes["route_queue_waits"] == 60


def test_route_flush_stage_readers_by_hand():
    before = spans(route__flush=(1.0, 1), route__device=(0.9, 1),
                   route__dispatch=(0.95, 1), route__pack=(0.02, 1),
                   route__reconstruct=(0.01, 1))
    after = spans(route__flush=(11.0, 11), route__device=(9.5, 11),
                  route__dispatch=(10.6, 11), route__pack=(0.52, 11),
                  route__reconstruct=(0.31, 11),
                  route__host_solve=(0.004, 2))
    run = FakeRun(before, after)
    # 10 flushes of 1.0 s, 8.6 s of them in the device stage
    assert reader("flush_host_pct.route").read(run) == pytest.approx(14.0)
    assert run.notes["route_flush_ms"] == pytest.approx(1000.0)
    assert run.notes["route_device_ms"] == pytest.approx(860.0)
    assert run.notes["route_host_solves"] == 2
    # 0.5 s of packing and 0.3 s of reconstruction over 10 dispatches
    assert reader("pack_ms.route").read(run) == pytest.approx(50.0)
    assert reader("reconstruct_ms.route").read(run) == pytest.approx(30.0)
    # host-only flushes and no device stage: nothing to take a share of
    host_only = FakeRun({}, spans(route__flush=(0.5, 10)))
    assert reader("flush_host_pct.route").read(host_only) is None
    assert reader("pack_ms.route").read(host_only) is None


def test_host_only_share_of_a_pass_by_hand():
    after = spans(recovery__boot=(62.0, 2), replay__stream=(59.6, 2),
                  recovery__store=(0.6, 2), gossip__extract=(0.8, 2),
                  replay__sort=(0.1, 2), replay__readback=(0.7, 2),
                  verify__dispatch=(50.0, 4876))
    run = FakeRun({}, after)
    # 2.4 s of 62 s outside the stream
    assert reader("host_only_pct.replay").read(run) \
        == pytest.approx(100 * 2.4 / 62.0)
    n = run.notes
    assert n["replay_passes"] == 2
    assert n["replay_host_only_ms"] == pytest.approx(1200.0)
    assert n["replay_stage_ms"] == pytest.approx(
        {"recovery/store": 300.0, "gossip/extract": 400.0,
         "replay/sort": 50.0, "replay/readback": 350.0})
    assert n["replay_unattributed_ms"] == pytest.approx(100.0)
    # a boot that replayed nothing has no stream to set against it
    assert reader("host_only_pct.replay").read(
        FakeRun({}, spans(recovery__boot=(1.0, 1)))) is None


def test_entries_name_their_readers_and_layers():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert per_layer[name]["workloads"] == [cell]
            assert per_layer[name]["source"] in ("program_span",
                                                 "program_counter")
    assert per_layer["queue_wait_ms.route"]["moves"] == "route_p95_ms"
    assert per_layer["host_only_pct.replay"]["layer"] == "replay pipeline"


def test_crashboot_traced_rehearsal_reports_the_stage_share(tree):
    out = rehearse(tree, "mainnet-tenth.crashboot", seconds=3, trace=1)
    assert out["correct"] is True
    m = out["metrics"]
    assert 0 < m["host_only_pct.replay"]["value"] < 100
    # the old ones are still there
    assert m["lane_fill.replay"]["value"] > 0
    assert "prep_stall.replay" in m


def test_getroute_traced_rehearsal_reports_the_flush_stages(tree):
    cell = "mainnet-tenth.getroute"
    path = os.path.join(tree, "benchmarks", "workloads", cell + ".json")
    with open(path, encoding="utf8") as f:
        w = json.load(f)
    # the device path on the CPU backend, at a small bucket
    w["argv"] = [a for a in w["argv"] if a != "--cpu"]
    w["env"] = {"LIGHTNING_TPU_MCF_DEVICE": "0",
                "LIGHTNING_TPU_ROUTE_BATCH": "8"}
    w["params"].update(callers=12, ready_programs=1)
    with open(path, "w", encoding="utf8") as f:
        json.dump(w, f)
    out = rehearse(tree, cell, seconds=3, trace=1)
    m = out["metrics"]
    for name in NEW[cell]:
        assert m[name]["value"] > 0, name
    assert m["flush_host_pct.route"]["value"] < 100
    # the old ones are still there
    for name in ("batch_fill.route", "device_path.route",
                 "rpc_overhead_ms.route"):
        assert name in m, name
    assert m["device_path.route"]["value"] > 0
