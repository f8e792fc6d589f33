"""BENCHMARK.json against the contract's form, and against the files
the harness will look for under benchmarks/."""
import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as _f:
    B = json.load(_f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert B["paths"] == ["benchmarks"]
    assert all(_line(w) for w in B["command"]) and len(B["command"]) <= 32
    assert 1 <= len(B["configs"]) <= 24 and 1 <= len(B["workloads"]) <= 24
    assert 1 <= len(B["end_to_end"]) <= 16 and 1 <= len(B["per_layer"]) <= 128


def test_configs():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"]), encoding="utf8") as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["graph"]["channels"] > 0 and cfg["guarantees"]
        assert any(w["config"] == c["name"] for w in B["workloads"])
    assert len({c["name"] for c in B["configs"]}) == len(B["configs"])
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])


def test_cells_have_their_files():
    configs = {c["name"] for c in B["configs"]}
    seen = set()
    four = 0
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
        path = os.path.join(BENCH, "workloads", w["name"] + ".json")
        with open(path, encoding="utf8") as f:
            wl = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           wl["driver"] + ".py"))
        # every end-to-end metric of the cell names a driver quantity
        for m in B["end_to_end"]:
            if m["name"] != "setup_s" and \
                    w["name"] in m.get("workloads", [w["name"]]):
                assert m["name"] in wl["end_to_end"]
    assert four <= max(1, len(B["workloads"]) // 2)


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = set()
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
        names.add(m["name"])
    layers = set()
    for m in B["per_layer"]:
        # run.py reads a per-layer metric only in the cells it lists
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e and m["name"] not in names
        names.add(m["name"])
        layers.add(m["layer"])
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           m["name"] + ".py"))
        # listed in cells that report the metric it moves
        moved = e2e[m["moves"]].get("workloads", sorted(cells))
        assert m["workloads"] and set(m["workloads"]) <= set(moved)
    # every cell: setup_s, another end-to-end metric, a per-layer metric
    for c in cells:
        assert any(c in m.get("workloads", [c]) for m in B["end_to_end"]
                   if m["name"] != "setup_s")
        assert any(c in m.get("workloads", []) for m in B["per_layer"])
    # layer names are PERF.md's
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf8") as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, layer


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs
                   if x not in (".cache", "__pycache__", ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
