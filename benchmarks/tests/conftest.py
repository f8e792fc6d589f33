"""Fixtures for the benchmark's own tests (run with
`python -m pytest benchmarks/tests`, on the CPU; they are not part of
the repository's tier-1 suite)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

TINY = {
    "crashboot": {
        "graph": {"channels": 96, "nodes": 24},
        "params": {"bad_records": 24, "sample_records": 9,
                   "open_after_s": 0.2, "trace_after_s": 0.2,
                   "trace_seconds": 0.5},
        "env": {"LIGHTNING_TPU_VERIFY_BUCKET": "8"}, "argv": [],
    },
    "rpc_closed_loop": {
        "graph": {"channels": 400, "nodes": 100},
        "params": {"callers": 8, "think_mean_s": 0.05,
                   "start_spread_s": 0.2, "queries": 300,
                   "ramp_seconds": 0.5, "ready_programs": 0, "sample": 25,
                   "trace_seconds": 0.5},
        # host solvers: the CPU rehearsal proves the flow and the
        # comparison, not the device path
        "env": {}, "argv": ["--cpu", "--gossip-store", "gossip_store",
                            "--rpc-file", "lightning-rpc"],
    },
}


@pytest.fixture
def tree(tmp_path):
    """A checkout in miniature: the benchmark's files copied, the
    program linked, every configuration and cell cut to a tiny size."""
    t = tmp_path / "t"
    t.mkdir()
    shutil.copytree(BENCH, t / "benchmarks", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "lightning_tpu"), t / "lightning_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), t / "BENCHMARK.json")
    with open(t / "BENCHMARK.json", encoding="utf8") as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        wpath = t / "benchmarks" / "workloads" / (cell["name"] + ".json")
        w = json.loads(wpath.read_text())
        tiny = TINY[w["driver"]]
        w["params"].update(tiny["params"])
        w["env"], w["argv"] = tiny["env"], tiny["argv"]
        wpath.write_text(json.dumps(w))
        cfg = next(c for c in bench["configs"]
                   if c["name"] == cell["config"])
        cpath = t / cfg["file"]
        c = json.loads(cpath.read_text())
        c["graph"].update(tiny["graph"])
        cpath.write_text(json.dumps(c))
    return str(t)


def rehearse(tree: str, cell: str, *, trace: int = 0, seconds: float = 3,
             seed: int = 2_900_000_011, fault: str | None = None,
             timeout: float = 900) -> dict:
    """Run one cell through tests/rehearse.py; the result line."""
    cmd = [sys.executable, os.path.join(tree, "benchmarks", "tests",
                                        "rehearse.py"), tree]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--", "--workload", cell, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=tree)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    out = json.loads(last)
    out["_stderr"] = proc.stderr
    return out
