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

import controls     # noqa: E402


def build_tree(t, add=None) -> str:
    """A checkout in miniature at `t`: the benchmark's files copied, the
    program linked, `add(t)` left to add files and entries of its own,
    then every configuration and cell cut to its driver's tiny size."""
    t.mkdir()
    shutil.copytree(BENCH, t / "benchmarks", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", ".pytest_cache"))
    os.symlink(os.path.join(ROOT, "lightning_tpu"), t / "lightning_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), t / "BENCHMARK.json")
    if add is not None:
        add(t)
    with open(t / "BENCHMARK.json", encoding="utf8") as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        wpath = t / "benchmarks" / "workloads" / (cell["name"] + ".json")
        w = json.loads(wpath.read_text())
        # the tiny size travels with the traffic shape
        tiny = controls.driver_module(w["driver"],
                                      str(t / "benchmarks")).TINY
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


def cells_on(driver: str, root: str = ROOT) -> list[str]:
    """The cells of a checkout that run on a traffic shape."""
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    out = []
    for w in bench["workloads"]:
        with open(os.path.join(root, "benchmarks", "workloads",
                               w["name"] + ".json"), encoding="utf8") as f:
            if json.load(f)["driver"] == driver:
                out.append(w["name"])
    return out


@pytest.fixture
def tree(tmp_path):
    return build_tree(tmp_path / "t")


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
    out["_stderr"], out["_stdout"] = proc.stderr, proc.stdout
    return out
