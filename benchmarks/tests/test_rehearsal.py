"""Each driver at a tiny size on the CPU, through run.py's own `main`
with only the look for a chip replaced (tests/rehearse.py), and the
same with the timed path broken underneath: `correct` has to read
false for every fault the cell can have.

The crash-boot cells run the program's fused verify program on the
CPU at bucket 8: its first compile here takes minutes (it is cached in
$JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache afterwards).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, cells_on as _cells, rehearse

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as _f:
    _BENCH = json.load(_f)


@pytest.mark.parametrize("cell", _cells("crashboot"))
def test_crashboot_runs_and_is_correct(tree, cell):
    out = rehearse(tree, cell, seconds=3)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert [k for k in out if not k.startswith("_")][-1] == "compared"
    assert out["metrics"]["setup_s"]["value"] > 0
    rate = [m for n, m in out["metrics"].items() if n != "setup_s"]
    assert rate and all(m["value"] > 0 for m in rate)
    for name in out["compared"]:
        assert f"compared {name}:" in out["_stderr"]


@pytest.mark.parametrize("cell", _cells("crashboot"))
def test_crashboot_traced_run_reports_layers(tree, cell):
    out = rehearse(tree, cell, seconds=3, trace=1)
    assert out["correct"] is True
    # the counters' readers find something on any backend; the trace's
    # readers find no TPU plane here and return nothing, never 0
    assert "lane_fill.replay" in out["metrics"]
    assert "verify_roofline" not in out["metrics"]
    assert all(m["value"] != 0 for m in out["metrics"].values())


@pytest.mark.parametrize("fault,caught_by", [
    # one bit flipped outside the sample: the count of invalid records
    # no longer equals what was planted
    ("answer_altered_crashboot", "invalid_gap"),
    # planted records in the unchecked half read valid
    ("half_left_out_crashboot", "bits_mismatch"),
    # a channel_announcement's signature at one position taken on trust:
    # the record planted with a flip there reads valid
    ("ca_sig_0_skipped_crashboot", "bits_mismatch"),
    ("ca_sig_1_skipped_crashboot", "bits_mismatch"),
    ("ca_sig_2_skipped_crashboot", "bits_mismatch"),
    ("ca_sig_3_skipped_crashboot", "bits_mismatch")])
@pytest.mark.parametrize("cell", _cells("crashboot"))
def test_crashboot_faults_read_not_correct(tree, cell, fault, caught_by):
    out = rehearse(tree, cell, seconds=2, fault=fault)
    assert out["correct"] is False
    assert out["compared"][caught_by]["value"] > 0


@pytest.mark.parametrize("cell", _cells("rpc_closed_loop"))
def test_rpc_answers_match_the_reference(tree, cell):
    out = rehearse(tree, cell, seconds=3)
    c = out["compared"]
    assert c["wrong_answers"]["value"] == 0
    assert c["not_answers"]["value"] == 0
    assert c["never_answered"]["value"] == 0
    assert out["attempted"] > 10
    # the rehearsal's daemon runs --cpu: every answer came from the
    # host solvers, and a run must say so and read not correct
    assert c["host_fallback_pct"]["value"] > c["host_fallback_pct"]["limit"]
    assert out["correct"] is False


def test_rpc_altered_answer_reads_wrong(tree):
    cell = next(c for c in _cells("rpc_closed_loop")
                if c.endswith("getroute"))
    out = rehearse(tree, cell, seconds=2, fault="answer_altered_rpc")
    assert out["compared"]["wrong_answers"]["value"] > 0
    assert out["correct"] is False and out["failed"] > 0


def test_no_accelerator_no_result(tree):
    cell = _BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=tree,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""


def test_no_program_no_result(tree):
    os.unlink(os.path.join(tree, "lightning_tpu"))
    cell = _BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, timeout=120, cwd=tree,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
