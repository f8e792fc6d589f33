"""bench.py emitted-record schema contract (`bench.py --selfcheck`):
the driver artifact must carry the real measurement platform/engine at
TOP level — never a `platform: cpu` headline with hardware numbers
buried in `last_measured_tpu` metadata.

Pure-host module (no jax): bench's top-level imports are stdlib only.
"""
from __future__ import annotations

import json

import bench


_HW = {"platform": "tpu", "e2e_date": "2026-08-01",
       "end_to_end_sig_verifies_per_sec": 45756.0,
       "impl": "pallas_fbj+pp", "bucket": 16384, "n_sigs": 153125}


def test_fallback_promotes_hardware_record():
    line = bench.compose_line(39.6, "cpu", engine="glv",
                              bucket=64, extra={"n_sigs": 1064},
                              last=_HW)
    assert line["value"] == 45756.0
    assert line["platform"] == "tpu"
    assert line["engine"] == "pallas_fbj+pp"
    assert line["bucket"] == 16384
    assert line["measurement"] == "replayed:bench_last_tpu.json"
    assert line["measured_at"] == "2026-08-01"
    assert line["vs_baseline"] == round(45756.0 / bench.BASELINE_CPU_OPS, 3)
    # the fallback run's own numbers still ride along, clearly scoped
    assert line["fallback_run"]["platform"] == "cpu"
    assert line["fallback_run"]["value"] == 39.6
    assert line["fallback_run"]["n_sigs"] == 1064
    assert bench.check_bench_line(line) == []


def test_live_accelerator_line_passes():
    line = bench.compose_line(91234.5, "tpu", engine="pallas_fbj+pp",
                              bucket=16384, last=_HW)
    assert line["measurement"] == "live"
    assert line["platform"] == "tpu"
    assert bench.check_bench_line(line) == []


def test_cpu_fallback_without_hardware_history_is_honest():
    line = bench.compose_line(39.6, "cpu", engine="glv",
                              bucket=64, last=None)
    assert line["platform"] == "cpu"
    assert line["measurement"] == "live"
    assert bench.check_bench_line(line) == []


def test_burial_regression_is_flagged():
    # the exact shape BENCH_r03..r05.json shipped with
    bad = {"metric": bench.METRIC, "value": 39.6, "unit": bench.UNIT,
           "vs_baseline": round(39.6 / bench.BASELINE_CPU_OPS, 3),
           "platform": "cpu", "measurement": "live",
           "engine": "glv", "bucket": 64, "last_measured_tpu": _HW}
    probs = bench.check_bench_line(bad)
    assert any("buried" in p for p in probs), probs


def test_missing_keys_and_inconsistent_baseline_flagged():
    probs = bench.check_bench_line({"metric": bench.METRIC})
    assert any("value" in p for p in probs)
    assert any("platform" in p for p in probs)
    line = bench.compose_line(1000.0, "tpu", engine="x", bucket=64,
                              last=None)
    line["vs_baseline"] = 99.0
    assert any("vs_baseline" in p
               for p in bench.check_bench_line(line))


def test_error_lines_exempt():
    assert bench.check_bench_line(
        {"metric": bench.METRIC, "value": 0.0, "unit": bench.UNIT,
         "vs_baseline": 0.0, "error": "watchdog: exceeded 2400s"}) == []


def test_route_line_passes():
    line = bench.compose_route_line(4000.0, "tpu", batch=64,
                                    n_channels=10_000, host_rps=850.0)
    assert line["metric"] == bench.ROUTE_METRIC
    assert line["unit"] == bench.ROUTE_UNIT
    assert line["platform"] == "tpu"
    assert line["measurement"] == "live"
    assert line["speedup_vs_host"] == round(4000.0 / 850.0, 3)
    assert bench.check_bench_line(line) == []


def test_route_line_cpu_fallback_labeled():
    line = bench.compose_route_line(120.0, "cpu", batch=64,
                                    n_channels=2_000, host_rps=300.0)
    assert line["platform"] == "cpu"
    assert bench.check_bench_line(line) == []


def test_route_line_missing_keys_and_bad_speedup_flagged():
    probs = bench.check_bench_line({"metric": bench.ROUTE_METRIC})
    assert any("value" in p for p in probs)
    assert any("host_baseline_rps" in p for p in probs)
    line = bench.compose_route_line(4000.0, "tpu", batch=64,
                                    n_channels=10_000, host_rps=850.0)
    line["speedup_vs_host"] = 99.0
    assert any("speedup_vs_host" in p
               for p in bench.check_bench_line(line))


def test_route_selfcheck_cli(tmp_path):
    good = bench.compose_route_line(500.0, "cpu", batch=64,
                                    n_channels=2_000, host_rps=250.0)
    bad = dict(good)
    del bad["host_baseline_rps"]
    pg, pb = tmp_path / "route_good.json", tmp_path / "route_bad.json"
    pg.write_text(json.dumps({"parsed": good}))   # driver-artifact wrap
    pb.write_text(json.dumps(bad))
    assert bench.run_selfcheck([str(pg)]) == 0
    assert bench.run_selfcheck([str(pb)]) == 1


def test_selfcheck_cli(tmp_path, capsys):
    good = bench.compose_line(50.0, "cpu", engine="glv",
                              bucket=64, last=None)
    bad = dict(good, last_measured_tpu=_HW)
    pg, pb = tmp_path / "good.json", tmp_path / "bad.json"
    pg.write_text(json.dumps(good))
    pb.write_text(json.dumps(bad))
    assert bench.run_selfcheck([str(pg)]) == 0
    assert bench.run_selfcheck([str(pb)]) == 1
    out = capsys.readouterr().out
    assert "ok" in out and "buried" in out


def test_history_roundtrip_of_emitted_line(tmp_path):
    """Every line compose_line emits must survive the history gate
    verbatim (append → load → identical record) — the contract that
    lets bench append unconditionally (doc/perf.md)."""
    path = str(tmp_path / "hist.jsonl")
    for line in (
        bench.compose_line(50.0, "cpu", engine="glv",
                           bucket=64, last=None),
        bench.compose_line(91234.5, "tpu", engine="pallas_fbj+pp",
                           bucket=16384, last=_HW),
        bench.compose_route_line(500.0, "cpu", batch=64,
                                 n_channels=2_000, host_rps=250.0),
        {"metric": bench.METRIC, "value": 0.0, "unit": bench.UNIT,
         "vs_baseline": 0.0, "error": "watchdog: exceeded deadline"},
    ):
        assert bench.append_history(line, path=path), line
    entries = bench.load_history(path)
    assert [e["record"] for e in entries][0]["value"] == 50.0
    assert len(entries) == 4
    # the .jsonl form of --selfcheck validates it too
    assert bench.run_selfcheck([path]) == 0
