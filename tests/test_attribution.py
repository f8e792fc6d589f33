"""Perf-observatory model tests (doc/perf.md): the synthetic
flight-ring attribution corpus — hand-built rings with KNOWN stage
splits must yield the exact expected breakdown, bottleneck name, and
speedup-if-removed projection — plus the post-warmup retrace detector
and tools/perf_report.py's CLI.

Deliberately jax-free end to end (obs/attribution.py is an obs-package
module): the whole file runs in milliseconds and sorts early in tier-1
without displacing dots.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))) + "/tools")

import perf_report  # noqa: E402
from lightning_tpu.obs import attribution, families, flight  # noqa: E402
from lightning_tpu.utils import events  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    attribution.reset_for_tests()
    flight.reset_for_tests()
    events.reset()
    yield
    attribution.reset_for_tests()
    flight.reset_for_tests()
    events.reset()


def _rec(family="verify", qw=2.0, prep=5.0, disp=4.0, rb=1.0,
         n=64, ts_ns=None, **extra):
    r = {"dispatch_id": 1, "family": family, "ts": 0.0,
         "ts_ns": ts_ns if ts_ns is not None else 0,
         "queue_wait_ms": qw, "prep_ms": prep, "dispatch_ms": disp,
         "readback_ms": rb, "n_real": n, "lanes": n, "outcome": "ok",
         "h2d_bytes": 0, "d2h_bytes": 0, "quarantined": 0}
    r.update(extra)
    return r


# ---------------------------------------------------------------------------
# the attribution corpus: known splits → exact expected output


def test_overlapped_breakdown_exact():
    """verify-family shape: counters are authoritative, the stall is
    the only visible prep, critical = stall + dispatch + readback."""
    n = 10
    records = [_rec(qw=3.0, prep=8.0, disp=6.0, rb=1.0)
               for _ in range(n)]
    totals = {"prep": n * 8.0 / 1e3, "stall": n * 3.0 / 1e3,
              "dispatch": n * 6.0 / 1e3, "readback": n * 1.0 / 1e3}
    sec = attribution.attribute_family("verify", records,
                                       stage_totals_s=totals,
                                       kernel_rate=1000.0)
    st = sec["stages"]
    assert st["prep_s"] == pytest.approx(0.08)
    assert st["stall_s"] == pytest.approx(0.03)
    assert st["dispatch_s"] == pytest.approx(0.06)
    assert st["readback_s"] == pytest.approx(0.01)
    assert sec["critical_path_s"] == pytest.approx(0.10)
    assert sorted(sec["critical_path"]) == ["dispatch", "readback",
                                            "stall"]
    assert sec["bottleneck"] == "dispatch"
    # Amdahl by hand: crit 10ms/dispatch, dispatch 6ms → 10/4 = 2.5x
    assert sec["speedup_if_removed"]["dispatch"] == pytest.approx(2.5)
    assert sec["speedup_if_removed"]["stall"] == pytest.approx(
        10 / 7, abs=1e-4)
    assert sec["speedup_if_removed"]["readback"] == pytest.approx(
        10 / 9, abs=1e-4)
    assert sec["overlap_ratio"] == pytest.approx(1 - 3 / 8)
    assert sec["hidden_prep_s"] == pytest.approx(0.05)
    # throughput = items / critical seconds; roofline vs 1000/s kernel
    assert sec["throughput_per_s"] == pytest.approx(640 / 0.10)
    assert sec["roofline"]["gap_x"] == pytest.approx(
        1000.0 / 6400.0, abs=0.01)
    # ring agrees with counters exactly here → reconciliation clean
    recon = sec["reconciliation"]
    assert recon["checked"] and recon["ok"]
    assert recon["max_rel_err"] == 0.0


def test_serial_breakdown_exact():
    """route/sign-family shape: no stage counters, every stage is on
    the critical path and prep is fully visible."""
    records = [_rec(family="route", qw=2.0, prep=1.0, disp=7.0, rb=0.0,
                    n=8) for _ in range(5)]
    sec = attribution.attribute_family("route", records)
    assert sec["pipeline"] == "serial"
    assert sec["critical_path_s"] == pytest.approx(5 * 10.0 / 1e3)
    assert sec["bottleneck"] == "dispatch"
    assert sorted(sec["critical_path"]) == ["dispatch", "prep",
                                            "queue_wait", "readback"]
    assert sec["speedup_if_removed"]["dispatch"] == pytest.approx(
        10 / 3, abs=1e-4)
    assert "reconciliation" not in sec


def test_each_stage_wins_when_inflated():
    """The bottleneck follows the inflated stage — the selfcheck
    contract, swept across every critical stage."""
    for inflate, expect in (("qw", "stall"), ("disp", "dispatch"),
                            ("rb", "readback")):
        base = {"qw": 2.0, "disp": 3.0, "rb": 1.0}
        base[inflate] *= 20
        n = 4
        records = [_rec(qw=base["qw"], prep=base["qw"] + 1.0,
                        disp=base["disp"], rb=base["rb"])
                   for _ in range(n)]
        totals = {"prep": n * (base["qw"] + 1.0) / 1e3,
                  "stall": n * base["qw"] / 1e3,
                  "dispatch": n * base["disp"] / 1e3,
                  "readback": n * base["rb"] / 1e3}
        sec = attribution.attribute_family("verify", records,
                                           stage_totals_s=totals)
        assert sec["bottleneck"] == expect, (inflate, sec["bottleneck"])


def test_reconciliation_flags_unattributed_time():
    """Counters that disagree with the ring beyond epsilon must be
    reported as a reconciliation failure, not silently averaged."""
    n = 8
    records = [_rec(qw=2.0, prep=4.0, disp=3.0, rb=1.0)
               for _ in range(n)]
    totals = {"prep": n * 4.0 / 1e3, "stall": n * 2.0 / 1e3,
              "dispatch": 2 * n * 3.0 / 1e3,  # 2x what the ring saw
              "readback": n * 1.0 / 1e3}
    sec = attribution.attribute_family("verify", records,
                                       stage_totals_s=totals)
    recon = sec["reconciliation"]
    assert recon["checked"] and not recon["ok"]
    assert recon["rel_err"]["dispatch"] == pytest.approx(0.5)


def test_incomplete_ring_skips_reconciliation():
    sec = attribution.attribute_family(
        "verify", [_rec()], stage_totals_s={"prep": 1.0, "stall": 0.5,
                                            "dispatch": 0.2,
                                            "readback": 0.1},
        ring_complete=False)
    assert sec["reconciliation"]["checked"] is False


def test_transfer_and_wall_span():
    records = [
        _rec(ts_ns=0, h2d_bytes=1000, d2h_bytes=10),
        _rec(ts_ns=50_000_000, h2d_bytes=2000, d2h_bytes=20),
    ]
    sec = attribution.attribute_family("sign", records)
    assert sec["transfer"]["h2d_bytes"] == 3000
    assert sec["transfer"]["d2h_bytes"] == 30
    # span = 50ms between starts + the last record's own
    # qw+prep+dispatch+readback (2+5+4+1 = 12ms) — prep included so a
    # serial family's span is never smaller than its critical path
    assert sec["wall_span_s"] == pytest.approx(0.062)
    assert sec["wall_span_s"] >= sec["critical_path_s"] / 2  # 2 recs


def test_report_local_uses_live_ring_and_counters():
    for _ in range(3):
        rec = flight.begin("verify", n_real=8, lanes=8,
                           queue_wait_ms=2.0, prep_ms=4.0)
        rec["readback_ms"] = 1.0
        flight.finish(rec, "ok", dispatch_ms=3.0)
    families.REPLAY_PREP.inc(0.012)
    families.REPLAY_STALL.inc(0.006)
    families.REPLAY_DISPATCH.inc(0.009)
    families.REPLAY_READBACK.inc(0.003)
    rep = attribution.report_local(kernel_rate=100.0)
    fam = rep["families"]["verify"]
    assert fam["pipeline"] == "overlapped"
    assert fam["reconciliation"]["ok"]
    assert fam["bottleneck"] == "dispatch"
    c = attribution.compact(rep)
    assert c["families"]["verify"]["bottleneck"] == "dispatch"


def test_verify_lifetime_rate_reads_replay_sigs_total():
    """getperf's verify section divides clntpu_replay_sigs_total by
    the stage counters' critical path: a rate that outlives the ring
    (two records left of a replay that dispatched 50 buckets)."""
    for _ in range(2):
        rec = flight.begin("verify", n_real=8, lanes=8,
                           queue_wait_ms=2.0, prep_ms=4.0)
        rec["readback_ms"] = 1.0
        flight.finish(rec, "ok", dispatch_ms=3.0)
    # the registry is the process's: read what earlier cases left
    from lightning_tpu.obs import REGISTRY

    metrics = REGISTRY.snapshot()["metrics"]
    totals = attribution.replay_stage_totals(metrics) or {}
    before = {"items": attribution.replay_sigs_total(metrics),
              "critical_path_s": sum(totals.get(k, 0.0) for k in (
                  "stall", "dispatch", "readback"))}
    families.REPLAY_PREP.inc(50 * 0.004)
    families.REPLAY_STALL.inc(50 * 0.002)
    families.REPLAY_DISPATCH.inc(50 * 0.003)
    families.REPLAY_READBACK.inc(50 * 0.001)
    families.REPLAY_SIGS.inc(50 * 8)
    fam = attribution.report_local()["families"]["verify"]
    assert fam["items"] == 16                    # the ring's two
    life = fam["lifetime"]
    assert life["items"] == before["items"] + 400
    assert life["critical_path_s"] == pytest.approx(
        before["critical_path_s"] + 0.3)
    assert life["throughput_per_s"] == pytest.approx(
        life["items"] / life["critical_path_s"], rel=1e-3)
    # no counter, no block: the serial families and a process that
    # never replayed report the ring alone
    assert "lifetime" not in attribution.attribute_family(
        "route", [_rec(family="route", n=8)])


def test_report_from_snapshot_offline():
    snap = {
        "metrics": {},
        "dispatch_log": [_rec(family="route", n=8) for _ in range(4)],
        "dispatches": {"families": {"route": {"total": 4}}},
    }
    rep = attribution.report_from_snapshot(snap)
    assert rep["families"]["route"]["dispatches"] == 4
    assert rep["families"]["route"]["pipeline"] == "serial"


# ---------------------------------------------------------------------------
# the retrace detector


def test_retrace_fires_only_after_warmup():
    got = []
    events.subscribe("retrace", got.append)
    # before any warmup: first-sights are silent (cold test processes
    # must not spam anomalies)
    assert not attribution.note_program("fused", (8, 4))
    with attribution.warmup_scope():
        assert not attribution.note_program("fused", (8, 8))
    # armed now: a seen shape stays quiet, a NEW one is the anomaly
    assert not attribution.note_program("fused", (8, 8))
    assert attribution.note_program("fused", (16, 8))
    assert len(got) == 1
    assert got[0]["program"] == "fused" and got[0]["key"] == [16, 8]
    st = attribution.retrace_state()
    assert st["armed"] and st["total"] == 1
    assert st["recent"][0]["program"] == "fused"


def test_retrace_counter_increments():
    from lightning_tpu.obs import REGISTRY

    def count():
        fam = REGISTRY.snapshot()["metrics"].get("clntpu_retrace_total",
                                                 {"samples": []})
        return sum(s["value"] for s in fam["samples"]
                   if s["labels"].get("program") == "prog_x")

    before = count()
    with attribution.warmup_scope():
        attribution.note_program("prog_x", (1,))
    attribution.note_program("prog_x", (2,))
    assert count() == before + 1


def test_rates_use_ring_window_when_ring_wrapped():
    """Counters are process-lifetime, the ring is bounded: once the
    ring wraps, throughput/transfer rates must divide ring items by
    RING-window seconds, not by the (much larger) lifetime totals."""
    n = 4
    records = [_rec(qw=2.0, prep=5.0, disp=3.0, rb=1.0, n=64,
                    h2d_bytes=1000) for _ in range(n)]
    # lifetime counters: 100x the window (the ring kept 4 of ~400)
    totals = {"prep": 0.5, "stall": 0.2, "dispatch": 0.3,
              "readback": 0.1}
    sec = attribution.attribute_family("verify", records,
                                       stage_totals_s=totals,
                                       ring_complete=False,
                                       kernel_rate=10_000.0)
    window = n * (2.0 + 3.0 + 1.0) / 1e3     # ring qw+disp+rb
    assert sec["window_s"] == pytest.approx(window)
    assert sec["throughput_per_s"] == pytest.approx(
        n * 64 / window, rel=1e-3)
    assert sec["transfer"]["h2d_bytes_per_s"] == pytest.approx(
        n * 1000 / window, rel=1e-3)
    assert sec["roofline"]["achieved_items_per_s"] == pytest.approx(
        n * 64 / window, rel=1e-3)
    # the stage breakdown itself stays lifetime (the authoritative
    # totals the bottleneck/speedup are computed from)
    assert sec["critical_path_s"] == pytest.approx(0.6)


def test_retrace_total_is_monotonic_beyond_ring():
    with attribution.warmup_scope():
        pass
    for i in range(70):
        assert attribution.note_program("p", (i,))
    st = attribution.retrace_state()
    assert st["total"] == 70
    assert len(st["recent"]) == 64   # the ring stays bounded


def test_nested_warmup_scopes_suppress():
    with attribution.warmup_scope():
        with attribution.warmup_scope():
            assert not attribution.note_program("p", (1,))
        # still inside the outer scope: expected, not an anomaly
        assert not attribution.note_program("p", (2,))
    assert attribution.note_program("p", (3,))


def test_sample_device_memory_never_imports_jax(monkeypatch):
    # the sampler peeks sys.modules instead of importing: in a process
    # without jax it must return {} rather than trigger the (possibly
    # hanging) accelerator probe.  Simulated here because the pytest
    # session itself may already have jax loaded via other files.
    monkeypatch.setitem(sys.modules, "jax", None)
    assert attribution.sample_device_memory() == {}


# ---------------------------------------------------------------------------
# the perf-smoke CLI (the run_suite.sh pass, end to end)


def test_perf_report_selfcheck_cli():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_report.py"),
         "--selfcheck"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "bottleneck named" in r.stdout
    assert "perf selfcheck ok" in r.stdout


def test_perf_report_has_no_compare_mode():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_report.py"),
         "--compare"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stdout + r.stderr
    assert "unrecognized arguments: --compare" in r.stderr


def test_perf_report_renders_without_a_kernel_rate(tmp_path, monkeypatch,
                                                   capsys):
    """The roofline is what --kernel-rate gives and otherwise absent;
    the CLI reads the capture it is handed and no file of the repo."""
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({
        "metrics": {},
        "dispatch_log": [_rec(n=8) for _ in range(4)],
        "dispatches": {"families": {"verify": {"total": 4}}},
    }))
    opened = []
    real_open = open

    def spy(path, *a, **kw):
        opened.append(os.path.abspath(path))
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", spy)

    def run(*extra):
        monkeypatch.setattr(sys, "argv", ["perf_report", "--capture",
                                          str(snap), *extra])
        assert perf_report.main() == 0
        return capsys.readouterr().out

    plain = run()
    assert "family verify: 4 dispatches" in plain
    assert "roofline" not in plain
    assert "roofline:" in run("--kernel-rate", "1000")
    assert str(snap) in opened
    assert not [p for p in opened
                if p != str(snap) and p.startswith(REPO + os.sep)]
