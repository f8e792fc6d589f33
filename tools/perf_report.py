#!/usr/bin/env python
"""Perf observatory CLI (doc/perf.md): stage attribution + the
bench-regression gate.

Subcommands / modes:

  --rpc <unix-socket> [--family F] [--kernel-rate R]
      Call `getperf` on a running daemon and render the report.  The
      kernel roofline defaults to the best measured kernel rate in
      bench_last_tpu.json (sweep_best, falling back to kernel_only).

  --capture snapshot.json
      Render the report OFFLINE from a saved obs_snapshot capture that
      includes a dispatch_log (capture --dispatches N).

  --local
      Attribute THIS process's registry/flight rings (only useful
      under -c/import after driving a workload — the live-daemon
      equivalent of `obs_snapshot capture --local`).

  --selfcheck [--inflate STAGE]
      Synthetic pipeline proof (the run_suite.sh perf-smoke pass):
      drives the REAL flight ring + clntpu_replay_* counters with a
      hand-built workload whose STAGE (default dispatch) is
      deliberately inflated, then asserts the attribution model names
      exactly that stage as the bottleneck, reproduces the
      hand-computed speedup-if-removed, and reconciles ring vs counter
      sums within the stated epsilon.  Jax-free and fast.

  --compare [--history BENCH_HISTORY.jsonl] [--tolerance 0.10]
      The regression gate: for every metric in the bench trajectory,
      compare the newest measurement against the most recent prior
      baseline of the same platform class (hardware compares against
      the last REAL-hardware baseline, never against a cpu-fallback)
      and exit non-zero when throughput dropped — or kernel
      ms-per-call rose — beyond the noise tolerance.  Replayed
      records (measurement "replayed:*") are skipped as candidates:
      they carry no new measurement.

All output is deterministic text (or --json); exit codes: 0 ok,
1 selfcheck/regression failure, 2 usage/data error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the gate's stated noise tolerance: 10% keeps the gate quiet on
# run-to-run noise and loud on real regressions
DEFAULT_TOLERANCE = 0.10


def load_kernel_rate() -> float | None:
    """The best measured kernel-alone rate (sigs/s) from
    bench_last_tpu.json — the roofline the e2e pipeline is compared
    against (sweep_best is the tuned number; kernel_only the last
    e2e-round measurement)."""
    try:
        with open(os.path.join(REPO, "bench_last_tpu.json")) as f:
            last = json.load(f)
    except Exception:
        return None
    for key in ("sweep_best", "kernel_only"):
        thr = (last.get(key) or {}).get("throughput")
        if thr:
            return float(thr)
    return None


# ---------------------------------------------------------------------------
# Rendering


def _fmt_s(v) -> str:
    return "-" if v is None else f"{v:.4f}s"


def render(report: dict) -> str:
    lines = []
    kr = report.get("kernel_rate")
    lines.append(f"# perf report (epsilon {report.get('epsilon')}"
                 + (f", kernel roofline {kr:.0f}/s" if kr else "") + ")")
    for fam, sec in sorted(report.get("families", {}).items()):
        lines.append("")
        occ = sec.get("occupancy")
        lines.append(
            f"family {fam}: {sec['dispatches']} dispatches, "
            f"{sec['items']} items"
            + (f", occupancy {occ:.2f}" if occ is not None else "")
            + f", pipeline {sec['pipeline']}")
        st = sec["stages"]
        lines.append(
            "  stages  queue_wait " + _fmt_s(st["queue_wait_s"])
            + "  prep " + _fmt_s(st["prep_s"])
            + "  stall " + _fmt_s(st["stall_s"])
            + "  dispatch " + _fmt_s(st["dispatch_s"])
            + "  readback " + _fmt_s(st["readback_s"]))
        ov = sec.get("overlap_ratio")
        lines.append(
            f"  critical path {_fmt_s(sec['critical_path_s'])}"
            f" ({'+'.join(sec['critical_path'])})"
            + (f", overlap {ov:.1%}" if ov is not None else "")
            + f", idle {_fmt_s(sec['idle_s'])}")
        bn = sec.get("bottleneck")
        if bn:
            sp = sec["speedup_if_removed"].get(bn)
            lines.append(
                f"  bottleneck: {bn}"
                + (f" — {sp}x e2e if removed" if sp else
                   " — the entire critical path"))
        thr = sec.get("throughput_per_s")
        if thr:
            lines.append(f"  throughput {thr:.1f} items/s")
        life = sec.get("lifetime")
        if life:
            lines.append(
                f"  lifetime {life['items']} items over "
                f"{_fmt_s(life['critical_path_s'])}: "
                f"{life['throughput_per_s']:.1f} items/s")
        roof = sec.get("roofline")
        if roof:
            lines.append(
                f"  roofline: {roof['fraction_of_roofline']:.1%} of "
                f"kernel rate ({roof['achieved_items_per_s']:.0f} vs "
                f"{roof['kernel_items_per_s']:.0f}/s, gap "
                f"{roof['gap_x']}x)")
        tr = sec.get("transfer", {})
        if tr.get("h2d_bytes") or tr.get("d2h_bytes"):
            lines.append(
                f"  transfer: h2d {tr['h2d_bytes']} B, "
                f"d2h {tr['d2h_bytes']} B")
        recon = sec.get("reconciliation")
        if recon and recon.get("checked"):
            lines.append(
                f"  reconciliation: max rel err "
                f"{recon['max_rel_err']:.4f} "
                + ("OK" if recon["ok"] else
                   "FAIL (unattributed wall time beyond epsilon)"))
    rt = report.get("retraces", {})
    lines.append("")
    lines.append(
        f"retraces: {rt.get('total', 0)} "
        f"(detector {'armed' if rt.get('armed') else 'not armed'})")
    for ev in rt.get("recent", [])[-5:]:
        lines.append(f"  RETRACE {ev.get('program')} {ev.get('key')}")
    dm = report.get("device_memory") or {}
    for dev, stats in sorted(dm.items()):
        lines.append(f"device {dev}: " + ", ".join(
            f"{k}={v}" for k, v in sorted(stats.items())))
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Selfcheck: the synthetic inflated-stage proof


# per-dispatch stage costs (ms); the inflated stage gets 12x
SELF_BASE_MS = {"queue_wait": 4.0, "dispatch": 3.0, "readback": 2.0}
SELF_HIDDEN_PREP_MS = 6.0
SELF_INFLATE = 12.0
SELF_N = 40


def run_selfcheck(inflate: str = "dispatch", as_json: bool = False) -> int:
    """Drive the real flight ring + replay counters with a synthetic
    verify workload whose `inflate` stage is 12x too slow, then hold
    the attribution model to its contract (doc/perf.md):

      1. it names exactly that stage as the bottleneck;
      2. its speedup-if-removed equals the hand-computed Amdahl value;
      3. per-stage totals reconcile with the flight-ring sums AND the
         clntpu_replay_* counter sums within the stated epsilon (no
         unattributed wall time).

    `inflate` is a critical-path stage name: the visible-prep stall is
    spelled "stall" (driven by inflating the producer-queue wait)."""
    from lightning_tpu.obs import attribution, families, flight

    if inflate not in ("stall", "dispatch", "readback"):
        print(f"--inflate must be stall|dispatch|readback, "
              f"got {inflate!r}", file=sys.stderr)
        return 2
    flight.reset_for_tests()
    attribution.reset_for_tests()

    ms = dict(SELF_BASE_MS)
    key = "queue_wait" if inflate == "stall" else inflate
    ms[key] *= SELF_INFLATE
    # prep = what the producer thread burned: the visible share is the
    # queue wait (stall), the rest was hidden behind device compute
    prep_ms = ms["queue_wait"] + SELF_HIDDEN_PREP_MS
    items = 64

    for _ in range(SELF_N):
        rec = flight.begin("verify", shape=(items, 8), n_real=items,
                           lanes=items, queue_wait_ms=ms["queue_wait"],
                           prep_ms=prep_ms, breaker_state="closed")
        rec["readback_ms"] = ms["readback"]
        rec["h2d_bytes"] = 37_000
        rec["d2h_bytes"] = items
        flight.finish(rec, "ok", dispatch_ms=ms["dispatch"])
    # the counters the live pipeline meters (gossip/verify._run_pipeline)
    families.REPLAY_PREP.inc(SELF_N * prep_ms / 1e3)
    families.REPLAY_STALL.inc(SELF_N * ms["queue_wait"] / 1e3)
    families.REPLAY_DISPATCH.inc(SELF_N * ms["dispatch"] / 1e3)
    families.REPLAY_READBACK.inc(SELF_N * ms["readback"] / 1e3)

    report = attribution.report_local(kernel_rate=200_000.0)
    fam = report["families"]["verify"]

    crit_ms = ms["queue_wait"] + ms["dispatch"] + ms["readback"]
    stage_ms = ms[key]
    expected_speedup = round(crit_ms / (crit_ms - stage_ms), 4)
    expected_crit_s = round(SELF_N * crit_ms / 1e3, 6)

    failures = []

    def check(name: str, ok: bool, detail: str) -> None:
        print(f"{'PASS' if ok else 'FAIL'}: {name} ({detail})")
        if not ok:
            failures.append(name)

    check("bottleneck named", fam["bottleneck"] == inflate,
          f"model says {fam['bottleneck']!r}, inflated {inflate!r}")
    got_sp = fam["speedup_if_removed"].get(inflate)
    check("speedup-if-removed matches hand-computed value",
          got_sp is not None and abs(got_sp - expected_speedup) < 1e-3,
          f"model {got_sp} vs hand {expected_speedup}")
    check("critical path total attributed",
          abs(fam["critical_path_s"] - expected_crit_s)
          <= attribution.EPSILON * expected_crit_s,
          f"model {fam['critical_path_s']}s vs hand {expected_crit_s}s")
    recon = fam.get("reconciliation", {})
    check("ring vs clntpu_replay_* reconciliation",
          bool(recon.get("checked")) and bool(recon.get("ok")),
          f"max rel err {recon.get('max_rel_err')} "
          f"<= epsilon {recon.get('epsilon')}")
    check("no unattributed wall time",
          (recon.get("unattributed_s") or 0.0)
          <= attribution.EPSILON * expected_crit_s,
          f"unattributed {recon.get('unattributed_s')}s")
    check("overlap ratio reflects hidden prep",
          fam["overlap_ratio"] is not None
          and abs(fam["overlap_ratio"]
                  - (1 - ms["queue_wait"] / prep_ms)) < 1e-3,
          f"model {fam['overlap_ratio']}")
    if as_json:
        print(json.dumps(report, indent=1))
    if failures:
        print(f"perf selfcheck FAILED: {', '.join(failures)}")
        return 1
    print("perf selfcheck ok")
    return 0


# ---------------------------------------------------------------------------
# The regression gate


# how many prior same-class candidates the gate scans for its
# baseline: comparing only against the IMMEDIATELY previous record
# would let a regression that slipped into the history become the next
# baseline (the gate would fire exactly once, and sub-tolerance drift
# could compound forever) — gating against the best of the recent
# window keeps the bar where the last good measurement put it
BASELINE_WINDOW = 5


def _platform_class(rec: dict) -> str:
    p = rec.get("platform")
    if not p:
        # pre-contract legacy seeds may lack the key entirely; they
        # must never serve as (or gate against) a hardware baseline
        return "unknown"
    return "cpu" if p in ("cpu", "cpu-fallback") else "hardware"


def _is_candidate(rec: dict) -> bool:
    if "error" in rec or not isinstance(rec.get("value"), (int, float)):
        return False
    return not str(rec.get("measurement", "live")).startswith("replayed")


def compare_records(base: dict, cand: dict, tolerance: float) -> list[str]:
    """Regressions of `cand` against `base` beyond the tolerance
    (empty = clean).  Throughput-shaped values regress downward;
    latency-shaped values regress upward."""
    regressions = []
    bv, cv = base.get("value"), cand.get("value")
    if bv and cv is not None and cv < bv * (1 - tolerance):
        regressions.append(
            f"throughput {cv:.1f} < baseline {bv:.1f} "
            f"(-{(1 - cv / bv):.1%}, tolerance {tolerance:.0%})")
    bk = base.get("kernel_only") or {}
    ck = cand.get("kernel_only") or {}
    bkt, ckt = bk.get("throughput"), ck.get("throughput")
    if bkt and ckt and ckt < bkt * (1 - tolerance):
        regressions.append(
            f"kernel throughput {ckt:.1f} < baseline {bkt:.1f} "
            f"(-{(1 - ckt / bkt):.1%})")
    bkm, ckm = bk.get("ms_per_call"), ck.get("ms_per_call")
    if bkm and ckm and ckm > bkm * (1 + tolerance):
        regressions.append(
            f"kernel ms/call {ckm:.2f} > baseline {bkm:.2f} "
            f"(+{(ckm / bkm - 1):.1%})")
    # stage-latency gate: rounds run with --metrics embed the
    # clntpu_replay_* stage sums; compare per-item stage cost
    for stage in ("prep", "prep_stall", "dispatch", "readback"):
        name = f"clntpu_replay_{stage}_seconds_total"
        bs = _stage_per_item(base, name)
        cs = _stage_per_item(cand, name)
        if bs and cs and cs > bs * (1 + tolerance):
            regressions.append(
                f"stage {stage} {cs * 1e6:.2f}us/item > baseline "
                f"{bs * 1e6:.2f}us/item (+{(cs / bs - 1):.1%})")
    return regressions


def _stage_per_item(rec: dict, counter: str) -> float | None:
    fam = (rec.get("metrics") or {}).get(counter)
    n = rec.get("n_sigs")
    if not fam or not n:
        return None
    total = sum(s.get("delta", s.get("value", 0.0))
                for s in fam.get("samples", ()))
    return total / n if total else None


def run_compare(history_path: str, tolerance: float,
                metric: str | None = None) -> int:
    import bench

    try:
        entries = bench.load_history(history_path)
    except (OSError, ValueError) as e:
        print(f"compare: {e}", file=sys.stderr)
        return 2
    by_metric: dict[str, list[dict]] = {}
    for e in entries:
        rec = e["record"]
        m = rec.get("metric")
        if m and (metric is None or m == metric):
            by_metric.setdefault(m, []).append(rec)
    if metric is not None and metric not in by_metric:
        print(f"compare: no history for metric {metric!r}",
              file=sys.stderr)
        return 2
    any_regression = False
    for m, recs in sorted(by_metric.items()):
        cands = [r for r in recs if _is_candidate(r)]
        if not cands:
            print(f"{m}: no measurable candidate (errors/replays only)")
            continue
        cand = cands[-1]
        cls = _platform_class(cand)
        baselines = [r for r in cands[:-1] if _platform_class(r) == cls]
        if not baselines:
            print(f"{m}: no prior {cls} baseline — nothing to gate "
                  f"(candidate {cand.get('value')})")
            continue
        base = max(baselines[-BASELINE_WINDOW:],
                   key=lambda r: r.get("value") or 0.0)
        regs = compare_records(base, cand, tolerance)
        if regs:
            any_regression = True
            print(f"{m} [{cls}]: REGRESSION vs baseline "
                  f"{base.get('measured_at', '?')}")
            for r in regs:
                print(f"  {r}")
        else:
            print(f"{m} [{cls}]: ok ({cand.get('value')} vs baseline "
                  f"{base.get('value')}, tolerance {tolerance:.0%})")
    return 1 if any_regression else 0


# ---------------------------------------------------------------------------


def main() -> int:
    p = argparse.ArgumentParser(prog="perf_report")
    p.add_argument("--rpc", help="daemon unix socket (lightning-rpc)")
    p.add_argument("--capture", help="saved obs_snapshot capture "
                                     "(with --dispatches) to attribute")
    p.add_argument("--local", action="store_true",
                   help="attribute this process's registry")
    p.add_argument("--selfcheck", action="store_true",
                   help="synthetic inflated-stage model proof")
    p.add_argument("--inflate", default="dispatch",
                   help="selfcheck: which critical stage to inflate "
                        "(stall|dispatch|readback)")
    p.add_argument("--compare", action="store_true",
                   help="bench-regression gate over the history")
    p.add_argument("--history", default=None,
                   help="history path (default: repo "
                        "BENCH_HISTORY.jsonl / $BENCH_HISTORY)")
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                   help="relative noise tolerance for --compare "
                        f"(default {DEFAULT_TOLERANCE})")
    p.add_argument("--metric", default=None,
                   help="--compare: gate only this metric")
    p.add_argument("--family", default=None,
                   help="--rpc: restrict to one dispatch family")
    p.add_argument("--kernel-rate", type=float, default=None,
                   help="roofline items/s (default: bench_last_tpu.json)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw report JSON instead of text")
    args = p.parse_args()

    if args.selfcheck:
        return run_selfcheck(args.inflate, as_json=args.json)
    if args.compare:
        import bench

        return run_compare(args.history or bench.HISTORY_PATH,
                           args.tolerance, args.metric)

    kernel_rate = args.kernel_rate or load_kernel_rate()
    if args.rpc:
        from tools.obs_snapshot import rpc_call

        params: dict = {}
        if args.family:
            params["family"] = args.family
        if kernel_rate:
            params["kernel_rate"] = kernel_rate
        report = rpc_call(args.rpc, "getperf", params)
    elif args.capture:
        from lightning_tpu.obs import attribution

        with open(args.capture) as f:
            snap = json.load(f)
        report = attribution.report_from_snapshot(
            snap, kernel_rate=kernel_rate)
    elif args.local:
        from lightning_tpu.obs import attribution

        report = attribution.report_local(kernel_rate=kernel_rate)
    else:
        p.error("need one of --rpc/--capture/--local/"
                "--selfcheck/--compare")
    print(json.dumps(report, indent=1) if args.json else render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
