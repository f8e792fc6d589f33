#!/usr/bin/env python
"""Perf observatory CLI (doc/perf.md): stage attribution of a running
daemon, a saved capture or this process, and the model's selfcheck.
Regressions of speed are caught by the driver's check of every PR in
every cell of BENCHMARK.json, not here.

Subcommands / modes:

  --rpc <unix-socket> [--family F] [--kernel-rate R]
      Call `getperf` on a running daemon and render the report.  The
      kernel roofline is what --kernel-rate gives (items/s); without it
      the report carries no roofline line.

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

All output is deterministic text (or --json); exit codes: 0 ok,
1 selfcheck failure, 2 usage/data error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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
    p.add_argument("--family", default=None,
                   help="--rpc: restrict to one dispatch family")
    p.add_argument("--kernel-rate", type=float, default=None,
                   help="roofline items/s (default: no roofline line)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw report JSON instead of text")
    args = p.parse_args()

    if args.selfcheck:
        return run_selfcheck(args.inflate, as_json=args.json)

    if args.rpc:
        from tools.obs_snapshot import rpc_call

        params: dict = {}
        if args.family:
            params["family"] = args.family
        if args.kernel_rate:
            params["kernel_rate"] = args.kernel_rate
        report = rpc_call(args.rpc, "getperf", params)
    elif args.capture:
        from lightning_tpu.obs import attribution

        with open(args.capture) as f:
            snap = json.load(f)
        report = attribution.report_from_snapshot(
            snap, kernel_rate=args.kernel_rate)
    elif args.local:
        from lightning_tpu.obs import attribution

        report = attribution.report_local(kernel_rate=args.kernel_rate)
    else:
        p.error("need one of --rpc/--capture/--local/--selfcheck")
    print(json.dumps(report, indent=1) if args.json else render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
