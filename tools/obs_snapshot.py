#!/usr/bin/env python
"""Capture and diff metrics snapshots (the bench-side consumer of the
lightning_tpu.obs registry).

Subcommands:
  capture --rpc <unix-socket> [-o out.json]
      Call `getmetrics` on a running daemon and write the snapshot.
  capture --url http://host:port [-o out.json]
      Scrape the REST `getmetrics` POST surface instead.
  capture --local [-o out.json]
      Snapshot THIS process's registry (only useful under -c/import).
  diff a.json b.json
      Print per-metric deltas b-a: counters as deltas, gauges as the
      new value, histograms as count/sum deltas plus the mean.
  capture ... --watch N
      Periodic-diff mode: re-capture every N seconds and print the
      delta since the previous capture (one JSON object per tick,
      prefixed with an ISO timestamp comment).  This is how replay
      overlap is observed live during a run: watch the
      clntpu_replay_prep/_stall/_dispatch stage counters and the
      overlap-ratio histogram move while verify_store streams buckets
      (doc/replay_pipeline.md).  Ctrl-C exits cleanly.
  capture ... --dispatches N
      Fold the last N flight records (listdispatches, doc/tracing.md)
      into the capture as `dispatch_log`; diff/--watch then print only
      the dispatches NEW since the previous snapshot — the "which
      dispatch blew up that counter delta" view.

The diff output is the "what did this flush actually do" view:
two snapshots bracket a workload and the delta is attributable to it.

Captures carry the getmetrics `perf` section (the stage-attribution
report, doc/perf.md) — capture --local computes it in-process, and
diffs/--watch ticks fold in its compact per-family view (bottleneck +
critical-path seconds), so a watch tick NAMES the bottleneck as the
stage counters move.

RPC/REST captures also carry the health engine's `gethealth` report
when the daemon runs one (doc/health.md); --watch ticks then print the
rolled-up state, per-SLO statuses, and window rates read from the
engine's time-series rings — the same numbers tools/dashboard.py
renders — falling back to plain local diffing on daemons without the
engine.

Captures additionally carry the `listincidents` summary when the
daemon runs the black-box recorder (doc/incidents.md); --watch prints
a `# NEW INCIDENT ...` line (plus the bundle summary in the delta)
the tick a new bundle lands mid-watch.

When the daemon samples per-item journeys (doc/journeys.md,
LIGHTNING_TPU_JOURNEY_SAMPLE) the `getjourney` summary rides along
too; --watch then prints a `# SLOW JOURNEY ...` line naming the
slowest finished entity the tick the rolling e2e p99 breaches
--slow-journey-ms (default 1000).
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rpc_call(rpc_path: str, method: str, params: dict | None = None) -> dict:
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(30)
    s.connect(rpc_path)
    s.sendall(json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                          "params": params or {}}).encode())
    buf = b""
    while b"\n\n" not in buf:
        chunk = s.recv(1 << 20)
        if not chunk:
            break
        buf += chunk
    s.close()
    resp = json.loads(buf.split(b"\n\n")[0])
    if "error" in resp:
        raise SystemExit(f"{method} failed: {resp['error']}")
    return resp["result"]


def capture_rpc(rpc_path: str, dispatches: int | None = None) -> dict:
    """getmetrics over the daemon's unix JSON-RPC socket;
    --dispatches N folds the last N flight records in (listdispatches,
    doc/tracing.md).  When the daemon runs the health engine the
    gethealth report rides along too, so --watch ticks read their
    window rates from the SAME rings the dashboard renders
    (doc/health.md); a daemon without the engine falls back to plain
    local diffing."""
    snap = rpc_call(rpc_path, "getmetrics")
    if dispatches:
        snap["dispatch_log"] = rpc_call(
            rpc_path, "listdispatches",
            {"limit": dispatches})["dispatches"]
    try:
        health = rpc_call(rpc_path, "gethealth")
        # a daemon that registers gethealth but never installed/ran an
        # engine answers with an empty zero-tick report — that is the
        # "no engine" case too, not a health signal worth folding
        if health.get("ticks"):
            snap["health"] = health
    except (SystemExit, OSError, ValueError, KeyError):
        pass
    try:
        inc = rpc_call(rpc_path, "listincidents", {"limit": 8})
        if inc.get("enabled"):
            snap["incidents"] = inc
    except (SystemExit, OSError, ValueError, KeyError):
        pass  # no black-box recorder behind this socket
    try:
        jr = rpc_call(rpc_path, "getjourney", {"limit": 5})
        if jr.get("enabled"):
            snap["journeys"] = jr
    except (SystemExit, OSError, ValueError, KeyError):
        pass  # no journey sampling behind this socket
    return snap


def capture_url(url: str, rune: str | None = None,
                dispatches: int | None = None) -> dict:
    """getmetrics over the REST gateway (POST /v1/getmetrics).  A
    rune-gated daemon (commando configured) needs --rune."""
    import urllib.request

    headers = {"Rune": rune} if rune else {}

    def post(method: str, body: dict) -> dict:
        req = urllib.request.Request(
            url.rstrip("/") + "/v1/" + method,
            data=json.dumps(body).encode(), method="POST",
            headers=headers)
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.load(r)

    snap = post("getmetrics", {})
    if dispatches:
        snap["dispatch_log"] = post(
            "listdispatches", {"limit": dispatches})["dispatches"]
    try:
        health = post("gethealth", {})
        if health.get("ticks"):      # zero ticks = no engine running
            snap["health"] = health
    except Exception:
        pass  # no health engine behind this gateway: local diffing only
    try:
        inc = post("listincidents", {"limit": 8})
        if inc.get("enabled"):
            snap["incidents"] = inc
    except Exception:
        pass  # no black-box recorder behind this gateway
    try:
        jr = post("getjourney", {"limit": 5})
        if jr.get("enabled"):
            snap["journeys"] = jr
    except Exception:
        pass  # no journey sampling behind this gateway
    return snap


def capture_local(dispatches: int | None = None) -> dict:
    from lightning_tpu import obs
    # well-known families owned by heavyweight modules (routing.device,
    # daemon.hsmd) are declared in this jax-free module so they appear
    # present-at-zero in a fresh capture process — a diff against a
    # later in-daemon snapshot then attributes deltas correctly.  The
    # attribution import does the same for the perf-observatory
    # families (clntpu_retrace_total, clntpu_transfer_bytes_total,
    # clntpu_device_memory_bytes) and adds the `perf` section the
    # getmetrics RPC carries (doc/perf.md).
    from lightning_tpu.obs import attribution, families, flight  # noqa: F401

    snap = obs.snapshot()
    snap["perf"] = attribution.report_local(metrics=snap["metrics"])
    if dispatches:
        snap["dispatch_log"] = flight.recent(limit=dispatches)
    from lightning_tpu.obs import incident as _incident

    rec = _incident.current()
    if rec is not None:
        snap["incidents"] = rec.summary(limit=8)
    from lightning_tpu.obs import journey as _journey

    if _journey.enabled():
        snap["journeys"] = {"enabled": True,
                            "summary": _journey.summary(),
                            "journeys": _journey.recent(5)}
    return snap


def _sample_key(rec: dict) -> tuple:
    return tuple(sorted(rec.get("labels", {}).items()))


def diff_snapshots(a: dict, b: dict) -> dict:
    """Per-metric delta of two snapshot dicts (the getmetrics shape).
    Metrics/samples absent from `a` count from zero."""
    out: dict = {}
    am = a.get("metrics", {})
    for name, fam in b.get("metrics", {}).items():
        prev = {_sample_key(s): s
                for s in am.get(name, {}).get("samples", [])}
        rows = []
        for s in fam["samples"]:
            p = prev.get(_sample_key(s), {})
            labels = s.get("labels", {})
            if fam["kind"] == "histogram":
                dc = s["count"] - p.get("count", 0)
                ds = s["sum"] - p.get("sum", 0.0)
                if dc == 0:
                    continue
                rows.append({"labels": labels, "count": dc,
                             "sum": round(ds, 6),
                             "mean": round(ds / dc, 6)})
            elif fam["kind"] == "counter":
                d = s["value"] - p.get("value", 0.0)
                if d == 0:
                    continue
                rows.append({"labels": labels, "delta": d})
            else:  # gauge: point-in-time, report the new value
                rows.append({"labels": labels, "value": s["value"]})
        if rows:
            out[name] = {"kind": fam["kind"], "samples": rows}
    # the perf section (getmetrics "perf" / capture_local) is a
    # point-in-time analysis like a gauge: the diff carries `b`'s
    # compact view (bottleneck + critical path per family) so a
    # --watch tick names the bottleneck as the counters move
    if "perf" in b:
        try:
            from lightning_tpu.obs import attribution

            out["perf"] = attribution.compact(b["perf"])
        except Exception:
            out["perf"] = b["perf"]
    # the health engine's report (gethealth) is point-in-time like the
    # perf section: a --watch tick carries the compact view — rolled-up
    # state, per-SLO statuses, and the short-window rates read from the
    # engine's own rings, so watch output and tools/dashboard.py agree
    # on the same numbers (doc/health.md)
    if "health" in b:
        try:
            from lightning_tpu.obs import health as _health

            out["health"] = _health.compact(b["health"])
        except Exception:
            out["health"] = b["health"]
    # incident bundles (listincidents, doc/incidents.md): the diff
    # keeps only the bundles NEW since `a` — the "--watch prints a line
    # when a new incident lands" hook reads this
    if "incidents" in b:
        seen_inc = {r.get("id")
                    for r in (a.get("incidents") or {}).get(
                        "incidents", [])}
        new_inc = [r for r in b["incidents"].get("incidents", [])
                   if r.get("id") not in seen_inc]
        if new_inc:
            out["incidents"] = {
                "new": new_inc,
                "count": b["incidents"].get("count"),
                "total_bytes": b["incidents"].get("total_bytes"),
            }
    # the journey summary (getjourney, doc/journeys.md) is
    # point-in-time like the perf/health sections: a --watch tick
    # carries the compact view — table occupancy, the rolling e2e
    # tail, and the slowest finished entity — so the SLOW JOURNEY
    # hook below has its numbers in the delta too
    if "journeys" in b:
        s = b["journeys"].get("summary") or {}
        slowest = s.get("slowest")
        out["journeys"] = {
            "entities": s.get("entities"),
            "finished": s.get("finished"),
            "evicted": s.get("evicted"),
            "e2e_ms_p50": s.get("e2e_ms_p50"),
            "e2e_ms_p99": s.get("e2e_ms_p99"),
            "slowest": None if not slowest else {
                "kind": slowest.get("kind"),
                "key": str(slowest.get("key")),
                "e2e_ms": slowest.get("e2e_ms"),
            },
        }
    # flight records captured with --dispatches: the diff keeps only
    # the dispatches NEW since `a`, so a --watch tick shows WHICH
    # dispatch blew up a counter delta, not just that one did
    if "dispatch_log" in b:
        seen = {r.get("dispatch_id") for r in a.get("dispatch_log", [])}
        new = [r for r in b["dispatch_log"]
               if r.get("dispatch_id") not in seen]
        if new:
            out["dispatch_log"] = new
    return out


def watch(capture, interval: float, out=None,
          ticks: int | None = None, sleep=None,
          slow_journey_ms: float = 1000.0) -> None:
    """Capture every `interval` seconds, printing the per-tick delta
    (the live view of a replay's clntpu_replay_* stage counters, or of
    the clntpu_breaker_* / clntpu_quarantine_* resilience families
    while a fault plays out).  `ticks` bounds the number of deltas
    printed (None = until Ctrl-C); `sleep` injects a waiter (tests).
    A tick whose journey e2e p99 exceeds `slow_journey_ms` calls it out
    on a `# SLOW JOURNEY` line naming the slowest finished entity."""
    import datetime
    import time

    if out is None:
        out = sys.stdout
    if sleep is None:
        sleep = time.sleep
    prev = capture()
    printed = 0
    try:
        while ticks is None or printed < ticks:
            sleep(interval)
            cur = capture()
            stamp = datetime.datetime.now().isoformat(timespec="seconds")
            delta = diff_snapshots(prev, cur)
            print(f"# {stamp} (+{interval:g}s)", file=out, flush=False)
            for row in (delta.get("incidents") or {}).get("new", []):
                # a bundle landed mid-watch: call it out on its own
                # line, not just inside the delta JSON
                print(f"# NEW INCIDENT {row.get('id')} "
                      f"trigger={row.get('trigger')} "
                      f"bytes={row.get('bytes')}", file=out,
                      flush=False)
            jsum = delta.get("journeys") or {}
            p99 = jsum.get("e2e_ms_p99")
            if isinstance(p99, (int, float)) and p99 > slow_journey_ms:
                slow = jsum.get("slowest") or {}
                print(f"# SLOW JOURNEY e2e p99={p99:.1f}ms > "
                      f"{slow_journey_ms:g}ms slowest="
                      f"{slow.get('kind')} {slow.get('key')} "
                      f"({slow.get('e2e_ms')}ms)", file=out,
                      flush=False)
            print(json.dumps(delta if delta else {}, indent=1),
                  file=out, flush=True)
            prev = cur
            printed += 1
    except KeyboardInterrupt:
        pass


def main() -> int:
    p = argparse.ArgumentParser(prog="obs_snapshot")
    sub = p.add_subparsers(dest="cmd", required=True)
    cap = sub.add_parser("capture")
    cap.add_argument("--rpc", help="daemon unix socket (lightning-rpc)")
    cap.add_argument("--url", help="REST base url (http://127.0.0.1:PORT)")
    cap.add_argument("--rune", help="rune for a commando-gated REST "
                                    "server (with --url)")
    cap.add_argument("--local", action="store_true",
                     help="snapshot this process's registry")
    cap.add_argument("--watch", type=float, metavar="N",
                     help="periodic-diff mode: re-capture every N "
                          "seconds and print the delta since the "
                          "previous capture")
    cap.add_argument("--ticks", type=int, metavar="K",
                     help="with --watch: stop after K deltas instead "
                          "of running until Ctrl-C")
    cap.add_argument("--dispatches", type=int, metavar="N",
                     help="include the last N flight records "
                          "(listdispatches) in the capture; with "
                          "--watch, each tick prints only the "
                          "dispatches NEW since the previous tick")
    cap.add_argument("--slow-journey-ms", type=float, default=1000.0,
                     metavar="MS",
                     help="with --watch: print a SLOW JOURNEY line "
                          "when the rolling journey e2e p99 exceeds "
                          "MS (doc/journeys.md)")
    cap.add_argument("-o", "--out", default="-")
    d = sub.add_parser("diff")
    d.add_argument("a")
    d.add_argument("b")
    args = p.parse_args()

    if args.cmd == "capture":
        if args.dispatches is not None and args.dispatches <= 0:
            p.error("--dispatches must be positive")
        if args.rpc:
            capture = lambda: capture_rpc(args.rpc,
                                          dispatches=args.dispatches)
        elif args.url:
            capture = lambda: capture_url(args.url, rune=args.rune,
                                          dispatches=args.dispatches)
        elif args.local:
            capture = lambda: capture_local(dispatches=args.dispatches)
        else:
            p.error("need --rpc, --url, or --local")
        if args.watch is not None:
            if args.watch <= 0:
                p.error("--watch interval must be positive")
            if args.ticks is not None and args.ticks <= 0:
                p.error("--ticks must be positive")
            if args.slow_journey_ms <= 0:
                p.error("--slow-journey-ms must be positive")
            if args.out == "-":
                watch(capture, args.watch, ticks=args.ticks,
                      slow_journey_ms=args.slow_journey_ms)
            else:
                with open(args.out, "w") as f:
                    watch(capture, args.watch, out=f, ticks=args.ticks,
                          slow_journey_ms=args.slow_journey_ms)
            return 0
        snap = capture()
        text = json.dumps(snap, indent=1)
        if args.out == "-":
            print(text)
        else:
            with open(args.out, "w") as f:
                f.write(text)
    else:
        with open(args.a) as f:
            a = json.load(f)
        with open(args.b) as f:
            b = json.load(f)
        print(json.dumps(diff_snapshots(a, b), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
