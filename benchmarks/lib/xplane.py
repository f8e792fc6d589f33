"""From a JAX profiler trace (`.xplane.pb`) to the few numbers the
per-layer readers use.  Read with jax alone (`ProfileData`).

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
op (named by the op's whole HLO text: `%while.18 = (s32[]...`; the
reduction keeps what stands before ` = `) and whose line `XLA Modules`
has one event per executed program (`jit_<function>(<fingerprint>)`);
and a plane `/host:CPU` with one line per host thread, where
`jax.profiler.TraceAnnotation` spans appear under their own names.
The chip's trace buffer is finite: the fused verify program alone
writes about ten million op events a second, and once the buffer is
full the device plane gets one `Trace Buffers Dropped` event (line
`XLA TraceMe`) spanning what was lost.  The reduction ends the window
where that event starts, so busy and idle are taken over the part the
trace really covers.

`reduce()` returns
  window_s    first device-or-host event start to last end, in the
              traced sub-window
  busy_s      union of the intervals in which an op ran, averaged over
              the device planes
  modules     {program name: [seconds, count]}  (XLA Modules), of the
              executions that lie whole inside the window: one cut by
              an edge would count as one with part of its time
  periods     {program name: mean seconds from one of those executions'
              starts to the next}, where there are two or more
  ops         {op name: seconds}                (XLA Ops)
  spans       {annotation name: [seconds, count]} of the host spans
              named in `span_names`
  idle_gaps   [[what the host was in, seconds], ...] the longest gaps
              between device ops, by the enclosing host span
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
DROPPED = "Buffers Dropped"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list]:
    """Total covered nanoseconds -> seconds, and the gaps between the
    merged intervals as (start_ns, end_ns)."""
    covered, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            covered += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered / 1e9, gaps


def reduce_planes(planes: list[dict], span_names: tuple = (),
                  window_span: str | None = None) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns)]}]}] -- the plain form, so a small recorded trace can be
    kept as JSON and the reduction tested without a chip.  Where a
    host span named window_span is in the trace, it bounds the window
    and device events are clipped to it; otherwise the window runs
    from the first device event to the last."""
    dev = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    host = [p for p in planes if not p["name"].startswith(DEVICE_PREFIX)]
    spans: dict = {}
    span_iv = []
    win = None
    for p in host:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name == window_span and win is None:
                    win = (s, s + d)
                if name in span_names:
                    sp = spans.setdefault(name, [0.0, 0])
                    sp[0] += d / 1e9
                    sp[1] += 1
                    span_iv.append((s, s + d, name))

    # where the chip's trace buffer overflowed, the window ends there
    for p in dev:
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if DROPPED in name and win is not None and s < win[1]:
                    win = (win[0], max(win[0], s))

    def clip(s, d):
        e = s + d
        if win is not None:
            s, e = max(s, win[0]), min(e, win[1])
        return (s, e) if e > s else None

    ops: dict = {}
    modules: dict = {}
    starts: dict = {}
    busy, all_gaps, edges = [], [], []
    for p in dev:
        by_line = {ln["name"]: ln["events"] for ln in p["lines"]}
        for name, s, d in by_line.get(MODULES_LINE, ()):
            if clip(s, d) == (s, s + d):
                m = modules.setdefault(name, [0.0, 0])
                m[0] += d / 1e9
                m[1] += 1
                starts.setdefault((p["name"], name), []).append(s)
        intervals = []
        for name, s, d in by_line.get(OPS_LINE, ()):
            iv = clip(s, d)
            if iv:
                intervals.append(iv)
                ops[name] = ops.get(name, 0.0) + (iv[1] - iv[0]) / 1e9
        if not intervals:       # no op line: the programs bound it
            intervals = [iv for _, s, d in by_line.get(MODULES_LINE, ())
                         if (iv := clip(s, d))]
        if intervals:
            if win is not None:  # the window's edges are gaps too
                intervals += [(win[0], win[0]), (win[1], win[1])]
            b, gaps = union_seconds(intervals)
            busy.append(b)
            all_gaps += gaps
            edges += [min(s for s, _ in intervals),
                      max(e for _, e in intervals)]
    # a gap belongs to the host span that covers its middle
    gap_by: dict = {}
    span_iv.sort()
    for gs, ge in all_gaps:
        mid = (gs + ge) // 2
        owner = "outside any span"
        for s, e, name in span_iv:
            if s <= mid < e:
                owner = name
                break
        gap_by[owner] = gap_by.get(owner, 0.0) + (ge - gs) / 1e9
    if win is not None:
        window_s = (win[1] - win[0]) / 1e9
    else:
        window_s = (max(edges) - min(edges)) / 1e9 if edges else 0.0
    periods: dict = {}
    for (_, name), at in starts.items():
        if len(at) > 1 and name not in periods:     # the first chip's
            periods[name] = (max(at) - min(at)) / (len(at) - 1) / 1e9
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "modules": modules, "periods": periods, "ops": ops, "spans": spans,
        "idle_gaps": sorted(([k, v] for k, v in gap_by.items()),
                            key=lambda kv: -kv[1]),
        "device_planes": len(dev),
    }


def load_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            lines.append({"name": ln.name, "events": [
                (ev.name.split(" = ", 1)[0][:96], int(ev.start_ns),
                 int(ev.duration_ns)) for ev in ln.events]})
        planes.append({"name": p.name, "lines": lines})
    return planes


def reduce(trace_dir: str, span_names: tuple = (),
           window_span: str | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(load_planes(path), span_names, window_span)
