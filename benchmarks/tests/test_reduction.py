"""The trace -> metrics reduction on a small trace kept in data/: the
plain form of a few events cut from a chip trace, small enough to count
by hand."""
import json
import os

from lib import counters, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load():
    with open(os.path.join(DATA, "trace_small.json"), encoding="utf8") as f:
        return json.load(f)["planes"]


def test_busy_idle_and_gaps():
    t = xplane.reduce_planes(load(), ("verify/dispatch",),
                             "bench/traced_window")
    # window span 1000..11000 ns; ops cover 2000-5000 (two ops, one
    # overlapping pair merged) and 7000-9000; the op at 10500-12000 is
    # clipped to the window's end
    assert t["window_s"] == 10000 / 1e9
    assert abs(t["busy_s"] - (3000 + 2000 + 500) / 1e9) < 1e-15
    assert t["device_planes"] == 1
    # the third execution is cut by the window's end: not counted
    secs, n = t["modules"]["jit_fused_verify_kernel(123)"]
    assert abs(secs - (3000 + 2000) / 1e9) < 1e-15 and n == 2
    # those two start at 2000 and 7000
    assert abs(t["periods"]["jit_fused_verify_kernel(123)"]
               - 5000 / 1e9) < 1e-15
    assert abs(t["ops"]["fusion.1"] - 3500 / 1e9) < 1e-15
    assert t["spans"]["verify/dispatch"] == [3000 / 1e9, 2]
    gaps = dict(map(tuple, t["idle_gaps"]))
    # 1000-2000 lies in no span, 5000-7000 in the second dispatch span,
    # 9000-10500 in none
    assert abs(gaps["verify/dispatch"] - 2000 / 1e9) < 1e-15
    assert abs(gaps["outside any span"] - 2500 / 1e9) < 1e-15


def test_without_window_span_uses_device_edges():
    t = xplane.reduce_planes(load(), (), None)
    assert t["window_s"] == (12000 - 2000) / 1e9
    assert abs(t["busy_s"] - (3000 + 2000 + 1500) / 1e9) < 1e-15


def test_no_device_plane_reads_nothing():
    planes = [p for p in load() if not p["name"].startswith("/device")]
    t = xplane.reduce_planes(planes, (), "bench/traced_window")
    assert t["busy_s"] == 0.0 and t["modules"] == {}


def test_counter_deltas():
    def snap(v, s, c):
        return {"x_total": {"samples": [
                    {"labels": {"path": "fused"}, "value": v},
                    {"labels": {"path": "host"}, "value": 1}]},
                "h": {"samples": [{"labels": {}, "sum": s, "count": c}]}}
    d = counters.Delta(snap(10, 5.0, 2), snap(25, 9.0, 4))
    assert d.counter("x_total") == 15
    assert d.counter("x_total", path="fused") == 15
    assert d.hist_sum("h") == 4.0 and d.hist_count("h") == 2
    assert d.by_label("x_total", "path") == {"fused": 15}
    assert d.counter("absent_total") == 0
