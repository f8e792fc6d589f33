"""What the per-layer readers share.  Each reader under layers/ takes
the finished run (run.py's `Run`) and returns a number, or None where
there is nothing to read."""
from __future__ import annotations


def share(part: float, whole: float) -> float | None:
    """100 * part / whole, or None where nothing was counted."""
    return 100.0 * part / whole if whole else None


def device_idle(run) -> float | None:
    """100 * (1 - device busy / traced window)."""
    t = run.traced
    if not t or not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def modules_matching(run, kernel: str) -> tuple[float, int]:
    """(device seconds, executions) of the traced programs whose XLA
    module name contains the cell's `kernels.<kernel>.module_match`."""
    t = run.traced
    spec = run.workload.get("kernels", {}).get(kernel)
    if not t or not spec:
        return 0.0, 0
    secs = n = 0
    for name, (s, c) in t["modules"].items():
        if spec["module_match"] in name:
            secs += s
            n += c
    return secs, n


def load_roofline(name: str):
    """benchmarks/rooflines/<name>.py as a module."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "rooflines", name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_roofline_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hist_mean(run, name: str) -> float | None:
    """Mean of a histogram family over the window."""
    n = run.delta.hist_count(name)
    return run.delta.hist_sum(name) / n if n else None
