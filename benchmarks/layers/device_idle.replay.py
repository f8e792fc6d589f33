"""Share of the whole window in which the chip ran nothing, during
crash-boot replay: 1 - (dispatches in the window x device-busy time
per dispatch) / the window's seconds.  The busy time per dispatch is
the trace's: the busy share of the traced sub-window, which lies inside
a pass, times the period from one verify execution's start to the
next.  The dispatches and the seconds are the window's own, so the
host-only work between two passes, which the window holds whole and
the short sub-window cannot, is counted."""


def read(run):
    t, q = run.traced, run.quantities
    spec = run.workload.get("kernels", {}).get("verify")
    if not t or not spec or not t["window_s"] or not t["busy_s"] \
            or not q.get("buckets_in_window"):
        return None
    period = next((v for k, v in t["periods"].items()
                   if spec["module_match"] in k), None)
    if not period:
        return None
    busy_per_dispatch = period * t["busy_s"] / t["window_s"]
    run.note(verify_dispatch_period_ms=1e3 * period,
             verify_dispatch_busy_ms=1e3 * busy_per_dispatch)
    return 100.0 * (1.0 - q["buckets_in_window"] * busy_per_dispatch
                    / q["window_elapsed_s"])
