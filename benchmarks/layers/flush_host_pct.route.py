"""Share of RouteService's flush time that is not the device stage:
100 x (1 - sum of `route/device` spans / sum of `route/flush` spans).
`route/device` runs from the call of the route program to the last of
its outputs read back (upload, execute, readback); the rest of a flush
is the host's: plane refresh and packing, the thread hop,
reconstruction, resolving the futures, host re-solves."""
from lib import spans


def read(run):
    device_s, dispatches = spans.total(run, "route/device")
    flush_s, flushes = spans.total(run, "route/flush")
    if not dispatches or not flush_s:
        return None
    host_solve_s, host_solves = spans.total(run, "route/host_solve")
    run.note(route_flush_ms=1e3 * flush_s / flushes,
             route_device_ms=1e3 * device_s / dispatches,
             route_flushes=flushes, route_device_stages=dispatches,
             route_resolve_ms=spans.mean_ms_per(
                 run, "route/resolve", "route/dispatch"),
             route_host_solves=host_solves,
             route_host_solve_s=host_solve_s)
    return 100.0 * (1.0 - device_s / flush_s)
