"""The route program's share of its roofline: the least time the chip
could take for the batches the traced dispatches carried
(rooflines/route.py) over the device time of those programs."""
from lib import readers

roofline = readers.load_roofline("route")


def read(run):
    secs, n = readers.modules_matching(run, "route")
    per_batch = readers.hist_mean(run, "clntpu_route_batch_queries")
    if not secs or not n or not per_batch:
        return None
    g = run.config["graph"]
    least, bound = roofline.least_seconds(
        2 * g["channels"], g["nodes"], per_batch, run.peaks)
    run.note(route_roofline_bound=bound, route_device_s=secs,
             route_dispatches=n, queries_per_dispatch=per_batch)
    return 100.0 * n * least / secs
