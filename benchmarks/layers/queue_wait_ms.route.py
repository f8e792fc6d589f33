"""How long a getroute query waits in RouteService's queue before the
flush that takes it starts: the mean of
`clntpu_route_queue_wait_seconds` (one observation per query, made at
the flush's start) over the window.  Under closed-loop load this is the
part of a dispatch in flight that a request waits out."""
from lib import readers


def read(run):
    mean = readers.hist_mean(run, "clntpu_route_queue_wait_seconds")
    if mean is None:
        return None
    run.note(route_queue_waits=run.delta.hist_count(
        "clntpu_route_queue_wait_seconds"))
    return 1e3 * mean
