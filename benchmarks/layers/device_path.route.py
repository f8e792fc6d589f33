"""Share of the getroute queries solved in the window that the device
path answered (`clntpu_route_queries_total{path="device",
outcome="ok"}` over all paths and outcomes): the number that would have
shown PR 23's 320 silent host re-solves."""
from lib import readers


def read(run):
    d = run.delta
    return readers.share(
        d.counter("clntpu_route_queries_total", path="device",
                  outcome="ok"),
        d.counter("clntpu_route_queries_total"))
