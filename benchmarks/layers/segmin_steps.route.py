"""Doubling steps of the route program's segmented minimum
(`clntpu_route_segmin_steps`: 2^steps >= the graph's largest
out-degree), as the gauge stands at the window's close: the static size
by which one graph's route program differs from another's at the same
padded shapes.  A program without the gauge, or one that has run no
route flush, has nothing to read."""
from lib import counters


def read(run):
    return counters.total(run.delta.after,
                          "clntpu_route_segmin_steps") or None
