"""Real queries over padded device lanes per route dispatch: the mean
of `clntpu_route_batch_occupancy_ratio` over the window."""
from lib import readers


def read(run):
    m = readers.hist_mean(run, "clntpu_route_batch_occupancy_ratio")
    return None if m is None else 100.0 * m
