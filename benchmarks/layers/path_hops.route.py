"""Mean hops of the routes the device path answered in the window: the
mean of `clntpu_route_path_hops` (one observation per query answered ok
from the device path).  The program runs its 20 sweeps whatever the
graph; this says how many of them the answers needed.  The window's
counts by hops go into the notes."""
from lib import readers

FAMILY = "clntpu_route_path_hops"


def _buckets(metrics: dict) -> dict:
    """{upper bound: cumulative count} of the family's one sample."""
    samples = metrics.get(FAMILY, {}).get("samples", [])
    return {b: c for s in samples for b, c in s.get("buckets", [])}


def read(run):
    mean = readers.hist_mean(run, FAMILY)
    if mean is None:
        return None
    before, after = _buckets(run.delta.before), _buckets(run.delta.after)
    by_hops, below = {}, 0
    for bound in sorted(after):
        cum = after[bound] - before.get(bound, 0)
        if cum > below:
            by_hops[int(bound)] = cum - below
        below = cum
    run.note(route_paths=run.delta.hist_count(FAMILY),
             route_paths_by_hops=by_hops)
    return mean
