"""Host time to turn one route dispatch's labels into answers
(`route/reconstruct`: the predecessor walk and the exact re-pricing of
every answered query), in milliseconds per flush that reached the
device."""
from lib import spans


def read(run):
    value = spans.mean_ms_per(run, "route/reconstruct", "route/dispatch")
    if value is not None:
        run.note(route_reconstruct_spans=spans.total(
            run, "route/reconstruct")[1])
    return value
