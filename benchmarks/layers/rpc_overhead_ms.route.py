"""What the JSON-RPC layer adds to a getroute: the clients' mean
latency less the mean of `clntpu_route_answer_seconds` (the handler's
own clock), over the window."""
from lib import readers


def read(run):
    inner = readers.hist_mean(run, "clntpu_route_answer_seconds")
    if inner is None or not run.samples:
        return None
    mean = sum(s[2] - s[1] for s in run.samples) / len(run.samples)
    return (mean - inner) * 1e3
