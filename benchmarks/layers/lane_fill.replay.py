"""Signatures carried over device lanes dispatched by the replay
(`clntpu_verify_batch_sigs` sum over
`clntpu_verify_lanes_total{kind="verify"}`)."""
from lib import readers


def read(run):
    d = run.delta
    return readers.share(
        d.hist_sum("clntpu_verify_batch_sigs"),
        d.counter("clntpu_verify_lanes_total", kind="verify"))
