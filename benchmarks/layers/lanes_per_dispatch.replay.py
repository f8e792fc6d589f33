"""Lanes one verify dispatch of the replay carried
(`clntpu_verify_lanes_total{kind="verify"}` over
`clntpu_replay_buckets_total`), over the passes the run completed: the
replay's bucket, by which a line run at one bucket is told from a line
run at another.  Where no bucket was dispatched there is nothing to
read."""


def read(run):
    d = run.delta
    buckets = d.counter("clntpu_replay_buckets_total")
    lanes = d.counter("clntpu_verify_lanes_total", kind="verify")
    return lanes / buckets if buckets and lanes else None
