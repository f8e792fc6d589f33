"""Share of the replay's wall time in which the dispatch thread waited
for a prepared bucket (`clntpu_replay_prep_stall_seconds_total` over
`clntpu_verify_flush_seconds`), over the passes the run completed: the
program moves both once a pass."""
from lib import readers


def read(run):
    d = run.delta
    return readers.share(
        d.counter("clntpu_replay_prep_stall_seconds_total"),
        d.hist_sum("clntpu_verify_flush_seconds"))
