"""The verify programs' share of their roofline: the least time the
chip could take for the signatures the traced dispatches carried
(rooflines/verify.py, int8 peak or HBM, whichever bounds) over the
device time of those programs in the trace."""
from lib import readers

roofline = readers.load_roofline("verify")


def read(run):
    secs, n = readers.modules_matching(run, "verify")
    d = run.delta
    buckets = d.counter("clntpu_replay_buckets_total")
    if not secs or not n or not buckets:
        return None
    sigs = n * d.hist_sum("clntpu_verify_batch_sigs") / buckets
    signed = sigs * run.config["signed_bytes_per_signature"]
    least, bound = roofline.least_seconds(sigs, signed, run.peaks)
    run.note(verify_roofline_bound=bound, verify_device_s=secs,
             verify_dispatches=n, verify_sigs=sigs)
    return 100.0 * least / secs
