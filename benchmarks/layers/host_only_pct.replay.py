"""Share of a crash-boot pass in which no bucket is in the dispatch
stream: 100 x sum(`recovery/boot` - `replay/stream`) / sum(`recovery/boot`)
over the whole passes the run completed (the driver hands the readers
those).  `replay/stream` is the pass's dispatch section, from the
producer's start to the last dispatch returned; what is outside it is the store scan and crc check
(`recovery/store`), extraction (`gossip/extract`), the sort and bucket
plan (`replay/sort`), the readback of the validity bits
(`replay/readback`, which also waits for the dispatches still queued on
the device) and a rest no span owns.  Each is noted in milliseconds a
pass."""
from lib import spans

STAGES = ("recovery/store", "gossip/extract", "replay/sort",
          "replay/readback")


def read(run):
    boot_s, passes = spans.total(run, "recovery/boot")
    stream_s, streams = spans.total(run, "replay/stream")
    if not passes or not streams or not boot_s:
        return None
    per_pass = {name: 1e3 * spans.total(run, name)[0] / passes
                for name in STAGES}
    outside = 1e3 * (boot_s - stream_s) / passes
    run.note(replay_pass_ms=1e3 * boot_s / passes,
             replay_stream_ms=1e3 * stream_s / passes,
             replay_host_only_ms=outside, replay_passes=passes,
             replay_stage_ms=per_pass,
             replay_unattributed_ms=outside - sum(per_pass.values()))
    return 100.0 * (boot_s - stream_s) / boot_s
