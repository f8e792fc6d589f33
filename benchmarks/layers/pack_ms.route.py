"""Host time to build one route dispatch's operands (`route/pack`:
plane refresh, node index lookup, the per-query edge masks, the operand
arrays), in milliseconds per flush that reached the device."""
from lib import spans


def read(run):
    value = spans.mean_ms_per(run, "route/pack", "route/dispatch")
    if value is not None:
        run.note(route_pack_spans=spans.total(run, "route/pack")[1],
                 route_dispatch_spans=spans.total(run, "route/dispatch")[1])
    return value
