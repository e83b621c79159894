"""Time in collective operations with no compute running on that
device, over the traced window. Source: trace. Only across chips."""

from chipbench import reduce


def read(o):
    if o.trace is None or o.chips < 2 or o.trace_window_s <= 0:
        return None
    return 100.0 * reduce.exposed_collective_seconds(o.trace) \
        / o.trace_window_s
