"""Prefill modules' device time over the device's busy time.
Source: trace."""

from chipbench import reduce


def read(o):
    if o.trace is None:
        return None
    busy = reduce.busy_seconds(o.trace)
    if busy <= 0:
        return None
    return 100.0 * sum(reduce.module_seconds(o.trace, "prefill_chunk")) / busy
