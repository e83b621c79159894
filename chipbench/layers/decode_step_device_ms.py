"""Median device time of the decode-step XLA modules. Source: trace."""

from chipbench import reduce, stats


def read(o):
    if o.trace is None:
        return None
    durs = reduce.module_seconds(o.trace, "decode_step")
    return 1e3 * stats.median(durs) if durs else None
