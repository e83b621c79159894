"""Longest device time of one prefill dispatch (a ``prefill_chunk``
module) in the trace: while it runs no decoding request gets a token,
so it is the long gap between tokens. Source: trace."""

from chipbench import reduce


def read(o):
    if o.trace is None:
        return None
    durs = reduce.module_seconds(o.trace, "prefill_chunk")
    return 1e3 * max(durs) if durs else None
