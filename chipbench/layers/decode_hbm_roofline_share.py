"""Decode against the memory roofline: the bytes a decode step NEEDS
(the weights once + the KV of the tokens resident in the decode slots,
mean over the traced run's steps; chipbench/arith.py) over the chip's
bandwidth, over the mean device time of a decode-step module."""

from chipbench import arith, reduce


def read(o):
    c = o.counters
    if o.trace is None or not c.get("kv_live_tokens_mean"):
        return None
    durs = reduce.module_seconds(o.trace, "decode_step")
    if not durs:
        return None
    need = c["param_bytes"] + c["kv_live_tokens_mean"] * c["token_bytes"]
    bw = arith.peaks(o.device_kind)["hbm_gbytes_per_s"] * 1e9
    return 100.0 * (need / bw) / (sum(durs) / len(durs))
