"""The three flash kernels against their roofline: the least time the
chip could take for the FLOPs and bytes they need (chipbench/arith.py:
the larger of FLOPs/peak and bytes/bandwidth, summed over the kernels)
over their device time in the trace. Which peak bounds each kernel is in
``bounds()``; at head size 64 and 512 tokens all three are compute-bound.
"""

from chipbench import arith, reduce

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _need(o):
    c = o.counters
    return arith.flash_flops_bytes(c["per_chip_batch"], c["heads"],
                                   c["seq_len"], c["head_dim"])


def bounds(o) -> dict:
    peak = arith.peaks(o.device_kind)
    return {k: arith.roofline_seconds(v["flops"], v["bytes"], peak)["bound"]
            for k, v in _need(o).items()}


def read(o):
    if o.trace is None or "head_dim" not in o.counters:
        return None
    peak = arith.peaks(o.device_kind)
    need = _need(o)
    least = took = 0.0
    for k in KERNELS:
        calls = reduce.count_by_name(o.trace, k)
        if not calls:
            return None
        least += calls * arith.roofline_seconds(
            need[k]["flops"], need[k]["bytes"], peak)["seconds"]
        took += reduce.seconds_by_name(o.trace, k)
    return 100.0 * least / took if took > 0 else None
