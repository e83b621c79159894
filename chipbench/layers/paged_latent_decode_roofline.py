"""The fused paged latent-attention kernel (``paged_latent_decode``,
``ops/pallas_paged_latent_attention.py``) against its roofline: the
least time the chip could take for what the traced decode steps' calls
of it need (chipbench/arith_xing4.py: the resident tokens' latent rows
read once a layer-call as stored, scores and weighted sums for every
head; the larger of bytes / bandwidth and FLOPs / peak, a call) over the
device time under that kernel's name in the trace. The resident tokens
are the driver's count, sampled after every traced step (the sum of the
decoding slots' contexts). At 32 heads the kernel is bound by bytes
(58 FLOP a byte of row against the chip's 240). None without a trace,
without the kernel in it (a gather step, a CPU) or without the count."""

from chipbench import arith, arith_xing4 as need, reduce

KERNEL = "paged_latent_decode"


def read(o):
    live = o.counters.get("kv_live_tokens_mean")
    if o.trace is None or not live:
        return None
    calls = reduce.count_by_name(o.trace, KERNEL)
    took = reduce.seconds_by_name(o.trace, KERNEL)
    if not calls or took <= 0:
        return None
    call = need.paged_latent_decode_need(o.cell.config, live)
    least = arith.roofline_seconds(call["flops"], call["bytes"],
                                   arith.peaks(o.device_kind))["seconds"]
    return 100.0 * calls * least / took
