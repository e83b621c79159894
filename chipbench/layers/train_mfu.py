"""Model FLOP/s utilization while a step runs: the FLOPs the samples of
one step need on one chip (chipbench/arith.py; recomputation does not
count) over the median device time of the train-step module, over the
chip's bf16 peak (chipbench/peaks.json). Times (1 - device idle share)
it is the end-to-end utilization, ``train_samples_per_s_per_chip`` x
FLOPs a sample / peak; taken from the trace because stopping the
profiler stalls a traced run's own window."""

from chipbench import arith, reduce, stats


def read(o):
    c = o.counters
    if o.trace is None or not c.get("flops_per_sample"):
        return None
    durs = reduce.module_seconds(o.trace, "train_step")
    if not durs:
        return None
    peak = arith.peaks(o.device_kind)["bf16_tflops"] * 1e12
    return 100.0 * c["per_chip_batch"] * c["flops_per_sample"] \
        / stats.median(durs) / peak
