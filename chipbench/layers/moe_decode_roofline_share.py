"""A decode step of a model with routed experts against the chip's
roofline: the larger of bytes / bandwidth and FLOPs / peak that the step
NEEDS (chipbench/arith_deepseek_v2.py: the weights every token passes and
the head once, the held experts that got a pair by the program's own
counts, the resident tokens' latent rows; absorbed attention and the
matmuls) over the mean device time of a ``decode_step`` module, over the
traced iterations. ``decode_hbm_roofline_share`` counts every weight
once, which a step that reads only the experts it routed to does not
need. None from a program whose ledger has no routed counts."""

from chipbench import arith, arith_deepseek_v2 as need, reduce
from chipbench.layers.engine_host_ms_per_step import ledger_lines, traced


def decode_lines(o) -> list:
    """The traced ``iteration_ledger`` lines that carry a decode step's
    routed counts."""
    return [e for e in traced(o, ledger_lines(o))
            if e.get("moe_experts_touched") is not None]


def read(o):
    lines = decode_lines(o)
    live = o.counters.get("kv_live_tokens_mean")
    if o.trace is None or not lines or not live:
        return None
    durs = reduce.module_seconds(o.trace, "decode_step")
    if not durs:
        return None
    n = len(lines)
    step = need.decode_step_need(
        o.cell.config,
        slots=sum(e["moe_decode_pairs"] for e in lines) / n / (
            o.cell.config["num_experts_per_tok"]
            * need.layer_counts(o.cell.config)[1]),
        live_tokens=live,
        experts_touched=sum(sum(e["moe_experts_touched"]) for e in lines) / n,
        pairs_held=sum(e["moe_decode_pairs_held"] for e in lines) / n)
    least = arith.roofline_seconds(step["flops"], step["bytes"],
                                   arith.peaks(o.device_kind))["seconds"]
    return 100.0 * least / (sum(durs) / len(durs))
