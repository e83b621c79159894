"""Prefill of a model with latent attention and routed experts against
the chip's peak: the FLOPs the REAL tokens of the traced prefill
dispatches need (chipbench/arith_deepseek_v2.py: attention projections,
shared experts, router, head, the pairs that landed on held experts,
expanded-form attention over ``prefill_keys_needed``) over the
``prefill_chunk`` modules' device seconds in the trace, over the peak.
Pad rows, pad tails and the keys of a bucket beyond the context are time
and no need. None from a program whose ledger has no routed counts."""

from chipbench import arith, arith_deepseek_v2 as need, reduce
from chipbench.layers.engine_host_ms_per_step import ledger_lines, traced


def read(o):
    lines = [e for e in traced(o, ledger_lines(o))
             if e.get("moe_pairs") is not None]
    if o.trace is None or not lines:
        return None
    took = sum(reduce.module_seconds(o.trace, "prefill_chunk"))
    cfg = o.cell.config
    fan = cfg["num_experts_per_tok"] * need.layer_counts(cfg)[1]
    tokens = sum(e["moe_pairs"] - e.get("moe_decode_pairs", 0)
                 for e in lines) / fan
    if took <= 0 or tokens <= 0:
        return None
    flops = need.prefill_need_flops(
        cfg, tokens=tokens,
        rows=sum(e["prefill_chunks"] for e in lines),
        keys_needed=sum(e["prefill_keys_needed"] for e in lines),
        pairs_held=sum(e["moe_pairs_held"] - e.get("moe_decode_pairs_held", 0)
                       for e in lines),
        chunk=int(cfg["deployment"]["prefill_chunk"]))
    peak = arith.peaks(o.device_kind)["bf16_tflops"] * 1e12
    return 100.0 * flops / took / peak
