"""Prefill of a model with recurrent (linear-attention) layers against
the chip's peak: the FLOPs the REAL tokens of the traced prefill
dispatches need (chipbench/arith_olmo_hybrid.py: projections and MLP a
token, one head row a chunk row, causal full attention over
``prefill_keys_needed``, the recurrence's chunked form at 64) over the
``prefill_chunk`` modules' device seconds in the trace, over the peak. Pad
rows, pad tails and the keys of a bucket beyond the context are time and
no need. None from a program whose ledger has no state counts."""

from chipbench import arith, arith_olmo_hybrid as need, reduce
from chipbench.layers.engine_host_ms_per_step import ledger_lines, traced


def read(o):
    lines = [e for e in traced(o, ledger_lines(o))
             if e.get("prefill_tokens") and e.get("state_slots") is not None]
    if o.trace is None or not lines:
        return None
    took = sum(reduce.module_seconds(o.trace, "prefill_chunk"))
    if took <= 0:
        return None
    cfg = o.cell.config
    flops = need.prefill_need_flops(
        cfg, tokens=sum(e["prefill_tokens"] for e in lines),
        rows=sum(e["prefill_chunks"] for e in lines),
        keys_needed=sum(e["prefill_keys_needed"] for e in lines),
        chunk=int(cfg["deployment"]["prefill_chunk"]))
    peak = arith.peaks(o.device_kind)["bf16_tflops"] * 1e12
    return 100.0 * flops / took / peak
