"""A decode step of a model with recurrent (linear-attention) layers
against the memory roofline: the bytes a step NEEDS
(chipbench/arith_olmo_hybrid.py: the layers' weights and the head once,
the K/V of the tokens resident in the step's slots, and each of those
slots' recurrent state and convolution tails read once and written once,
by the program's own counts on the ``iteration_ledger`` lines of the
traced iterations that dispatched a decode step) over the chip's
bandwidth, over the mean device time of a ``decode_step`` module. This is
the whole step's share: ``decode_hbm_roofline_share`` counts weights and
K/V only. None from a program whose ledger has no state counts."""

from chipbench import arith, arith_olmo_hybrid as need, reduce
from chipbench.layers.engine_host_ms_per_step import ledger_lines, traced


def decode_lines(o) -> list:
    """``(slots, resident tokens)`` of each traced iteration whose ledger
    line says it dispatched a decode step of a model with state: the rows
    whose state the iteration touched less its prefill rows, and the
    tokens the step attended."""
    return [(e["state_slots"] - e.get("prefill_chunks", 0),
             e["kv_tokens_resident"])
            for e in traced(o, ledger_lines(o))
            if e.get("state_slots") is not None
            and e.get("kv_tokens_resident")]


def read(o):
    steps = decode_lines(o)
    if o.trace is None or not steps:
        return None
    durs = reduce.module_seconds(o.trace, "decode_step")
    if not durs:
        return None
    n = len(steps)
    nbytes = need.decode_step_need_bytes(
        o.cell.config, slots=sum(s for s, _ in steps) / n,
        kv_tokens=sum(t for _, t in steps) / n)["total"]
    bw = arith.peaks(o.device_kind)["hbm_gbytes_per_s"] * 1e9
    return 100.0 * (nbytes / bw) / (sum(durs) / len(durs))
