"""How many of the held experts a decode step reads: over the traced
decode steps' ``iteration_ledger`` lines, the held experts that got a
(token, expert) pair (``moe_experts_touched``, one count an expert layer)
over all the held experts of all the expert layers. With every expert on
the chip this share IS the routed part of the step's bytes: near 100 a
step reads all of them whatever the tokens are, and a gate that collapses
onto few experts shows here before it shows in tokens per second. A count
from the program's own routed counts; None without them."""

from chipbench import arith_deepseek_v2 as need
from chipbench.layers.moe_decode_roofline_share import decode_lines


def read(o):
    lines = decode_lines(o)
    cfg = o.cell.config
    held = need.layer_counts(cfg)[1] * cfg["n_routed_experts"]
    if not lines or held <= 0:
        return None
    touched = sum(sum(e["moe_experts_touched"]) for e in lines)
    return 100.0 * touched / (len(lines) * held)
