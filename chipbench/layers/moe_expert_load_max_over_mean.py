"""How unevenly a decode step's tokens fall on the held experts: the
busiest held expert's (token, expert) pairs over the mean over the held
experts, per expert layer of each traced decode step, the median over
steps and layers. 1 is even; grouped matmuls wait for the busiest. A
count from the program's own routed counts; None without them."""

from chipbench import stats
from chipbench.layers.moe_decode_roofline_share import decode_lines


def read(o):
    ratios = [mx / mean for e in decode_lines(o)
              for mx, mean in zip(e["moe_expert_load_max"],
                                  e["moe_expert_load_mean"]) if mean > 0]
    return stats.median(ratios) if ratios else None
