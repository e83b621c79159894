"""Milliseconds of host work BEFORE the dispatches of one engine
iteration that decoded (admission, capacity math, building the tables,
positions and sampling rows): the median ``stage_s`` over such
``iteration_ledger`` lines. Source: as ``engine_host_ms_per_step``."""

from chipbench import stats
from chipbench.layers.engine_host_ms_per_step import ledger_lines


def read(o):
    stage = [e["stage_s"] for e in ledger_lines(o, True)]
    return 1e3 * stats.median(stage) if stage else None
