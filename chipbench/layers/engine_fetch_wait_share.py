"""Share of the serving loop's wall time in which the host only waits
for the device: the sum of ``fetch_wait_s`` over the sum of ``dur_s +
gap_s`` (an iteration and the caller's time before it: together they
tile the loop) of an engine's ``iteration_ledger`` lines. 100 less this
is what a faster device cannot shrink. A window of laps holds several
engines' runs (a lap's engine counts its iterations from 0): the share
is taken for each and the MEDIAN reported, as tokens per second is the
median lap's, because the caller's time of ONE lap of a traced run
holds the benchmark's own ``stop_trace`` (13 s of a 51 s window, my
chip run PR 25), which is no part of the loop. Source: as
``engine_host_ms_per_step``."""

from chipbench import stats
from chipbench.layers.engine_host_ms_per_step import ledger_lines


def engine_runs(lines: list) -> list:
    """``lines`` split where the iteration count starts again: one list
    for each engine that wrote them."""
    runs: list = []
    for e in lines:
        if not runs or e["iteration"] <= runs[-1][-1]["iteration"]:
            runs.append([])
        runs[-1].append(e)
    return runs


def read(o):
    shares = []
    for run in engine_runs(ledger_lines(o)):
        wall = sum(e["dur_s"] + e["gap_s"] for e in run)
        if wall > 0:
            shares.append(100.0 * sum(e["fetch_wait_s"] for e in run) / wall)
    return stats.median(shares)
