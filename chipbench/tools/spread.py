"""Median and spread of each end-to-end metric over kept runs, per cell
and per set: what the bounds in ``BENCHMARK.json`` are set from.

    python3 -m chipbench.tools.spread <dir of *.trace0.seed<n>.json> [set size]

Runs are taken in order of seed and cut into sets of ``set size``
(default: all in one set); spread is the distance between the quartiles
over the median, as the driver computes it.
"""

from __future__ import annotations

import glob
import json
import os
import sys

from chipbench import stats


def main(directory: str, set_size: int = 0) -> None:
    runs: dict = {}
    for path in glob.glob(os.path.join(directory, "*.trace0.seed*.json")):
        with open(path) as f:
            line = json.load(f)
        runs.setdefault(line["workload"], []).append(line)
    for cell in sorted(runs):
        lines = sorted(runs[cell], key=lambda l: l["seed"])
        size = set_size or len(lines)
        sets = [lines[i:i + size] for i in range(0, len(lines), size)]
        for k, members in enumerate(sets):
            for name in members[0]["metrics"]:
                vals = [m["metrics"][name]["value"] for m in members]
                print(f"{cell} set {k} n={len(vals)} {name}: median "
                      f"{stats.median(vals):.4f} spread "
                      f"{100 * (stats.spread(vals) or 0):.2f}% values "
                      + " ".join(f"{v:.4f}" for v in vals))
            bad = [m["seed"] for m in members
                   if not m["correct"] or m["failed"]]
            if bad:
                print(f"{cell} set {k}: NOT CORRECT in seeds {bad}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 0)
