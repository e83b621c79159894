"""Reads ``BENCHMARK.json`` and the data files a cell names.

Everything that belongs to one configuration or one traffic mix is a
file of its own, found by the name in ``BENCHMARK.json``; nothing here
knows a cell by name.
"""

from __future__ import annotations

import copy
import json
import os
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


class Cell(NamedTuple):
    name: str
    chips: int
    config_name: str
    config: dict       # the configuration file as run (rehearsal applied)
    traffic_name: str
    traffic: dict      # the traffic file as run (rehearsal applied)
    end_to_end: list   # metric entries this cell reports, trace 0
    per_layer: list    # metric entries this cell reports, trace 1
    run_seconds: int


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def merge(base: dict, over: dict) -> dict:
    """``over`` laid on ``base``; nested dicts merge key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def _reported(metrics: list, cell: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, rehearsal: bool, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files.
    ``rehearsal`` (the CPU was asked for by name) lays each file's
    ``rehearsal`` group over it: tiny sizes, same code path."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    if rehearsal:
        config = merge(config, config.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=_reported(bench["end_to_end"], name),
                per_layer=_reported(bench["per_layer"], name),
                run_seconds=int(bench["run_seconds"]))
