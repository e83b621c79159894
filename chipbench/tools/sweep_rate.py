"""Find the knee of an open-loop cell once: the highest arrival rate the
system sustains with no growing backlog. One process, one model, a new
engine per rate; prints one JSON line per rate. The cell's traffic file
then holds the NUMBER (the benchmark never searches for a rate).

    python3 -m chipbench.tools.sweep_rate <workload> <traffic file> <seconds> <rate> [<rate> ...]

``workload`` is a cell of ``BENCHMARK.json`` whose configuration and
deployment serve the mix; ``traffic file`` is the open-loop mix, which
need not be a cell yet (its ``rate_per_s`` is overridden).

The last line names the knee by this rule: the highest swept rate such
that it and every lower one left no request unfinished after the grace,
held fewer requests in the system at the close than the engine has slots,
and did not grow its backlog over the window's last third (requests in
the system after each step: mean over the last third of the window at
most 1.3 x the mean over the middle third + 1). Give it windows several
request lifetimes long and some hundreds of requests a rate: PR 22's
sweep of a document mix (40 s windows, 9-28 requests, a request living
15-20 s) could not place a knee.
"""

from __future__ import annotations

import copy
import gc
import importlib
import json
import sys
import time


def main(workload: str, traffic_file: str, seconds: float,
         rates: list) -> None:
    import jax

    from chipbench import device, spec, stats
    from chipbench.kinds.serve import Driver, build_engine
    from chipbench.loadgen import make_requests
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
        enable_compilation_cache,
    )

    rehearsal = device.asked_for_cpu()
    cell = spec.load_cell(workload, rehearsal)
    device.devices_for(cell.chips)
    if not rehearsal:
        enable_compilation_cache()
    mix = spec.load_json(traffic_file)
    if rehearsal:
        mix = spec.merge(mix, mix.get("rehearsal", {}))
    cfg, dep = cell.config, cell.config["deployment"]
    family = importlib.import_module("chipbench.families." + cfg["family"])
    model, params = family.build(cfg, 0, dtype=dep["dtype"])
    knee = None
    sustained_so_far = True
    for i, rate in enumerate(sorted(rates)):
        traffic = copy.deepcopy(mix)
        traffic["loop"]["rate_per_s"] = rate
        engine = build_engine(model, params, dep)
        engine.warmup()
        plans = make_requests(traffic, 100 + i, cfg["vocab_size"], seconds)
        drv = Driver(traffic["loop"], seconds)
        window_s = drv.run(engine, plans)
        in_system = len(drv.live)
        drv.drain(float(traffic["loop"]["drain_grace_s"]))
        end = time.perf_counter()
        ttft = [((t.first_t or end) - t.due_t) for t in drv.tracks]
        thirds = []
        for k in range(3):
            part = [b for at, b in drv.backlog
                    if k * seconds / 3 <= at < (k + 1) * seconds / 3]
            thirds.append(sum(part) / len(part) if part else 0.0)
        unfinished = sum(1 for t in drv.tracks if t.done_t is None)
        sustained = (unfinished == 0 and in_system < engine.num_slots
                     and thirds[2] <= 1.3 * thirds[1] + 1)
        sustained_so_far = sustained_so_far and sustained
        if sustained_so_far:
            knee = rate
        print(json.dumps({
            "rate_per_s": rate, "sustained": sustained,
            "due": len(drv.tracks),
            "finished_in_window": sum(
                1 for t in drv.tracks
                if t.done_t is not None and t.done_t - drv.t0 <= window_s),
            "in_system_at_close": in_system,
            "in_system_mean_by_third": thirds,
            "unfinished_after_grace": sum(
                1 for t in drv.tracks if t.done_t is None),
            "ttft_p50_ms": 1e3 * (stats.median(ttft) or 0),
            "ttft_p90_ms": 1e3 * (stats.percentile(ttft, 90) or 0),
            "itl_p50_ms": 1e3 * (stats.median([g for _, g in drv.gaps]) or 0),
            "itl_p99_ms": 1e3 * (stats.percentile(
                [g for _, g in drv.gaps], 99) or 0),
            "out_tok_per_s": sum(1 for s in drv.token_stamps
                                 if s <= window_s) / window_s,
            "preemptions": int(engine.sched.n_preemptions),
            "lateness": stats.lateness([t.due_t for t in drv.tracks],
                                       [t.sent_t for t in drv.tracks]),
            "peak_bytes": max((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0) for d in jax.devices()),
        }), flush=True)
        del engine, drv
        gc.collect()
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], float(sys.argv[3]),
         [float(r) for r in sys.argv[4:]])
