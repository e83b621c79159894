"""Run one cell of ``BENCHMARK.json`` once and print one JSON line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no supervisor and no child. It fails, it does not fall
back, where the default JAX backend is not a TPU, unless
``JAX_PLATFORMS`` names ``cpu`` first (the rehearsal at tiny sizes, whose
line says ``"platform": "cpu"``). It refuses to start while any
``HSTD_*`` variable is set, so that no knob of the program leaks into a
number.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def _parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the raw trace in, and the line "
                         "with the run's notes beside it")
    return ap.parse_args(argv)


def read_layer(name: str, observed):
    """The per-layer metric ``name`` by its own reader,
    ``chipbench/layers/<name>.py``; None where it finds nothing."""
    mod = importlib.import_module("chipbench.layers." + name)
    return mod.read(observed)


def main(argv=None) -> int:
    args = _parse(argv)
    knobs = sorted(k for k in os.environ if k.startswith("HSTD_"))
    if knobs:
        print(f"chipbench: refusing to run with {knobs} set: a knob of the "
              "program must not leak into a number", file=sys.stderr)
        return 2

    import jax

    from chipbench import device, reduce, spec

    try:
        rehearsal = device.asked_for_cpu()
        cell = spec.load_cell(args.workload, rehearsal)
        devs = device.devices_for(cell.chips)
    except device.NoChipError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    seconds = float(args.seconds if args.seconds is not None
                    else cell.run_seconds)

    # the program's one rule for the compile cache: the directory that
    # JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
        enable_compilation_cache,
    )

    if not rehearsal:
        enable_compilation_cache()
        # every program, however quick to compile, is found again by the
        # cell's next run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = device.CompileCounter().install()

    kind = importlib.import_module("chipbench.kinds." + cell.traffic["kind"])
    out = kind.run(cell, args.seed, seconds, bool(args.trace), devs,
                   compiles, _T_START, keep_dir=args.keep)

    facts = device.device_facts(devs)
    # the line holds the contract's keys and no other; what else a run
    # knows goes to the --keep file
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {},
            "device": {k: facts[k] for k in
                       ("platform", "kind", "count", "memory_peak_bytes",
                        "jax_version")}}
    if not args.trace:
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {"value": out["values"][m["name"]],
                                          "unit": m["unit"]}
    else:
        trace_dir = out["session"].dir
        xplane = reduce.find_xplane(trace_dir)
        trace = reduce.load_trace(xplane) if xplane else None
        observed = device.Observed(
            cell=cell, device_kind=facts["kind"], chips=cell.chips,
            window_s=out["window_s"], values=out["values"],
            counters=out["counters"], events=out["events"], trace=trace,
            trace_window_s=reduce.window_seconds(trace) if trace else 0.0,
            memory_peak_bytes=facts["memory_peak_bytes"],
            memory_limit_bytes=facts["memory_limit_bytes"],
            compiles_in_window=compiles.in_window)
        for m in cell.per_layer:
            try:
                value = read_layer(m["name"], observed)
            except LookupError:
                if not rehearsal:   # the CPU has no entry in the peaks
                    raise
                value = None
            if value is not None:
                line["metrics"][m["name"]] = {"value": float(value),
                                              "unit": m["unit"]}
        if trace is not None:
            line["device"]["busy_s"] = reduce.busy_seconds(trace)
            line["device"]["window_s"] = observed.trace_window_s
            line["breakdown"] = {"device_ops": reduce.top_ops(trace),
                                 "idle_gaps": reduce.idle_gaps(trace)}
        if not args.keep:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    if args.keep:
        kept = dict(line, workload=cell.name, seed=args.seed,
                    window_s=out["window_s"],
                    compiles_in_window=compiles.in_window,
                    end_to_end={m["name"]: out["values"][m["name"]]
                                for m in cell.end_to_end},
                    notes={k: v for k, v in out["counters"].items()
                           if not isinstance(v, list) or len(v) <= 16})
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep, f"{cell.name}.trace{args.trace}"
                               f".seed{args.seed}.json"), "w") as f:
            json.dump(kept, f, indent=1, default=str)
    print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
