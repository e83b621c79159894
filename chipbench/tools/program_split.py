"""Device time of a cell's programs by the program's own modules, over
any iterations of a lap: the opening too, which no traced range holds.

    python3 -m chipbench.tools.program_split --workload <cell> \\
        [--iterations a b]... [--top n] [--seed n] [--keep dir]

A serving cell: builds and warms the cell's engine as ``kinds/serve.py``
does (its functions, imported), then for each ``--iterations a b`` (the
cell's own ``trace_lap_iterations`` without one; ``0 0`` is the opening;
the option may be given several times, a lap each) runs one lap from an
empty engine with the profiler over exactly those iterations. A training
cell: the cell's own traced run (``kinds/train.py::run``). Then, from
the trace and the ``program_map`` events the program wrote
(``chipbench/split.py``), for every program that ran, by name and key:
dispatches, ms a dispatch, the component table (``fwd`` | ``bwd`` apart
for a train step), and under each component its collapsed module paths
with the ``n`` largest operations (name, result type, ms a dispatch,
``mixed`` where a fusion spans components); a train step that the
session's start or stop cut is left out. ``--keep`` leaves the raw
traces and the printed tables (``program_split.<cell>.txt``) there.
A hand tool: the driver reads nothing of it.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import os
import sys
import tempfile
import time

from chipbench import device, reduce, spec, split, stats


def _parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench.tools.program_split",
                                 description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--iterations", type=int, nargs=2, action="append",
                    metavar=("A", "B"))
    ap.add_argument("--top", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="a training cell's window")
    ap.add_argument("--keep", default=None)
    return ap.parse_args(argv)


def tables(found: list, top: int = 3) -> list:
    """The printed lines for ``found`` (``split.ModuleSplit``s): one
    section a program, told apart by the map that covered it."""
    by_program: dict = {}
    for s in found:
        m = s.map
        ident = (split.short_name(s.module.name),
                 "" if m is None else str(m.get("key")))
        by_program.setdefault(ident, []).append(s)
    lines = []
    for (name, key), runs in sorted(by_program.items()):
        n = len(runs)
        took = sum(s.module.end_s - s.module.start_s for s in runs)
        busy = sum(s.busy_s for s in runs)
        m = runs[0].map
        lines.append(f"== {name} {key}: {n} dispatch(es), "
                     f"{1e3 * took / n:.3f} ms a dispatch "
                     f"(busy {1e3 * busy / n:.3f})"
                     + ("" if m is None else
                        f"; map resolve_s {m.get('resolve_s')} from_cache "
                        f"{m.get('from_cache')} refined_by "
                        f"{m.get('refined_by')}"))
        # component -> pass -> path -> operation group -> [ms, mixed]
        tree: dict = {}
        for s in runs:
            scopes = [] if s.map is None else s.map["scopes"]
            for r in s.rows:
                path, which = (scopes[r.scope] if 0 <= r.scope < len(scopes)
                               else ("", ""))
                leaf = tree.setdefault((r.component, which), {}) \
                    .setdefault(path, {}) \
                    .setdefault(r.op.group or r.op.name, [0.0, False])
                leaf[0] += 1e3 * r.self_s / n
                leaf[1] = leaf[1] or r.mixed
        total = sum(v[0] for paths in tree.values()
                    for ops in paths.values() for v in ops.values())
        for (component, which), paths in sorted(
                tree.items(), key=lambda kv: -sum(
                    v[0] for ops in kv[1].values() for v in ops.values())):
            ms = sum(v[0] for ops in paths.values() for v in ops.values())
            mixed = sum(v[0] for ops in paths.values()
                        for v in ops.values() if v[1])
            lines.append(f"  {component + (' ' + which if which else ''):<16}"
                         f"{ms:10.3f} ms {100 * ms / total:5.1f}%"
                         f"  (mixed fusions {mixed:.3f} ms)")
            for path, ops in sorted(paths.items(), key=lambda kv: -sum(
                    v[0] for v in kv[1].values()))[:max(top * 3, 6)]:
                lines.append(f"    {sum(v[0] for v in ops.values()):10.3f}"
                             f"  {path or '(no module path)'}")
                for group, (op_ms, op_mixed) in sorted(
                        ops.items(), key=lambda kv: -kv[1][0])[:top]:
                    lines.append(f"      {op_ms:10.3f}    {group}"
                                 + ("  [mixed]" if op_mixed else ""))
        lines.append(f"  {'sum':<16}{total:10.3f} ms a dispatch")
    return lines


def _whole_steps(found: list) -> list:
    """``found`` without the train steps that a session driven by time
    cut at its ends: those shorter than 0.98 of the median step."""
    steps = [s.module.end_s - s.module.start_s for s in found
             if "train_step" in s.module.name]
    if len(steps) < 3:
        return found
    floor = 0.98 * stats.median(steps)
    return [s for s in found if "train_step" not in s.module.name
            or s.module.end_s - s.module.start_s >= floor]


def _serve_laps(cell, seed: int, ranges: list, keep) -> list:
    """``[(range, trace dir)]`` and the events: one lap a range, each
    under a profiler session of its own, as a traced run's last lap."""
    import jax

    from chipbench.kinds import serve
    from chipbench.loadgen import make_requests
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    cfg, traffic = cell.config, cell.traffic
    dep, loop = cfg["deployment"], traffic["loop"]
    family = importlib.import_module("chipbench.families." + cfg["family"])
    model, params = family.build(cfg, seed, dtype=dep["dtype"])
    jax.block_until_ready(params)
    engine = serve.build_engine(model, params, dep)
    engine.warmup()
    jax.block_until_ready(jax.live_arrays())
    del engine
    gc.collect()
    plans = make_requests(traffic, seed, cfg["vocab_size"], 51.0)
    laps = loop.get("laps") or {}
    # a lap's opening once, untraced: what a new engine's first
    # iterations compile or load beyond warmup() they do here
    dry = serve.Driver(loop, float("inf"))
    dry.serve(serve.build_engine(model, params, dep), plans,
              int(laps.get("dry_steps", 4)))
    jax.block_until_ready(jax.live_arrays())
    del dry
    gc.collect()
    tel_dir = tempfile.mkdtemp(prefix="chipbench_obs_")
    obs.configure(out_dir=tel_dir, enabled=True)
    traced = []
    for n, (a, b) in enumerate(ranges):
        out_dir = (os.path.join(keep, f"iterations_{a}_{b}") if keep
                   else None)
        session = device.TraceSession(out_dir=out_dir, iterations=(a, b))
        drv = serve.Driver(loop, float("inf"), session)
        drv.serve(serve.build_engine(model, params, dep), make_requests(
            traffic, seed, cfg["vocab_size"], 51.0, lap=n + 1), b + 1)
        jax.block_until_ready(jax.live_arrays())
        session.stop()
        if not session.complete:
            print(f"program_split: iterations {a}..{b} were not all "
                  "recorded", file=sys.stderr)
        traced.append(((a, b), session.dir))
        del drv
        gc.collect()
    obs.shutdown()
    return traced, device.read_events(tel_dir)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = _parse(argv)
    try:
        rehearsal = device.asked_for_cpu()
        cell = spec.load_cell(args.workload, rehearsal)
        devs = device.devices_for(cell.chips)
    except device.NoChipError as e:
        print(f"program_split: {e}", file=sys.stderr)
        return 3
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
        enable_compilation_cache,
    )

    if not rehearsal:
        enable_compilation_cache()
    if cell.traffic["kind"] == "serve":
        ranges = [tuple(r) for r in args.iterations or
                  [cell.traffic["trace_lap_iterations"]]]
        traced, events = _serve_laps(cell, args.seed, ranges, args.keep)
    else:
        from chipbench.kinds import train

        out = train.run(cell, args.seed, args.seconds, True, devs,
                        device.CompileCounter().install(), t_start,
                        keep_dir=args.keep)
        traced, events = [(("window",), out["session"].dir)], out["events"]
    maps = split.program_maps(events)
    lines = [f"# {cell.name} on {devs[0].device_kind}: "
             f"{len(maps)} program map(s), "
             f"{sum(len(str(m)) for m in maps)} characters of events"]
    for m in maps:
        lines.append(f"#   {m.get('program')} {m.get('key')}: "
                     f"{len(m['ops'])} operations, resolve_s "
                     f"{m.get('resolve_s')}, from_cache {m.get('from_cache')}"
                     f", refined_by {m.get('refined_by')}")
    for what, trace_dir in traced:
        xplane = reduce.find_xplane(trace_dir)
        lines.append(f"# traced {' '.join(str(x) for x in what)}")
        if xplane is None:
            lines.append("  no trace was written")
            continue
        trace = reduce.load_trace(xplane)
        found = split.split_modules(trace, maps, "")
        if cell.traffic["kind"] != "serve":
            found = _whole_steps(found)
        busy = reduce.busy_seconds(trace)
        covered = sum(v for s in found for k, v in s.seconds().items()
                      if k not in (split.UNMAPPED, "other"))
        lines.append(f"# busy {busy:.6f} s of "
                     f"{reduce.window_seconds(trace):.6f}"
                     f"; in the maps and not `other`: "
                     f"{100 * covered / busy if busy else 0:.2f}%")
        lines += tables(found, args.top)
        if not args.keep:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    text = "\n".join(lines)
    print(text, flush=True)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        with open(os.path.join(args.keep,
                               f"program_split.{cell.name}.txt"), "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
