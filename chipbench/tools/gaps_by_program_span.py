"""Device-idle seconds of a kept traced run by the PROGRAM's host span.

    python3 -m chipbench.tools.gaps_by_program_span <keep dir or .xplane.pb>

The program's ``obs`` spans are ``jax.profiler.TraceAnnotation``s named
``hstd/<span>`` (``obs/core.py``), so a kept trace holds them on the
host plane beside the benchmark's ``chipbench/*`` spans, on the device
operations' clock. ``breakdown.idle_gaps`` names the benchmark's span
that covers the middle of each idle gap; this names the program's
innermost one under it, by the same rule, and prints each program
span's own seconds and self seconds (its time less its children's).
A hand tool: it prints nothing the driver reads.
"""

from __future__ import annotations

import os
import sys

from chipbench import reduce

PROGRAM_PREFIX = "hstd/"


def host_spans(path: str, prefix: str = PROGRAM_PREFIX) -> list:
    """``[(thread line, name, start_s, end_s), ...]`` of the host
    plane's events whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = ev.start_ns * 1e-9
                    out.append((i, ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def _innermost(spans: list, t: float):
    cover = [sp for sp in spans if sp[2] <= t < sp[3]]
    return min(cover, key=lambda sp: sp[3] - sp[2])[1] if cover else None


def idle_by_span(trace, spans: list, device: int = 0) -> list:
    """``[[benchmark span, program span, idle seconds], ...]``, longest
    first: each idle gap on ``device`` goes to the innermost benchmark
    span and the innermost program span that cover its middle."""
    dev = [(o.start_s, o.end_s) for o in trace.ops if o.device == device]
    notes = [(-1, a.name, a.start_s, a.end_s) for a in trace.annotations]
    total: dict = {}
    for s, e in reduce.gaps(dev):
        mid = 0.5 * (s + e)
        key = (_innermost(notes, mid) or "unannotated",
               _innermost(spans, mid) or "no program span")
        total[key] = total.get(key, 0.0) + (e - s)
    return [[b, p, v] for (b, p), v in
            sorted(total.items(), key=lambda kv: -kv[1])]


def own_and_self_seconds(spans: list) -> list:
    """``[[name, count, seconds, self seconds], ...]`` by name, longest
    first. A span's self seconds are its own less what the spans nested
    in it on its thread cover."""
    total: dict = {}
    by_line: dict = {}
    for sp in spans:
        by_line.setdefault(sp[0], []).append(sp)
    for line in by_line.values():
        line.sort(key=lambda sp: (sp[2], -sp[3]))
        for i, (_, name, s, e) in enumerate(line):
            inner = []
            for _, _, cs, ce in line[i + 1:]:
                if cs >= e:
                    break
                inner.append((cs, min(ce, e)))
            row = total.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += e - s
            row[2] += (e - s) - reduce.length(inner)
    return [[n, c, t, own] for n, (c, t, own) in
            sorted(total.items(), key=lambda kv: -kv[1][1])]


def report(path: str) -> str:
    if os.path.isdir(path):
        found = reduce.find_xplane(path)
        if found is None:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found
    trace = reduce.load_trace(path)
    spans = host_spans(path)
    rows = idle_by_span(trace, spans)
    idle = sum(v for _, _, v in rows)
    out = [f"{path}",
           f"device busy {reduce.busy_seconds(trace):.6f} s of "
           f"{reduce.window_seconds(trace):.6f} s traced; idle "
           f"{idle:.6f} s; {len(spans)} program spans",
           "", "idle s      benchmark span            program span"]
    for bench, prog, secs in rows:
        out.append(f"{secs:10.6f}  {bench:<24}  {prog}")
    out.append("")
    for bench in sorted({b for b, _, _ in rows}):
        of = sum(v for b, _, v in rows if b == bench)
        named = sum(v for b, p, v in rows
                    if b == bench and p.startswith(PROGRAM_PREFIX))
        out.append(f"of {of:.6f} s idle under {bench}: {named:.6f} s "
                   f"({100.0 * named / of:.1f}%) under a program span")
    out += ["", "seconds     self s      count  program span"]
    for name, count, secs, own in own_and_self_seconds(spans):
        out.append(f"{secs:10.6f}  {own:10.6f}  {count:5d}  {name}")
    return "\n".join(out)


if __name__ == "__main__":
    print(report(sys.argv[1]))
