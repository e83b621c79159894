"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.
The arithmetic works on plain ``(start, end)`` intervals so that it can
be checked on hand-made ones; only :func:`load_trace` knows the file.

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO operation (a Pallas kernel appears under its ``kernel_name``) and
whose line ``XLA Modules`` has one event per executed program
(``jit_<function>(<fingerprint>)``); host threads are lines of the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear under
their own names. All on one clock, in nanoseconds. An operation's event
is named by its whole HLO line (``%fusion.12 = f32[16,512]{...}
fusion(...)``): :func:`parse_op` takes the operation's own name, so that a
kernel is never confused with an operation that merely reads its result,
and its result type, by which operations of all layers group.
A CPU run has no
device plane: its operations are the host events that carry an
``hlo_op`` stat, and they stand in for device 0 in the rehearsal.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Iterable, NamedTuple, Optional

COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all", "allreduce",
                      "allgather", "psum")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "chipbench/"


class Op(NamedTuple):
    device: int
    name: str          # the operation's own name (`flash_fwd.1`), the
    start_s: float     # program's (`jit__decode_step(123)`) or the span's
    end_s: float
    group: str = ""    # operations: name without numbering + result type


class Trace(NamedTuple):
    ops: list           # Op: operations that ran on a device
    modules: list       # Op: whole programs that ran on a device
    annotations: list   # Op (device -1): the benchmark's host spans


# -- interval arithmetic ----------------------------------------------------

def merge(intervals: Iterable[tuple]) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[tuple]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Iterable[tuple], b: Iterable[tuple]) -> list:
    """The part of union(a) that union(b) does not cover."""
    out = []
    cover = merge(b)
    for s, e in merge(a):
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals: Iterable[tuple], window: Optional[tuple] = None) -> list:
    """The idle gaps between the merged intervals (inside ``window``)."""
    m = merge(intervals)
    if not m:
        return []
    lo, hi = window if window else (m[0][0], m[-1][1])
    return subtract([(lo, hi)], m)


# -- the trace ----------------------------------------------------------------

def is_collective(name: str) -> bool:
    low = name.lower()
    return any(mark in low for mark in COLLECTIVE_MARKERS)


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def _base(name: str) -> str:
    # `fusion.123` -> `fusion`: the numbering is the compiler's, not ours
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def parse_op(text: str) -> tuple:
    """``(name, group)`` of an operation's event. ``%fusion.12 =
    (f32[16,512]{1,0:T(8,128)}, ...) fusion(...)`` gives the name
    ``fusion.12`` and the group ``fusion (f32[16,512], ...)``: the name
    without the compiler's numbering, and the result type without
    layouts. A plain name (a CPU trace) is its own group."""
    if not text.startswith("%") or " = " not in text:
        return text, _base(text)
    name, rest = text[1:].split(" = ", 1)
    m = _OPCODE.search(" " + rest)
    result = rest[:m.start()].strip() if m else ""
    result = _LAYOUT.sub("", result)
    if len(result) > 72:
        result = result[:69] + "..."
    return name, (_base(name) + " " + result).strip()


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_trace(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, notes = [], [], []
    device_planes = [p for p in data.planes
                     if p.name.startswith("/device:TPU:")]
    for plane in device_planes:
        dev = int(plane.name.rsplit(":", 1)[1])
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if line.name == OPS_LINE:
                    name, group = parse_op(ev.name)
                    ops.append(Op(dev, name, s, e, group))
                else:
                    modules.append(Op(dev, ev.name, s, e))
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if ev.name.startswith(ANNOTATION_PREFIX):
                    notes.append(Op(-1, ev.name, s, e))
                elif not device_planes and ev.duration_ns > 0:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        ops.append(Op(0, ev.name, s, e, _base(ev.name)))
                        modules.append(Op(0, str(stats.get("hlo_module", "")),
                                          s, e))
    return Trace(ops, modules, notes)


def _by_device(ops: list) -> dict:
    out: dict = {}
    for op in ops:
        out.setdefault(op.device, []).append(op)
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of the
    operations' intervals, averaged over the devices in the trace."""
    per = [length((o.start_s, o.end_s) for o in dev_ops)
           for dev_ops in _by_device(trace.ops).values()]
    return sum(per) / len(per) if per else 0.0


def window_seconds(trace: Trace) -> float:
    """The span the trace covers on a device: from its first operation's
    start to its last operation's end, averaged over the devices. Busy
    and exposed-collective seconds are parts of this span, so a share of
    it never passes 100%."""
    per = [max(o.end_s for o in dev_ops) - min(o.start_s for o in dev_ops)
           for dev_ops in _by_device(trace.ops).values()]
    return sum(per) / len(per) if per else 0.0


def exposed_collective_seconds(trace: Trace) -> float:
    """Seconds a device spent in collective operations while no other
    operation ran on it, averaged over the devices."""
    per = []
    for dev_ops in _by_device(trace.ops).values():
        coll = [(o.start_s, o.end_s) for o in dev_ops if is_collective(o.name)]
        rest = [(o.start_s, o.end_s) for o in dev_ops
                if not is_collective(o.name)]
        per.append(length(subtract(coll, rest)))
    return sum(per) / len(per) if per else 0.0


def seconds_by_name(trace: Trace, base: str, device: int = 0) -> float:
    """Summed device seconds of the operations on ``device`` whose own
    name, without the compiler's numbering, is ``base``."""
    return sum(o.end_s - o.start_s for o in trace.ops
               if o.device == device and _base(o.name) == base)


def count_by_name(trace: Trace, base: str, device: int = 0) -> int:
    return sum(1 for o in trace.ops
               if o.device == device and _base(o.name) == base)


def module_seconds(trace: Trace, contains: str, device: int = 0) -> list:
    """Device seconds of each executed program on ``device`` whose name
    contains ``contains``."""
    return [o.end_s - o.start_s for o in trace.modules
            if o.device == device and contains in o.name]


def _module_of(trace: Trace, device: int):
    """A function from a time to the short name of the program that ran
    on ``device`` then (``decode_step`` of ``jit__decode_step(123)``)."""
    mods = sorted((m for m in trace.modules if m.device == device),
                  key=lambda m: m.start_s)
    starts = [m.start_s for m in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t > mods[i].end_s:
            return ""
        name = mods[i].name.split("(", 1)[0]
        return name[4:].lstrip("_") if name.startswith("jit_") else name

    return find


def top_ops(trace: Trace, n: int = 10, device: int = 0) -> list:
    """``[[name, seconds], ...]``: the groups of operations that took
    most device time, as ``<program>: <operation group>``."""
    module_of = _module_of(trace, device)
    total: dict = {}
    for o in trace.ops:
        if o.device == device:
            mod = module_of(o.start_s)
            k = f"{mod}: {o.group or o.name}" if mod else (o.group or o.name)
            total[k] = total.get(k, 0.0) + (o.end_s - o.start_s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10, device: int = 0) -> list:
    """``[[what the host was doing, seconds], ...]``: idle time on
    ``device`` by the benchmark's innermost host span that covers the
    middle of each gap (``unannotated`` where none does), summed per
    span name, longest first."""
    dev = [(o.start_s, o.end_s) for o in trace.ops if o.device == device]
    total: dict = {}
    for s, e in gaps(dev):
        mid = 0.5 * (s + e)
        cover = [a for a in trace.annotations if a.start_s <= mid < a.end_s]
        name = (min(cover, key=lambda a: a.end_s - a.start_s).name
                if cover else "unannotated")
        total[name] = total.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
