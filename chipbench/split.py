"""Device time by the program's own modules: the trace's operations
joined with the ``program_map`` events the program writes about itself
(``obs/programs.py``: for every instruction of a compiled hot-path
program the module path it came from and its component, ``mixer`` |
``ffn`` | ``residual`` | ``head`` | ``embed`` | ``cache`` | ``optimizer`` |
``collective`` | ``other``).

A trace names an operation by its HLO line and a program by its jitted
function; several programs share a function's name (a prefill dispatch
of one row and of four, a decode step at two buckets) and differ in
their shapes, so in their instructions' names and result types. For
each executed module the map is chosen whose table covers most of the
operations that ran inside the module's interval; no runtime id is
needed. An operation's time is its SELF time, its interval less the
operations nested in it (a ``while`` and its body's operations are both
events), so the components of a module, with ``unmapped`` for what no
table holds, sum to the module's busy seconds.

A helper of the per-layer readers and of ``tools/program_split``; a
program without ``program_map`` events (any before PR 37) gives None.
"""

from __future__ import annotations

import bisect
from typing import NamedTuple, Optional

from chipbench import reduce, stats

UNMAPPED = "unmapped"


class Row(NamedTuple):
    op: reduce.Op
    self_s: float
    component: str      # a component of the map, or UNMAPPED
    scope: int          # index into the map's `scopes`; -1 without one
    mixed: bool         # a fusion whose parts lie under several components


class ModuleSplit(NamedTuple):
    module: reduce.Op
    map: Optional[dict]     # the program_map event chosen; None: no table
    rows: list              # Row, one an operation inside the module
    busy_s: float           # union of those operations' intervals

    def seconds(self) -> dict:
        out: dict = {}
        for r in self.rows:
            out[r.component] = out.get(r.component, 0.0) + r.self_s
        return out


def program_maps(events: list) -> list:
    return [e for e in events if e.get("type") == "program_map"
            and isinstance(e.get("ops"), dict)]


def self_seconds(ops: list) -> list:
    """Each operation's own seconds: its interval less whatever started
    inside it. ``ops`` sorted by ``(start, -end)``; every moment in which
    some operation ran goes to the one that started last, so the result
    sums to the union of the intervals whatever their nesting."""
    out = [0.0] * len(ops)
    stack: list = []
    now = 0.0

    def advance(to: float) -> None:
        nonlocal now
        while stack:
            top = stack[-1]
            end = ops[top].end_s
            if end <= to:
                if end > now:
                    out[top] += end - now
                    now = end
                stack.pop()
            else:
                if to > now:
                    out[top] += to - now
                    now = to
                return
        now = max(now, to)

    for i, op in enumerate(ops):
        advance(op.start_s)
        stack.append(i)
    advance(float("inf"))
    return out


def _expected(m: dict) -> dict:
    """Instruction name -> the group a TPU trace gives its event."""
    return {name: (reduce._base(name) + " " + row[2]).strip()
            for name, row in m["ops"].items()}


def _matches(op: reduce.Op, want: Optional[str]) -> bool:
    # a CPU trace's events carry no result type: the name alone
    return want is not None and (" " not in op.group or op.group == want)


def split_modules(trace: reduce.Trace, maps: list, program: str,
                  device: int = 0) -> list:
    """One :class:`ModuleSplit` for each executed module on ``device``
    whose short name contains ``program``, in the order they ran."""
    mods = sorted((m for m in trace.modules if m.device == device),
                  key=lambda m: m.start_s)
    mod_starts = [m.start_s for m in mods]
    inside_of: list = [[] for _ in mods]
    # an operation belongs to the module that was running when it began
    for op in sorted((o for o in trace.ops if o.device == device),
                     key=lambda o: (o.start_s, -o.end_s)):
        i = bisect.bisect_right(mod_starts, op.start_s) - 1
        if i >= 0 and op.start_s <= mods[i].end_s:
            inside_of[i].append(op)
    expected = [(m, _expected(m)) for m in maps]
    out = []
    for mod, inside in zip(mods, inside_of):
        short = short_name(mod.name)
        if program not in short:
            continue
        best, best_want, best_n = None, {}, 0
        for m, want in expected:
            if m.get("program") != short:
                continue
            n = sum(1 for o in inside if _matches(o, want.get(o.name)))
            if n > best_n:
                best, best_want, best_n = m, want, n
        rows = []
        for op, own in zip(inside, self_seconds(inside)):
            row = best["ops"].get(op.name) if best is not None and _matches(
                op, best_want.get(op.name)) else None
            if row is None:
                rows.append(Row(op, own, UNMAPPED, -1, False))
            else:
                rows.append(Row(op, own, str(row[1]), int(row[0]),
                                bool(row[3])))
        out.append(ModuleSplit(mod, best, rows, reduce.length(
            (o.start_s, o.end_s) for o in inside)))
    return out


def short_name(module_name: str) -> str:
    """``prefill_chunk`` of ``jit__prefill_chunk(123)``, as
    ``reduce._module_of`` shortens it."""
    name = module_name.split("(", 1)[0]
    return name[4:].lstrip("_") if name.startswith("jit_") else name


# the last (trace, events) split, and its result
_memo: list = [None, None, None]


def splits(o, program: str) -> Optional[list]:
    """:func:`split_modules` of a run's trace and events; None without a
    trace, without a map, or without such a module in the trace. The
    readers of one run share one pass over its trace."""
    if o.trace is None:
        return None
    if _memo[0] is not o.trace or _memo[1] is not o.events:
        maps = program_maps(o.events)
        _memo[:] = [o.trace, o.events,
                    split_modules(o.trace, maps, "") if maps else None]
    if _memo[2] is None:
        return None
    return [s for s in _memo[2]
            if program in short_name(s.module.name)] or None


def component_seconds(o, program: str) -> Optional[dict]:
    """Device seconds by component (and ``unmapped``) over every executed
    module whose short name contains ``program``."""
    found = splits(o, program)
    if found is None:
        return None
    out: dict = {}
    for s in found:
        for k, v in s.seconds().items():
            out[k] = out.get(k, 0.0) + v
    return out


# -- what the eleven readers read -------------------------------------------

def prefill_ms(o, component: str) -> Optional[float]:
    """Device ms under ``component`` in the traced range's
    ``prefill_chunk`` modules over the number of those modules."""
    found = splits(o, "prefill_chunk")
    if found is None:
        return None
    total = sum(s.seconds().get(component, 0.0) for s in found)
    return 1e3 * total / len(found)


def train_ms(o, component: str) -> Optional[float]:
    """Device ms under ``component``, forward and backward, of a
    ``train_step`` module: the median over the traced modules."""
    found = splits(o, "train_step")
    if found is None:
        return None
    return 1e3 * stats.median([s.seconds().get(component, 0.0)
                               for s in found])


def coverage_share(o) -> Optional[float]:
    """Device seconds of the operations some map holds under a component
    other than ``other``, over the device's busy seconds in the trace,
    every program counted."""
    found = splits(o, "")
    if found is None:
        return None
    busy = reduce.length((x.start_s, x.end_s) for x in o.trace.ops
                         if x.device == 0)
    if busy <= 0:
        return None
    named = sum(v for s in found for k, v in s.seconds().items()
                if k not in (UNMAPPED, "other"))
    return 100.0 * named / busy
