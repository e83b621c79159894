"""The program map: which of the program's own modules each operation of
a compiled hot-path program came from.

A device trace names an operation by its HLO line (``%fusion.12 = ...``)
and nothing else; the compiled program's text carries, on every
instruction, ``metadata={op_name="jit(_prefill_chunk)/LlamaForCausalLM/
backbone/layers_3/self_attn/q_proj/dot_general"}``: jax's name stack,
with flax's module instance names as its segments. This module joins
the two from inside the program:

- :func:`register` keeps, for each hot-path program, its jitted callable
  and the ABSTRACT arguments it was run with (shapes, types, shardings:
  no live array), by the program's short name and its shape key. It is
  called where a program is first run at full shape (``ServeEngine.
  warmup``, the trainer's first step) and nowhere on a hot path. Like a
  life-cycle span an entry is kept before a directory is configured,
  and the registry is bounded.
- :func:`flush` (from ``obs.flush()`` / ``obs.shutdown()``), only with a
  sink and a non-empty registry, resolves each entry once:
  ``jitted.lower(*abstract).compile().as_text()`` (with a persistent
  compilation cache a hit), every instruction classified by the table
  below, ONE ``program_map`` event a program.

The classification rests on flax's module paths and the primitive's
name alone, which every executable carries, also one that a cache holds
from before a ``jax.named_scope`` was added (metadata is no part of the
cache's key). The engine's and the trainer's own scopes (``serve/
cache_read`` ...) refine it where the text has them; the event says
which it found (``refined_by``).

jax is imported inside :func:`register` and the resolve only: ``obs``
stays importable without it.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
import weakref
from typing import Any, Callable, NamedTuple, Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
    PROGRAM_COMPONENTS as COMPONENTS,
)

MAX_PROGRAMS = 64          # entries kept: the last registered

# module instance name -> component. The LAST known segment of an
# operation's path decides: `layers_3/attn_hc/self_attn/q_proj` is the
# mixer's, `layers_3/attn_hc/mul` the residual path's, `layers_3/add`
# (a residual add directly under the layer) too.
SEGMENTS = {
    # token mixers: projections, rotary, the kernels called under them,
    # the recurrence and its convolution, cache rows read or written
    # inside them; latent attention's own norms stay with it
    "self_attn": "mixer", "linear_attn": "mixer", "attention": "mixer",
    "cross_attn": "mixer", "q_a_ln": "mixer", "kv_a_ln": "mixer",
    "q_norm": "mixer", "k_norm": "mixer",
    # MLPs and experts: gate, routing and the grouped matmuls included
    "mlp": "ffn", "moe": "ffn", "shared_experts": "ffn", "ffn": "ffn",
    # norms, hyper-connection wraps, dropout, the layer's own adds
    "input_ln": "residual", "post_attn_ln": "residual",
    "post_mlp_ln": "residual", "attention_ln": "residual",
    "ffn_ln": "residual", "attn_hc": "residual", "ffn_hc": "residual",
    "layers_*": "residual", "layer_*": "residual", "Dropout_*": "residual",
    # the head, the loss and the token pick
    "final_ln": "head", "lm_head": "head", "mlm_head": "head",
    "pooler": "head", "classifier": "head",
    "embed_tokens": "embed", "embeddings": "embed",
    # the program's own scopes (jax.named_scope): a refinement only
    "cache_read": "cache", "cache_write": "cache", "sample": "head",
    "loss": "head", "optimizer": "optimizer",
}
REFINING_SCOPES = ("serve/cache_read", "serve/cache_write", "serve/sample",
                   "train/loss", "train/optimizer")

# outside the model's path, by the primitive (the path's last segment)
# or the HLO opcode: rows gathered from the pools or written back
_CACHE_PRIMITIVES = frozenset((
    "gather", "scatter", "scatter-add", "scatter_add", "dynamic_slice",
    "dynamic-slice", "dynamic_update_slice", "dynamic-update-slice"))
_PICK_PRIMITIVES = frozenset((
    "argmax", "argmin", "sort", "top_k", "cumsum", "random_bits",
    "threefry2x32", "random_wrap", "random_unwrap", "random_fold_in"))
_COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "collective-broadcast")
# instructions that never run as an operation of their own
_SILENT_OPCODES = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id"))

_COMPUTATION = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"(branch_computations|called_computations)="
                       r"\{([^}]*)\}")
_POOL_ARG = re.compile(r"pools\[\d+\]$")
_METADATA = re.compile(r",\s*metadata=\{[^{}]*\}")
_NUMBERED = re.compile(r"^(layers?|Dropout|LayerNorm|Dense)_\d+$")
# jvp(X), transpose(jvp(X))
_WRAPPER = re.compile(r"^(?:[a-z_]+\()+(.*?)\)+$")


class _Instruction:
    __slots__ = ("name", "result", "opcode", "op_name", "refs", "root",
                 "called")

    def __init__(self, name, result, opcode, op_name, refs, root, called):
        self.name, self.result, self.opcode = name, result, opcode
        self.op_name, self.refs, self.root = op_name, refs, root
        self.called = called      # [(attribute, computation name)]


def short_name(module_name: str) -> str:
    """``prefill_chunk`` of ``jit__prefill_chunk``: a program's short
    name, as the benchmark's trace reduction makes it of a module's."""
    name = module_name.split("(", 1)[0]
    return name[4:].lstrip("_") if name.startswith("jit_") else name


def result_type(rest: str, opcode_at: int) -> str:
    """An instruction's result type as a trace's event line gives it:
    without layouts, cut at 72 characters."""
    result = _LAYOUT.sub("", rest[:opcode_at].strip())
    return result if len(result) <= 72 else result[:69] + "..."


def parse_hlo(text: str) -> tuple:
    """``(module name, {computation: [instructions]})`` of an optimised
    HLO module's text (``compiled.as_text()``), computations in the
    text's order: a callee before its caller."""
    module = ""
    comps: dict = {}
    current = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line[len("HloModule "):].split(",", 1)[0].strip()
            continue
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.search(" " + rest)
        if op is None:
            continue
        at = max(op.start() - 1, 0)      # where the result type ends
        meta = _METADATA.search(rest)
        body = (rest if meta is None
                else rest[:meta.start()] + rest[meta.end():])
        named = _OP_NAME.search(meta.group(0)) if meta else None
        called = _CALLS.findall(body)
        for attr, group in _BRANCHES.findall(body):
            called += [(attr, c) for c in _REF.findall(group)]
        current.append(_Instruction(
            m.group(2), result_type(rest, at), op.group(1),
            named.group(1).replace("\\'", "'") if named else "",
            _REF.findall(body[at:]), bool(m.group(1)), called))
    return module, comps


class _Path(NamedTuple):
    segments: list      # below the jit(...) wrappers, numbered modules
    #                     collapsed (`layers_3` -> `layers_*`)
    which: str          # "fwd" | "bwd" from a jvp / transpose(jvp)
    #                     wrapper, "" without one
    model_at: int       # index of the model's class among the segments;
    #                     -1: the path lies outside the model


def _top_level(path: str) -> list:
    """``path`` cut at the slashes that lie outside every parenthesis
    (``jvp(train/loss)/Model/add`` has three parts)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(depth - 1, 0)
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def split_path(op_name: str, root: str) -> _Path:
    """An ``op_name`` taken apart; ``root`` is the model's class name,
    under which flax puts its modules' paths."""
    which, model_at, out = "", -1, []
    # a fused or merged instruction may carry several names: the first
    for part in _top_level(op_name.split(";", 1)[0]):
        if part.startswith(("jit(", "pjit(")) and not out:
            continue
        m = _WRAPPER.match(part)
        if m and ("jvp(" in part or "transpose(" in part):
            # what a transform wraps is a path of its own
            if "transpose(" in part:
                which = "bwd"
            elif not which:
                which = "fwd"
            part = m.group(1)
        for seg in part.split("/") if m else [part]:
            if not seg or (seg.startswith("jit(") and not out):
                continue
            if seg == root and model_at < 0:
                model_at = len(out)
            n = _NUMBERED.match(seg)
            out.append(f"{n.group(1)}_*" if n else seg)
    return _Path(out, which, model_at)


def classify(path: _Path, opcode: str, training: bool,
             reads_head: bool) -> Optional[str]:
    """The component of one named instruction; None where neither its
    path nor its primitive says (the caller then looks at what it reads
    and feeds). Inside the model only the segments below the model's
    class count, so a scope of the engine's or the trainer's own around
    the model's call decides nothing there."""
    inside = path.model_at >= 0
    # the last segment is the primitive's name, never a module's
    for seg in reversed(path.segments[path.model_at + 1:-1]):
        hit = SEGMENTS.get(seg)
        if hit is not None:
            return hit
    primitive = path.segments[-1] if path.segments else opcode
    if inside:
        # under the model's class and under no module the table knows:
        # the head's own matmul of a tied embedding sits here
        return "head" if primitive in ("dot_general", "dot") else None
    if training:
        # a train step outside the model: the loss is differentiated,
        # the optimizer is not
        return "head" if path.which else "optimizer"
    if reads_head:
        return "head"           # the token pick reads the logits
    if primitive in _CACHE_PRIMITIVES or opcode in _CACHE_PRIMITIVES:
        return "cache"
    if primitive in _PICK_PRIMITIVES:
        return "head"
    return None


def build_map(text: str, root: str = "") -> dict:
    """The ``program_map`` event's own fields from a compiled program's
    text: ``program``, ``scopes`` and ``ops`` (see the module's words and
    ``obs/schema.py``)."""
    module, comps = parse_hlo(text)
    fused, reducers = set(), set()
    for instrs in comps.values():
        for ins in instrs:
            for attr, comp in ins.called:
                if ins.opcode == "fusion" and attr == "calls":
                    fused.add(comp)
                elif attr == "called_computations" or (
                        attr == "to_apply" and ins.opcode != "call"):
                    reducers.add(comp)     # a reducer, a comparator
    training = "transpose(jvp(" in text
    # the block pools are arguments named `pools[i]` (`t_pools`, `d_pools`)
    pool_types = {ins.result for instrs in comps.values() for ins in instrs
                  if ins.opcode == "parameter"
                  and _POOL_ARG.search(ins.op_name)}
    scopes: list = []
    scope_index: dict = {}
    paths: dict = {}          # op_name -> _Path: layers repeat their names
    found = set()

    def scope_of(path: _Path) -> int:
        # the path below its primitive, which is the last segment
        key = ("/".join(path.segments[:-1]), path.which)
        if key not in scope_index:
            scope_index[key] = len(scopes)
            scopes.append(list(key))
        return scope_index[key]

    def named(ins, reads_head: bool = False) -> tuple:
        """``(component or None, scope index)`` by the instruction's own
        name; ``(None, -1)`` without one."""
        if not ins.op_name:
            return None, -1
        path = paths.get(ins.op_name)
        if path is None:
            path = paths[ins.op_name] = split_path(ins.op_name, root)
            joined = "/" + "/".join(path.segments) + "/"
            found.update(s for s in REFINING_SCOPES
                         if "/" + s + "/" in joined)
        return (classify(path, ins.opcode, training, reads_head),
                scope_of(path))

    ops: dict = {}
    for comp_name, instrs in comps.items():
        if comp_name in fused or comp_name in reducers:
            continue
        local: dict = {}          # name -> [scope, component, result, mixed]
        pending = []
        for ins in instrs:
            reads_head = any(local.get(r, (0, None))[1] == "head"
                             for r in ins.refs)
            component, scope = named(ins, reads_head)
            others: list = []
            if ins.opcode.startswith(_COLLECTIVE_OPCODES):
                component = "collective"
            elif ins.opcode == "fusion":
                inner = [(i, named(i, reads_head)) for attr, c in ins.called
                         if attr == "calls" for i in comps.get(c, ())
                         if i.opcode not in _SILENT_OPCODES]
                if component is None:
                    # the fusion has no name of its own: its root's, else
                    # the first fused instruction's that has one
                    inner.sort(key=lambda pair: not pair[0].root)
                    component, scope = next(
                        (got for _i, got in inner if got[0] is not None),
                        (None, scope))
                others = sorted({got[0] for _i, got in inner
                                 if got[0] not in (None, component)})
            elif component is None and ins.opcode == "copy" \
                    and ins.result in pool_types:
                component = "cache"     # a pool copied whole
            row = [scope, component, ins.result, bool(others)]
            if others:
                row.append(others)
            local[ins.name] = row
            if component is None:
                pending.append(ins)
        # what neither path nor primitive said: the first operand's
        # component, then (for what only feeds others) the first user's;
        # tuples and their elements pass a component on and are no
        # operations themselves
        for ins in pending:
            for ref in ins.refs:
                got = local.get(ref)
                if got is not None and got[1] is not None:
                    local[ins.name][0:2] = got[0:2]
                    break
        users: dict = {}
        for ins in instrs:
            for ref in ins.refs:
                users.setdefault(ref, []).append(ins.name)
        for ins in reversed(pending):
            if local[ins.name][1] is not None:
                continue
            for user in users.get(ins.name, ()):
                got = local.get(user)
                if got is not None and got[1] is not None:
                    local[ins.name][0:2] = got[0:2]
                    break
        for ins in instrs:
            if ins.opcode in _SILENT_OPCODES:
                del local[ins.name]
        ops.update(local)
    # a loop's body, a branch or a called computation whose instructions
    # say nothing of themselves are their caller's; the text lists a
    # callee before its caller, so callers come first from the end
    for comp_name in reversed(list(comps)):
        for ins in comps[comp_name]:
            row = ops.get(ins.name)
            if row is None or row[1] is None:
                continue
            for attr, callee in ins.called:
                if callee in fused or callee in reducers:
                    continue
                for inner in comps.get(callee, ()):
                    got = ops.get(inner.name)
                    if got is not None and got[1] is None:
                        got[0:2] = row[0:2]
    for row in ops.values():
        if row[1] is None:
            row[1] = "other"
    return {"program": short_name(module), "module": module,
            "scopes": scopes, "ops": ops,
            "refined_by": sorted(found), "training": training}


# -- the registry -------------------------------------------------------------

class _Entry:
    __slots__ = ("name", "key", "jitted", "args", "root", "context",
                 "payload", "written", "failed")

    def __init__(self, name, key, jitted, args, root, context):
        self.name, self.key, self.args = name, key, args
        self.root = root
        self.context = contextlib.nullcontext if context is None else context
        try:
            # a trainer's step is a bound method: the registry must not
            # keep the trainer, and its state, alive
            self.jitted = weakref.ref(jitted)
        except TypeError:
            self.jitted = lambda: jitted
        self.payload: Optional[dict] = None
        self.written: set = set()
        self.failed: Optional[str] = None


def _abstract(args: tuple, static: tuple) -> tuple:
    """``args`` with every array of the positions outside ``static``
    replaced by its shape, type and (where it is committed) sharding."""
    import jax
    import numpy as np

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        sharding = getattr(x, "sharding", None) if getattr(
            x, "committed", getattr(x, "_committed", False)) else None
        dtype = x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype
        return jax.ShapeDtypeStruct(np.shape(x), dtype, sharding=sharding)

    return tuple(a if i in static else jax.tree_util.tree_map(leaf, a)
                 for i, a in enumerate(args))


class ProgramRegistry:
    """The process's hot-path programs, and their maps once resolved:
    the ``MAX_PROGRAMS`` registered last (an engine rebuilt over the same
    model and geometry registers the same programs again: once)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict = {}      # in the order they were registered
        self.dropped = 0
        self.cache_hits = 0
        self._listening = False

    def __len__(self) -> int:
        return len(self._entries)

    def register(self, name: str, jitted: Callable, args: tuple,
                 static: tuple = (), key: Optional[dict] = None,
                 root: str = "", context: Optional[Callable] = None) -> bool:
        """Keep ``jitted`` and the abstract form of ``args`` (positions
        ``static`` as they are: hashable, no arrays) under ``name`` and
        ``key``. ``root`` is the model's class name, under which flax
        puts its modules' paths; ``context()`` a context manager the
        lowering needs around it (a mesh). False where the same callable
        is there already under that name, key and static arguments; the
        oldest entry goes where the registry is full."""
        static = tuple(static)
        ident = (name, tuple(sorted((key or {}).items())), id(jitted),
                 tuple(args[i] for i in static))
        with self._lock:
            have = self._entries.get(ident)
            if have is not None and have.jitted() is not None:
                return False
            # under the lock: a second caller must not abstract again
            self._entries.pop(ident, None)
            self._entries[ident] = _Entry(
                name, dict(key or {}), jitted, _abstract(tuple(args), static),
                root, context)
            while len(self._entries) > MAX_PROGRAMS:
                del self._entries[next(iter(self._entries))]
                self.dropped += 1
        return True

    def _listen(self) -> None:
        if self._listening:
            return
        from jax import monitoring

        def hit(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_listener(hit)
        self._listening = True

    def _resolve(self, entry: _Entry) -> None:
        jitted = entry.jitted()
        if jitted is None:
            entry.failed = "the jitted callable is gone"
            return
        self._listen()
        hits = self.cache_hits
        t0 = time.perf_counter()
        with entry.context():
            text = jitted.lower(*entry.args).compile().as_text()
        payload = build_map(text, entry.root)
        payload.update(name=entry.name, key=entry.key,
                       resolve_s=round(time.perf_counter() - t0, 6),
                       from_cache=self.cache_hits > hits)
        entry.payload = payload

    def flush(self, state: Any) -> int:
        """Resolve what is not resolved yet and write one ``program_map``
        event a program to the sink of ``state`` (``obs.core.ObsState``);
        nothing without a sink. Returns the events written."""
        events = state.events
        if events is None or not state.enabled or not self._entries:
            return 0
        with self._lock:
            entries = list(self._entries.values())
        written = 0
        for entry in entries:
            if entry.payload is None and entry.failed is None:
                try:
                    self._resolve(entry)
                except Exception as e:  # noqa: BLE001 — telemetry must
                    # never kill the run it observes; the event says why
                    entry.failed = f"{type(e).__name__}: {e}"
            if events.path in entry.written:
                continue
            entry.written.add(events.path)
            if entry.payload is not None:
                events.emit("program_map", entry.payload)
                written += 1
            else:
                events.emit("alert", {
                    "name": "program_map",
                    "message": f"{entry.name} {entry.key}: no map "
                               f"({entry.failed})"})
        return written


_registry = ProgramRegistry()


def registry() -> ProgramRegistry:
    return _registry


def register(name: str, jitted: Callable, args: tuple, static: tuple = (),
             key: Optional[dict] = None, root: str = "",
             context: Optional[Callable] = None) -> bool:
    """:meth:`ProgramRegistry.register` on the process's registry."""
    return _registry.register(name, jitted, args, static=static, key=key,
                              root=root, context=context)


def flush(state: Any) -> int:
    return _registry.flush(state)


def reset() -> None:
    """Test helper (``obs.reset``): forget every program."""
    global _registry
    _registry = ProgramRegistry()
