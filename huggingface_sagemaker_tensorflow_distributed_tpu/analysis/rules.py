"""graftlint rules R1–R7: the repo-specific invariants, each grounded
in a property a test already hunts dynamically —
the rule catches the regression in the diff instead.

Every rule is a pure function ``Project -> list[Finding]`` registered
in :data:`RULES`. Adding a rule: write the checker, register it with a
one-line rationale, add a positive/negative fixture pair to
``tests/test_graftlint.py``, and document it in README "Static
analysis"."""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Callable, Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (
    PACKAGE,
    Finding,
    Project,
    dotted_name,
    non_docstring_constants,
    walk_functions,
)


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    title: str
    rationale: str
    check: Callable[[Project], list[Finding]]


# ---------------------------------------------------------------------------
# R1: jax-free zones — static import reachability
# ---------------------------------------------------------------------------

#: top-level external import prefixes banned in jax-free zones
R1_BANNED = ("jax", "flax")

#: zone roots: path prefixes (dirs) or exact paths whose import-time
#: closure must stay jax-less
R1_ZONE_DIRS = (f"{PACKAGE}/obs/", f"{PACKAGE}/analysis/")
R1_ZONE_FILES = ("scripts/obsctl.py", "scripts/check_telemetry_schema.py",
                 "scripts/graftlint.py")


def r1_zone_roots(project: Project) -> list[str]:
    roots = []
    for path in project.files:
        if (path in R1_ZONE_FILES
                or any(path.startswith(d) for d in R1_ZONE_DIRS)):
            roots.append(path)
    return sorted(roots)


def r1_reachability(project: Project) -> dict[str, Optional[str]]:
    """The jax-free zone's import-time closure (path -> BFS parent)."""
    return project.import_closure(r1_zone_roots(project))


def check_r1(project: Project) -> list[Finding]:
    findings = []
    parent = r1_reachability(project)
    for path in sorted(parent):
        seen: set = set()           # one finding per banned package
        for name, lineno in project.top_level_imports(path):
            top = name.split(".")[0]
            if top in R1_BANNED and (lineno, top) not in seen:
                seen.add((lineno, top))
                chain = " -> ".join(Project.chain(parent, path))
                findings.append(Finding(
                    "R1", path, lineno,
                    f"import-time dependency on {top!r} inside the "
                    f"jax-free zone (reached via {chain}); move the "
                    "import into the function that needs it or out of "
                    "the zone"))
    return findings


# ---------------------------------------------------------------------------
# R2: host syncs on the serving hot path must be annotated
# ---------------------------------------------------------------------------

R2_FILE = f"{PACKAGE}/serve/engine.py"

#: the engine's per-iteration hot loop (the PR 12 dispatch/commit
#: split): one blocking fetch added here silently serializes the
#: overlap pipeline and eats the measured decode win
R2_HOT_FUNCS = frozenset({
    "_step", "_capacity_phase", "_capacity_covered", "_lone_stream",
    "_flush", "_select_bucket", "_switch_bucket",
    "_prefill_batch", "_decode_all", "_decode_all_spec",
    "_dispatch_decode", "_commit_decode", "_dispatch_spec",
    "_commit_spec", "_append", "_apply_cow",
    "_accrue_prefill", "_accrue_decode", "_stamp_admit",
    "_emit_timeline", "_swap_out", "_apply_restores", "_spill_block",
})

#: the CLI driver feeding the engine (PR 17): its per-request loop
#: sits upstream of admit(), so a stray blocking fetch there starves
#: the engine of ready work just as surely as one inside the engine
R2_DRIVER_FILE = "scripts/serve.py"

R2_DRIVER_FUNCS = frozenset({"main", "load_trace", "load_model"})

#: file -> function names whose bodies R2 scans
_R2_SCOPES = {R2_FILE: R2_HOT_FUNCS, R2_DRIVER_FILE: R2_DRIVER_FUNCS}

#: call patterns that block the host on device state
_R2_CALLS = ("jax.device_get", "jax.block_until_ready",
             "np.asarray", "numpy.asarray", "np.array", "numpy.array")


def _r2_sync_calls(fn: ast.FunctionDef) -> list[tuple[int, str]]:
    hits = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name in _R2_CALLS:
            hits.append((node.lineno, f"{name}(...)"))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr == "item" and not node.args
                and not node.keywords):
            hits.append((node.lineno, ".item()"))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr == "block_until_ready"):
            # the array-METHOD form blocks just like the module call
            hits.append((node.lineno, ".block_until_ready()"))
    return hits


def check_r2(project: Project) -> list[Finding]:
    findings = []
    for path in sorted(project.files):
        if "<stdin>" in path:
            scope = R2_HOT_FUNCS | R2_DRIVER_FUNCS
        elif path in _R2_SCOPES:
            scope = _R2_SCOPES[path]
        else:
            continue
        for fn in walk_functions(project.files[path].tree):
            if fn.name not in scope:
                continue
            for lineno, what in _r2_sync_calls(fn):
                findings.append(Finding(
                    "R2", path, lineno,
                    f"blocking host fetch {what} inside hot-loop "
                    f"function {fn.name}() — a new sync here "
                    "serializes the dispatch-ahead pipeline; annotate "
                    "why this fetch is safe or move it off the decode "
                    "path"))
    return findings


# ---------------------------------------------------------------------------
# R3: jit static-key hygiene
# ---------------------------------------------------------------------------


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted_name(node) == "jax.jit"


def _literal_static(value: ast.AST, want) -> bool:
    if isinstance(value, ast.Constant):
        return isinstance(value.value, want)
    if isinstance(value, (ast.Tuple, ast.List)):
        return all(isinstance(e, ast.Constant)
                   and isinstance(e.value, want)
                   for e in value.elts)
    return False


def _jit_sites(tree: ast.Module):
    """Yield ``(lineno, keywords)`` per jit site: direct ``jax.jit``
    calls, ``functools.partial(jax.jit, ...)`` wrappers (the inner
    bare ``jax.jit`` reference is an Attribute, so it never
    double-reports through the Call branch), and bare ``@jax.jit``
    decorators (empty keyword list)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            if _is_jax_jit(node.func):
                yield node.lineno, node.keywords
            elif (dotted_name(node.func) in ("functools.partial",
                                             "partial")
                  and node.args and _is_jax_jit(node.args[0])):
                yield node.lineno, node.keywords
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if _is_jax_jit(dec) and not isinstance(dec, ast.Call):
                    yield dec.lineno, []


def check_r3(project: Project) -> list[Finding]:
    findings = []
    for path in sorted(project.files):
        for lineno, keywords in _jit_sites(project.files[path].tree):
            static = [k for k in keywords
                      if k.arg in ("static_argnums", "static_argnames")]
            if not static:
                findings.append(Finding(
                    "R3", path, lineno,
                    "jax.jit site declares no static_argnums/"
                    "static_argnames — every non-array argument left "
                    "dynamic retraces, every one made static without "
                    "declaration here is invisible to the "
                    "compile-flatness gates; declare the statics or "
                    "state that every argument is traced"))
                continue
            for kw in static:
                want = int if kw.arg == "static_argnums" else str
                if not _literal_static(kw.value, want):
                    findings.append(Finding(
                        "R3", path, lineno,
                        f"{kw.arg} is not a literal tuple of "
                        f"{want.__name__}s — a computed static set "
                        "can mint unbounded compile keys (one compile "
                        "per distinct runtime value); spell the "
                        "statics out"))
    return findings


# ---------------------------------------------------------------------------
# R4: telemetry field contract — obs.serve(...) keys exist in the schema
# ---------------------------------------------------------------------------

R4_SCHEMA = f"{PACKAGE}/obs/schema.py"


def _schema_serve_fields(project: Project) -> Optional[set]:
    """Field names of the ``serve`` event, extracted STATICALLY from
    the schema module's REQUIRED_FIELDS/OPTIONAL_FIELDS dict literals
    (no import: the linter never executes the tree it checks)."""
    sf = project.files.get(R4_SCHEMA)
    if sf is None:
        return None
    fields: set = set()
    found = False
    for node in ast.walk(sf.tree):
        if not (isinstance(node, (ast.Assign, ast.AnnAssign))):
            continue
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        names = {t.id for t in targets if isinstance(t, ast.Name)}
        if not names & {"REQUIRED_FIELDS", "OPTIONAL_FIELDS"}:
            continue
        value = node.value
        if not isinstance(value, ast.Dict):
            continue
        for key, val in zip(value.keys, value.values):
            if (isinstance(key, ast.Constant) and key.value == "serve"
                    and isinstance(val, ast.Dict)):
                found = True
                for k in val.keys:
                    if isinstance(k, ast.Constant):
                        fields.add(k.value)
    return fields if found else None


def _schema_serve_events(project: Project) -> Optional[set]:
    """The serve-event vocabulary, extracted STATICALLY from the
    schema module's ``SERVE_EVENTS`` tuple literal (ISSUE 19) — same
    no-import contract as the field extraction."""
    sf = project.files.get(R4_SCHEMA)
    if sf is None:
        return None
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Assign):
            continue
        names = {t.id for t in node.targets if isinstance(t, ast.Name)}
        if "SERVE_EVENTS" not in names:
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
    return None


def check_r4(project: Project) -> list[Finding]:
    fields = _schema_serve_fields(project)
    if fields is None:
        return []          # no schema in scope (stdin / partial tree)
    allowed = fields | {"event"}
    events = _schema_serve_events(project)
    findings = []
    for path in sorted(project.files):
        if path == R4_SCHEMA:
            continue
        for node in ast.walk(project.files[path].tree):
            if not (isinstance(node, ast.Call)
                    and dotted_name(node.func) == "obs.serve"):
                continue
            # the event KIND (the literal first positional arg) must
            # come from the schema's SERVE_EVENTS vocabulary (ISSUE
            # 19) — an invented kind is the same silent drift for
            # consumers that switch on `event` as an undeclared field
            # is for ones that type-check kwargs
            if (events is not None and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value not in events):
                findings.append(Finding(
                    "R4", path, node.lineno,
                    f"serve-event kind {node.args[0].value!r} is not "
                    "declared in obs/schema.py SERVE_EVENTS — "
                    "undeclared kinds are silent schema drift; add it "
                    "to the vocabulary"))
            for kw in node.keywords:
                if kw.arg is None:       # **dynamic: not checkable here
                    continue
                if kw.arg not in allowed:
                    findings.append(Finding(
                        "R4", path, node.lineno,
                        f"serve-event field {kw.arg!r} is not declared "
                        "in obs/schema.py REQUIRED_FIELDS/"
                        "OPTIONAL_FIELDS['serve'] — undeclared fields "
                        "are silent schema drift (consumers can't "
                        "type-check them); declare it with its type"))
    return findings


# ---------------------------------------------------------------------------
# R5: env-knob registry — HSTD_* in code <-> README env table
# ---------------------------------------------------------------------------

_HSTD_RE = re.compile(r"^HSTD_[A-Z0-9_]+$")
_HSTD_TOKEN_RE = re.compile(r"HSTD_[A-Z0-9_]+")
_README_ROW_RE = re.compile(r"^\s*\|\s*`HSTD_")


def _code_env_reads(project: Project) -> dict[str, tuple[str, int]]:
    """var -> first (path, line) where a non-docstring string literal
    names it (env reads go through literals in this repo; a computed
    env name would be its own smell)."""
    out: dict[str, tuple[str, int]] = {}
    for path in sorted(project.files):
        for value, lineno in non_docstring_constants(
                project.files[path].tree):
            if _HSTD_RE.match(value) and value not in out:
                out[value] = (path, lineno)
    return out


def _readme_env_table(project: Project) -> dict[str, int]:
    """var -> README line of its env-table row (rows are the
    ``| `HSTD_...` | ...`` table lines)."""
    out: dict[str, int] = {}
    if not project.readme:
        return out
    for i, line in enumerate(project.readme.splitlines(), start=1):
        if _README_ROW_RE.match(line):
            for tok in _HSTD_TOKEN_RE.findall(line):
                out.setdefault(tok, i)
    return out


def check_r5(project: Project) -> list[Finding]:
    if project.readme is None:
        return []
    code = _code_env_reads(project)
    table = _readme_env_table(project)
    findings = []
    for var in sorted(set(code) - set(table)):
        path, lineno = code[var]
        findings.append(Finding(
            "R5", path, lineno,
            f"{var} is read in code but has no row in the README "
            "environment-variable table — every knob ships "
            "documented"))
    for var in sorted(set(table) - set(code)):
        findings.append(Finding(
            "R5", "README.md", table[var],
            f"{var} is documented in the README environment-variable "
            "table but nothing in the tree reads it — stale docs "
            "mislead operators; delete the row or wire the knob"))
    return findings


# ---------------------------------------------------------------------------
# R6: BlockManager discipline — no raw free()/refcount access outside
# serve/paged_kv.py
# ---------------------------------------------------------------------------

R6_HOME = f"{PACKAGE}/serve/paged_kv.py"
_R6_PRIVATE_ATTRS = ("_refs", "_extra_refs")


def check_r6(project: Project) -> list[Finding]:
    findings = []
    for path in sorted(project.files):
        if path == R6_HOME or not path.startswith(f"{PACKAGE}/"):
            continue
        for node in ast.walk(project.files[path].tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "free"):
                findings.append(Finding(
                    "R6", path, node.lineno,
                    "raw .free() on block ids outside serve/"
                    "paged_kv.py — the refcounted pool frees through "
                    "release() (a raw free of a shared block is the "
                    "double-free class the conservation property test "
                    "hunts at runtime)"))
            elif (isinstance(node, ast.Attribute)
                    and node.attr in _R6_PRIVATE_ATTRS
                    and not (isinstance(node.value, ast.Name)
                             and node.value.id == "self")):
                findings.append(Finding(
                    "R6", path, node.lineno,
                    f"direct access to BlockManager internals "
                    f"(.{node.attr}) outside serve/paged_kv.py — "
                    "refcount state mutates only through release()/"
                    "privatize()/commit_match()"))
    return findings


# ---------------------------------------------------------------------------
# R7: admission policy stays host-side — serve/policy.py and its
# import-time closure are jax-free
# ---------------------------------------------------------------------------

R7_ROOT = f"{PACKAGE}/serve/policy.py"


def check_r7(project: Project) -> list[Finding]:
    """Same reachability walk as R1, rooted at the admission-policy
    module. The policy layer's contract is that admission ordering is
    pure host arithmetic — the compile-flatness test (ZERO new
    compiled variants under ``policy=slo``) rests on no jax reaching
    the module at import time, and the router's rate limiter must keep
    importing on jax-less driver boxes."""
    if R7_ROOT not in project.files:
        return []
    findings = []
    parent = project.import_closure([R7_ROOT])
    for path in sorted(parent):
        seen: set = set()           # one finding per banned package
        for name, lineno in project.top_level_imports(path):
            top = name.split(".")[0]
            if top in R1_BANNED and (lineno, top) not in seen:
                seen.add((lineno, top))
                chain = " -> ".join(Project.chain(parent, path))
                findings.append(Finding(
                    "R7", path, lineno,
                    f"import-time dependency on {top!r} in the "
                    f"admission-policy zone (reached via {chain}); "
                    "admission ordering is host-side by contract — "
                    "keep serve/policy.py's closure jax-free"))
    return findings


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

RULES: dict[str, Rule] = {
    "R1": Rule(
        "R1", "jax-free-zones",
        "obs/, analysis/ and the obsctl/schema CLIs must import on "
        "boxes without jax; static reachability is complete where the "
        "subprocess poison test only covers imported-today paths.",
        check_r1),
    "R2": Rule(
        "R2", "host-sync-in-hot-path",
        "the dispatch-ahead decode loop's only blocking fetches are "
        "the deferred commit/spec ones; an unannotated sync silently "
        "eats the overlap win. Also covers the scripts/serve.py "
        "driver loop, which sits upstream of admit().",
        check_r2),
    "R3": Rule(
        "R3", "jit-static-key-hygiene",
        "every jit site declares its static argnums/argnames as "
        "literals, so the compile-flatness gates can trust that no "
        "unbounded static key (e.g. a per-request string) mints a "
        "compile per request.",
        check_r3),
    "R4": Rule(
        "R4", "telemetry-field-contract",
        "string field keys passed to obs.serve() must exist in "
        "obs/schema.py, and literal event kinds in its SERVE_EVENTS "
        "vocabulary, so schema drift fails lint instead of "
        "surfacing only when a test exercises the emitting path.",
        check_r4),
    "R5": Rule(
        "R5", "env-knob-registry",
        "every HSTD_* env var read in code has a README table row and "
        "vice versa — the two registries are kept from drifting.",
        check_r5),
    "R6": Rule(
        "R6", "blockmanager-discipline",
        "block ids are freed only through release()/privatize() "
        "inside serve/paged_kv.py — a raw free from the scheduler is "
        "exactly the double-free class the conservation test hunts.",
        check_r6),
    "R7": Rule(
        "R7", "policy-jax-free",
        "serve/policy.py and everything it imports stay jax-free — "
        "admission ordering is host arithmetic, which is what makes "
        "the zero-new-compiles test under policy=slo and jax-less "
        "driver-box imports hold.",
        check_r7),
}
