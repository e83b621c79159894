"""graftlint core: source loading, suppression pragmas, the module
import graph, the rule runner, and the two renderers.

Contracts (mirrored by ``tests/test_graftlint.py``):

- **Stdlib-only / jax-less.** The linter must run on the driver box and
  inside CI lint steps with no accelerator stack installed; rule R1
  enforces this on the linter itself.
- **Deterministic.** Same tree -> byte-identical output, regardless of
  the order paths were handed in: files load sorted by repo-relative
  path, findings sort by ``(path, line, rule, message)``, JSON renders
  with sorted keys and no wall-clock stamps.
- **Suppression pragmas.** ``# graftlint: allow[rule-id] reason`` on
  the offending line (or alone on the line above) suppresses that
  rule's findings there. The reason is mandatory: a pragma without one
  is itself a finding (rule id ``pragma``), so every exception in the
  tree documents why it is safe. A pragma whose rule does NOT fire on
  its line is also a finding (same rule id): stale suppressions are
  landmines — the code they excused is gone, and the next genuine
  violation on that line would be silently swallowed.
- **Exit codes** (CLI layer): 0 clean, 1 bad input (unparseable file,
  missing path), 2 unsuppressed findings — the same shape as
  ``obsctl diff``.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Iterable, Optional, Sequence

#: the package the linter analyzes (and lives in)
PACKAGE = "huggingface_sagemaker_tensorflow_distributed_tpu"

#: repo-root entries linted alongside the package
DEFAULT_EXTRAS = ("scripts", "launch.py")

#: rule id for pragma-hygiene findings (not suppressible — a pragma
#: cannot vouch for another pragma)
PRAGMA_RULE = "pragma"

_PRAGMA_RE = re.compile(
    r"#\s*graftlint:\s*allow\[([A-Za-z0-9_.\-]+)\]\s*(.*?)\s*$")
_PRAGMA_MARK_RE = re.compile(r"#\s*graftlint\s*:")


class LintInputError(Exception):
    """Bad input (missing path, unparseable source): CLI exit code 1."""


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str                      # repo-relative, posix separators
    line: int
    message: str
    suppressed: bool = False
    reason: Optional[str] = None   # the pragma's reason when suppressed

    def render(self) -> str:
        tag = f" (suppressed: {self.reason})" if self.suppressed else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{tag}"


@dataclasses.dataclass
class SourceFile:
    path: str                      # repo-relative, posix separators
    text: str
    tree: ast.Module
    #: line -> list of (rule_id, reason) pragmas governing that line
    pragmas: dict[int, list[tuple[str, str]]]
    #: (line, message) for malformed pragmas (missing reason, unparsed)
    bad_pragmas: list[tuple[int, str]]
    #: dotted module name for package modules, None for repo scripts
    module: Optional[str] = None


class Project:
    """The linted tree: parsed sources plus the top-level import graph."""

    def __init__(self, root: str, files: dict[str, SourceFile],
                 readme: Optional[str],
                 requested: Optional[list[str]] = None):
        self.root = root
        self.files = files                    # path -> SourceFile
        self.readme = readme                  # README text or None
        #: explicit path selection (None = whole tree): rules always
        #: see the FULL tree (cross-file contracts need it), the
        #: runner filters findings down to these paths afterwards
        self.requested = requested
        self.by_module = {
            sf.module: p for p, sf in files.items() if sf.module
        }
        self._imports: Optional[dict[str, list[tuple[str, int]]]] = None

    # -- import graph --------------------------------------------------------

    def top_level_imports(self, path: str) -> list[tuple[str, int]]:
        """``(dotted_name, lineno)`` for every import that executes at
        module import time: module-level statements, including those
        nested in ``if``/``try``/``with``/class bodies — but NOT inside
        function bodies (lazy imports are the sanctioned escape hatch
        for heavy deps)."""
        if self._imports is None:
            self._imports = {}
        if path not in self._imports:
            self._imports[path] = self._collect_imports(self.files[path])
        return self._imports[path]

    def _collect_imports(self, sf: SourceFile) -> list[tuple[str, int]]:
        seen: set[tuple[str, int]] = set()
        out: list[tuple[str, int]] = []

        def add(name: str, lineno: int) -> None:
            if (name, lineno) not in seen:
                seen.add((name, lineno))
                out.append((name, lineno))

        def visit(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(child, ast.Import):
                    for alias in child.names:
                        add(alias.name, child.lineno)
                elif isinstance(child, ast.ImportFrom):
                    base = child.module or ""
                    if child.level:                 # relative import
                        base = self._resolve_relative(sf, child.level,
                                                      base)
                        if base is None:
                            continue
                    add(base, child.lineno)
                    for alias in child.names:
                        # `from a.b import c` may bind module a.b.c or
                        # attribute c of a.b; record both candidates
                        # (edges to non-modules are simply dropped when
                        # the graph walks intra-package links)
                        if alias.name != "*":
                            add(f"{base}.{alias.name}", child.lineno)
                else:
                    visit(child)

        visit(sf.tree)
        return out

    def _resolve_relative(self, sf: SourceFile, level: int,
                          base: str) -> Optional[str]:
        if not sf.module:
            return None
        parts = sf.module.split(".")
        # a package __init__'s own dots resolve against the package
        if not sf.path.endswith("__init__.py"):
            parts = parts[:-1]
        if level > len(parts):
            return None
        parts = parts[:len(parts) - (level - 1)]
        return ".".join(parts + ([base] if base else [])).strip(".")

    def module_edges(self, path: str) -> list[tuple[str, int]]:
        """Intra-project ``(target_path, lineno)`` edges for ``path``:
        resolved package imports, each implying its ancestor package
        ``__init__`` modules too (importing ``a.b.c`` executes ``a``
        and ``a.b`` first)."""
        edges = []
        for name, lineno in self.top_level_imports(path):
            for target in self._expand_ancestors(name):
                tpath = self.by_module.get(target)
                if tpath is not None:
                    edges.append((tpath, lineno))
        return edges

    @staticmethod
    def _expand_ancestors(name: str) -> Iterable[str]:
        parts = name.split(".")
        for i in range(1, len(parts) + 1):
            yield ".".join(parts[:i])

    def import_closure(self, roots: Sequence[str]
                       ) -> dict[str, Optional[str]]:
        """BFS over intra-project import-time edges from ``roots``
        (paths). Returns ``{reached_path: parent_path_or_None}`` —
        parents reconstruct a witness chain for diagnostics.
        Deterministic: roots and adjacency walk in sorted order."""
        parent: dict[str, Optional[str]] = {}
        queue: list[str] = []
        for r in sorted(roots):
            if r in self.files and r not in parent:
                parent[r] = None
                queue.append(r)
        while queue:
            cur = queue.pop(0)
            for tpath, _ in sorted(self.module_edges(cur)):
                if tpath not in parent:
                    parent[tpath] = cur
                    queue.append(tpath)
        return parent

    @staticmethod
    def chain(parent: dict[str, Optional[str]], path: str) -> list[str]:
        out = [path]
        while parent.get(path) is not None:
            path = parent[path]          # type: ignore[assignment]
            out.append(path)
        return list(reversed(out))


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------


def _parse_pragmas(text: str
                   ) -> tuple[dict[int, list[tuple[str, str]]],
                              list[tuple[int, str]]]:
    """Pragmas from REAL comment tokens only (``tokenize``), so pragma
    syntax quoted in a docstring or string literal can neither create
    a phantom suppression nor fail the tree as a malformed pragma."""
    pragmas: dict[int, list[tuple[str, str]]] = {}
    bad: list[tuple[int, str]] = []
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError,
            SyntaxError):          # the ast parse is the gatekeeper
        return pragmas, bad
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        i, col = tok.start
        m = _PRAGMA_RE.search(tok.string)
        if m is None:
            if _PRAGMA_MARK_RE.search(tok.string):
                bad.append((i, "unparseable graftlint pragma: expected "
                              "`# graftlint: allow[rule-id] reason`"))
            continue
        rule_id, reason = m.group(1), m.group(2).strip()
        if not reason:
            bad.append((i, f"pragma allow[{rule_id}] carries no reason "
                           "— every suppression must say why it is "
                           "safe"))
            continue
        # a standalone pragma comment governs the NEXT line; a trailing
        # pragma governs its own line
        standalone = not tok.line[:col].strip()
        target = i + 1 if standalone else i
        pragmas.setdefault(target, []).append((rule_id, reason))
    return pragmas, bad


def _load_file(root: str, rel: str) -> SourceFile:
    abspath = os.path.join(root, rel.replace("/", os.sep))
    try:
        with open(abspath, "r", encoding="utf-8") as f:
            text = f.read()
    except OSError as e:
        raise LintInputError(f"cannot read {rel}: {e}")
    return _make_source(rel, text)


def _make_source(rel: str, text: str,
                 module: Optional[str] = None) -> SourceFile:
    try:
        tree = ast.parse(text, filename=rel)
    except SyntaxError as e:
        raise LintInputError(f"{rel}:{e.lineno}: syntax error: {e.msg}")
    pragmas, bad = _parse_pragmas(text)
    if module is None:
        module = _module_name(rel)
    return SourceFile(path=rel, text=text, tree=tree, pragmas=pragmas,
                      bad_pragmas=bad, module=module)


def _module_name(rel: str) -> Optional[str]:
    """Dotted module name for package files; repo scripts get a
    ``scripts.x`` / top-level name so intra-scripts imports
    resolve too."""
    if not rel.endswith(".py"):
        return None
    parts = rel[:-3].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts:
        return None
    return ".".join(parts)


def _discover(root: str, package: str = PACKAGE,
              extras: Sequence[str] = DEFAULT_EXTRAS) -> list[str]:
    rels: list[str] = []
    pkg_dir = os.path.join(root, package)
    if not os.path.isdir(pkg_dir):
        raise LintInputError(f"package directory {package!r} not found "
                             f"under {root}")
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames.sort()
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                rels.append(os.path.relpath(os.path.join(dirpath, fn),
                                            root).replace(os.sep, "/"))
    for extra in extras:
        p = os.path.join(root, extra)
        if os.path.isdir(p):
            for fn in sorted(os.listdir(p)):
                if fn.endswith(".py"):
                    rels.append(f"{extra}/{fn}")
        elif os.path.isfile(p) and extra.endswith(".py"):
            rels.append(extra)
    return sorted(set(rels))


def _normalize_rel(p: str, root: str) -> str:
    """Repo-relative posix form. Absolute paths are mapped back under
    ``root`` — the file keys MUST be repo-relative or every path-keyed
    rule (the engine hot-path file, the schema home, the paged_kv
    exemption) silently misses them."""
    if os.path.isabs(p):
        rel = os.path.relpath(p, root)
        if rel == ".." or rel.startswith(".." + os.sep):
            raise LintInputError(f"path outside the linted tree: {p}")
        p = rel
    return os.path.normpath(p).replace(os.sep, "/")


def load_project(root: str, paths: Optional[Sequence[str]] = None,
                 package: str = PACKAGE,
                 extras: Sequence[str] = DEFAULT_EXTRAS) -> Project:
    """Parse the tree rooted at ``root``. ``paths`` (repo-relative)
    SELECTS files to report on — the whole tree still loads, because
    the cross-file rules (schema contract, env registry, import
    reachability) are only correct against full context; the runner
    filters findings down to the selection. Paths are normalized +
    sorted, so caller ordering can never leak into output."""
    root = os.path.abspath(root)
    rels = _discover(root, package=package, extras=extras)
    requested = None
    if paths is not None:
        requested = sorted({_normalize_rel(p, root) for p in paths})
        for rel in requested:
            if not os.path.isfile(os.path.join(root,
                                               rel.replace("/", os.sep))):
                raise LintInputError(f"no such file: {rel}")
            if not rel.endswith(".py"):
                raise LintInputError(f"not a python source: {rel}")
        rels = sorted(set(rels) | set(requested))
    files = {rel: _load_file(root, rel) for rel in rels}
    readme = None
    readme_path = os.path.join(root, "README.md")
    if os.path.isfile(readme_path):
        with open(readme_path, "r", encoding="utf-8") as f:
            readme = f.read()
    return Project(root, files, readme, requested=requested)


# ---------------------------------------------------------------------------
# Running
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]        # every finding, suppressed included

    @property
    def active(self) -> list[Finding]:
        return [f for f in self.findings if not f.suppressed]

    @property
    def suppressed(self) -> list[Finding]:
        return [f for f in self.findings if f.suppressed]

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.active:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def _unused_pragmas(files: dict[str, SourceFile],
                    findings: list[Finding],
                    checkable: set[str]) -> list[Finding]:
    """Pragma findings for every ``allow[rid]`` whose rule produced no
    finding on its governed line. Runs against PRE-filter findings (a
    path selection must not turn a used pragma into an "unused" one)
    and only judges pragmas for rules in ``checkable`` — rules the
    caller actually ran on input they can fire on. A pragma for a rule
    outside the selection is not vouching for anything this run can
    see, so it is left alone (ids unknown to the catalog stay silently
    ignored, as before)."""
    fired = {(f.path, f.line, f.rule) for f in findings}
    out: list[Finding] = []
    for path in sorted(files):
        for line in sorted(files[path].pragmas):
            for rid, _reason in files[path].pragmas[line]:
                if rid in checkable and (path, line, rid) not in fired:
                    out.append(Finding(
                        PRAGMA_RULE, path, line,
                        f"unused pragma allow[{rid}]: {rid} does not "
                        f"fire on this line — remove the stale "
                        f"suppression before it hides a real finding"))
    return out


def _apply_pragmas(project: Project,
                   findings: list[Finding]) -> list[Finding]:
    out = []
    for f in findings:
        sf = project.files.get(f.path)
        reason = None
        if sf is not None and f.rule != PRAGMA_RULE:
            for rule_id, why in sf.pragmas.get(f.line, ()):
                if rule_id == f.rule:
                    reason = why
                    break
        if reason is not None:
            f = dataclasses.replace(f, suppressed=True, reason=reason)
        out.append(f)
    return out


def run_lint(root: str, paths: Optional[Sequence[str]] = None,
             rules: Optional[Sequence[str]] = None,
             package: str = PACKAGE,
             extras: Sequence[str] = DEFAULT_EXTRAS) -> LintResult:
    """Lint the tree: load, run the selected rules (default all), fold
    in pragma-hygiene findings, apply suppressions, sort."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (
        RULES,
    )

    project = load_project(root, paths=paths, package=package,
                           extras=extras)
    selected = sorted(RULES) if rules is None else sorted(set(rules))
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise LintInputError(f"unknown rule id(s): {', '.join(unknown)} "
                             f"(known: {', '.join(sorted(RULES))})")
    findings: list[Finding] = []
    for rid in selected:
        findings.extend(RULES[rid].check(project))
    findings.extend(_unused_pragmas(project.files, findings,
                                    checkable=set(selected)))
    for path in sorted(project.files):
        for line, msg in project.files[path].bad_pragmas:
            findings.append(Finding(PRAGMA_RULE, path, line, msg))
    if project.requested is not None:
        keep = set(project.requested)
        findings = [f for f in findings if f.path in keep]
    findings = _apply_pragmas(project, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return LintResult(findings)


def lint_text(text: str, name: str = "<stdin>",
              rules: Optional[Sequence[str]] = None) -> LintResult:
    """Lint one source snippet (the ``obsctl lint -`` stdin path).
    Only file-local rules apply — whole-project rules (import
    reachability, the env registry) need the tree and skip
    single-file input by construction."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (
        RULES,
    )

    sf = _make_source(name, text, module=None)
    project = Project(root=os.getcwd(), files={name: sf}, readme=None)
    selected = sorted(RULES) if rules is None else sorted(set(rules))
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise LintInputError(f"unknown rule id(s): {', '.join(unknown)} "
                             f"(known: {', '.join(sorted(RULES))})")
    findings: list[Finding] = []
    for rid in selected:
        findings.extend(RULES[rid].check(project))
    # only R2/R3 can fire on a bare snippet (R1's zones, R4's schema
    # home, R5's README and R6's pool home are all tree-anchored), so
    # only their pragmas are judged for staleness here
    findings.extend(_unused_pragmas({name: sf}, findings,
                                    checkable={"R2", "R3"}
                                    & set(selected)))
    for line, msg in sf.bad_pragmas:
        findings.append(Finding(PRAGMA_RULE, name, line, msg))
    findings = _apply_pragmas(project, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return LintResult(findings)


# ---------------------------------------------------------------------------
# Rendering (both byte-deterministic)
# ---------------------------------------------------------------------------

LINT_FORMAT_VERSION = 1


def render_json(result: LintResult) -> str:
    doc = {
        "graftlint_version": LINT_FORMAT_VERSION,
        "findings": [
            {"rule": f.rule, "path": f.path, "line": f.line,
             "message": f.message}
            for f in result.active
        ],
        "suppressed": [
            {"rule": f.rule, "path": f.path, "line": f.line,
             "message": f.message, "reason": f.reason}
            for f in result.suppressed
        ],
        "counts": result.counts(),
        "total": len(result.active),
    }
    return json.dumps(doc, indent=2, sort_keys=True) + "\n"


def render_text(result: LintResult, verbose: bool = False) -> str:
    lines = [f.render() for f in result.active]
    if verbose:
        lines += [f.render() for f in result.suppressed]
    n, s = len(result.active), len(result.suppressed)
    lines.append(f"graftlint: {n} finding(s), {s} suppressed")
    return "\n".join(lines) + "\n"


# -- shared AST helpers (used by rules.py) ----------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, None for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def walk_functions(tree: ast.AST) -> Iterable[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node       # type: ignore[misc]


def non_docstring_constants(tree: ast.Module
                            ) -> Iterable[tuple[str, int]]:
    """Every string-literal constant with its line, docstrings
    excluded (a knob merely *mentioned* in prose is not a read)."""
    doc_nodes = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            body = getattr(node, "body", [])
            if (body and isinstance(body[0], ast.Expr)
                    and isinstance(body[0].value, ast.Constant)
                    and isinstance(body[0].value.value, str)):
                doc_nodes.add(id(body[0].value))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and id(node) not in doc_nodes):
            yield node.value, node.lineno
