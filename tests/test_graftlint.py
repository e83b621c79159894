"""graftlint tests (ISSUE 15): one positive + one negative fixture per
rule (R1–R7), pragma suppression + mandatory-reason hygiene, byte
determinism across input orderings, the CLI exit-code contract
(0 clean / 1 bad input / 2 findings, matching ``obsctl diff``), and —
the teeth — the tier-1 gate that runs the full linter over the real
tree with zero unsuppressed findings, plus R1's static jax-free-zone
reachability as the PRIMARY no-jax gate (the subprocess poison runs
are now the slow-tier backstop).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (
    PACKAGE,
    LintInputError,
    lint_text,
    load_project,
    render_json,
    render_text,
    run_lint,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (
    RULES,
    check_r1,
    r1_reachability,
    r1_zone_roots,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GRAFTLINT = os.path.join(_REPO, "scripts", "graftlint.py")
_OBSCTL = os.path.join(_REPO, "scripts", "obsctl.py")


def make_tree(tmp_path, files, readme=None):
    """A minimal repo layout the loader accepts: files are
    repo-relative paths under a package named like the real one."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    pkg_init = tmp_path / PACKAGE / "__init__.py"
    if not pkg_init.exists():
        pkg_init.parent.mkdir(parents=True, exist_ok=True)
        pkg_init.write_text("")
    if readme is not None:
        (tmp_path / "README.md").write_text(textwrap.dedent(readme))
    return str(tmp_path)


def active(result, rule=None):
    out = [f for f in result.findings if not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# -- R1: jax-free zones -------------------------------------------------------

def test_r1_fires_on_transitive_import_time_jax(tmp_path):
    root = make_tree(tmp_path, {
        f"{PACKAGE}/obs/__init__.py": "from {p} import util\n".format(
            p=PACKAGE),
        f"{PACKAGE}/util.py": "import jax\n",
    })
    hits = active(run_lint(root, rules=["R1"]), "R1")
    assert len(hits) == 1
    assert hits[0].path == f"{PACKAGE}/util.py"
    assert "jax" in hits[0].message and "obs" in hits[0].message

def test_r1_lazy_import_is_legal(tmp_path):
    root = make_tree(tmp_path, {
        f"{PACKAGE}/obs/__init__.py": (
            "def heavy():\n    import jax\n    return jax\n"),
    })
    assert active(run_lint(root, rules=["R1"]), "R1") == []


# -- R2: host syncs on the hot path -------------------------------------------

_ENGINE = f"{PACKAGE}/serve/engine.py"

def test_r2_fires_on_hot_loop_fetch(tmp_path):
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(pending):
            return jax.device_get(pending)
        """})
    hits = active(run_lint(root, rules=["R2"]), "R2")
    assert len(hits) == 1 and "_commit_decode" in hits[0].message

def test_r2_matches_method_form_block_until_ready(tmp_path):
    # the idiomatic ARRAY-METHOD sync form blocks just like the
    # module-call form and must not slip through
    root = make_tree(tmp_path, {_ENGINE: """\
        def _dispatch_decode(pending):
            pending.nxt.block_until_ready()
            return pending
        """})
    hits = active(run_lint(root, rules=["R2"]), "R2")
    assert len(hits) == 1 and ".block_until_ready()" in hits[0].message

def test_r2_cold_path_fetch_is_legal(tmp_path):
    # the same fetch outside the hot-loop allowlist (warmup) is fine
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def warmup(tok):
            jax.block_until_ready(tok)
            return jax.device_get(tok)
        """})
    assert active(run_lint(root, rules=["R2"]), "R2") == []


# -- R3: jit static-key hygiene -----------------------------------------------

def test_r3_fires_on_undeclared_and_non_literal_statics(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": """\
        import functools
        import jax

        step = jax.jit(lambda x: x)
        spec = functools.partial(
            jax.jit, static_argnums=tuple(range(3)))
        """})
    hits = active(run_lint(root, rules=["R3"]), "R3")
    assert len(hits) == 2
    assert any("no static_argnums" in f.message for f in hits)
    assert any("not a literal" in f.message for f in hits)

def test_r3_literal_statics_are_legal(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": """\
        import functools
        import jax

        step = jax.jit(lambda m, x: x, static_argnums=(0,))
        fam = functools.partial(jax.jit,
                                static_argnames=("model", "width"))
        """})
    assert active(run_lint(root, rules=["R3"]), "R3") == []


# -- R4: telemetry field contract ---------------------------------------------

_SCHEMA = f"{PACKAGE}/obs/schema.py"
_SCHEMA_SRC = """\
    REQUIRED_FIELDS = {"serve": {"event": (str,)}}
    OPTIONAL_FIELDS = {"serve": {"request": (int,), "tokens": (int,)}}
    """

def test_r4_fires_on_undeclared_field(tmp_path):
    root = make_tree(tmp_path, {
        _SCHEMA: _SCHEMA_SRC,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "obs.serve('admit', request=1, slot=3)\n".format(p=PACKAGE)),
    })
    hits = active(run_lint(root, rules=["R4"]), "R4")
    assert len(hits) == 1 and "'slot'" in hits[0].message

def test_r4_declared_fields_and_dynamic_kwargs_are_legal(tmp_path):
    root = make_tree(tmp_path, {
        _SCHEMA: _SCHEMA_SRC,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "extra = {{}}\n"
            "obs.serve('finish', request=1, tokens=2, **extra)\n"
            .format(p=PACKAGE)),
    })
    assert active(run_lint(root, rules=["R4"]), "R4") == []

_SCHEMA_SRC_EVENTS = """\
    REQUIRED_FIELDS = {"serve": {"event": (str,)}}
    OPTIONAL_FIELDS = {"serve": {"request": (int,), "tokens": (int,)}}
    SERVE_EVENTS = ("admit", "finish")
    """

def test_r4_fires_on_undeclared_event_kind(tmp_path):
    """ISSUE 19: an emitter inventing a serve-event KIND outside the
    schema's SERVE_EVENTS vocabulary is the same silent drift for
    consumers switching on `event` as an undeclared field is for
    field type-checkers."""
    root = make_tree(tmp_path, {
        _SCHEMA: _SCHEMA_SRC_EVENTS,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "obs.serve('teleport', request=1)\n".format(p=PACKAGE)),
    })
    hits = active(run_lint(root, rules=["R4"]), "R4")
    assert len(hits) == 1 and "'teleport'" in hits[0].message
    assert "SERVE_EVENTS" in hits[0].message

def test_r4_declared_kinds_dynamic_kinds_and_no_registry_are_legal(
        tmp_path):
    # declared kinds and a non-literal kind (not statically checkable)
    root = make_tree(tmp_path, {
        _SCHEMA: _SCHEMA_SRC_EVENTS,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "kind = 'admit'\n"
            "obs.serve('finish', request=1)\n"
            "obs.serve(kind, request=1)\n".format(p=PACKAGE)),
    })
    assert active(run_lint(root, rules=["R4"]), "R4") == []
    # a schema without SERVE_EVENTS (pre-19 trees): kinds unchecked,
    # field checks still live
    root = make_tree(tmp_path / "old", {
        _SCHEMA: _SCHEMA_SRC,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "obs.serve('teleport', request=1)\n".format(p=PACKAGE)),
    })
    assert active(run_lint(root, rules=["R4"]), "R4") == []


# -- R5: env-knob registry ----------------------------------------------------

_README = """\
    # x

    | var | meaning |
    |---|---|
    | `HSTD_DOCUMENTED` | a knob |
    | `HSTD_ORPHANED` | stale row |
    """

def test_r5_fires_both_directions(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": """\
        import os
        A = os.environ.get("HSTD_DOCUMENTED", "")
        B = os.environ.get("HSTD_UNDOCUMENTED", "")
        """}, readme=_README)
    hits = active(run_lint(root, rules=["R5"]), "R5")
    assert len(hits) == 2
    undoc = [f for f in hits if "HSTD_UNDOCUMENTED" in f.message]
    orphan = [f for f in hits if "HSTD_ORPHANED" in f.message]
    assert undoc and undoc[0].path == f"{PACKAGE}/m.py"
    assert orphan and orphan[0].path == "README.md"

def test_r5_docstring_mention_is_not_a_read(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": '''\
        """Reads ``HSTD_NOT_REALLY_A_READ`` — prose only."""
        import os
        A = os.environ.get("HSTD_DOCUMENTED", "")
        ''' }, readme="| `HSTD_DOCUMENTED` | a knob |\n")
    assert active(run_lint(root, rules=["R5"]), "R5") == []


# -- R6: BlockManager discipline ----------------------------------------------

def test_r6_fires_on_raw_free_and_refcount_poke(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/serve/scheduler.py": """\
        def evict(blocks, table):
            blocks.free(table)
            blocks._refs[table[0]] -= 1
        """})
    hits = active(run_lint(root, rules=["R6"]), "R6")
    assert len(hits) == 2
    assert any(".free()" in f.message for f in hits)
    assert any("_refs" in f.message for f in hits)

def test_r6_release_and_manager_internals_are_legal(tmp_path):
    root = make_tree(tmp_path, {
        f"{PACKAGE}/serve/scheduler.py": (
            "def evict(blocks, table):\n"
            "    blocks.release(table)\n"),
        # the manager itself may touch its own refcounts, of course
        f"{PACKAGE}/serve/paged_kv.py": (
            "class BlockManager:\n"
            "    def release(self, t):\n"
            "        self._refs[t[0]] -= 1\n"
            "        self.free(t)\n"),
    })
    assert active(run_lint(root, rules=["R6"]), "R6") == []


# -- R7: admission policy stays jax-free --------------------------------------

def test_r7_fires_on_transitive_import_time_jax(tmp_path):
    root = make_tree(tmp_path, {
        f"{PACKAGE}/serve/policy.py": "from {p}.serve import kv\n".format(
            p=PACKAGE),
        f"{PACKAGE}/serve/kv.py": "import jax\n",
    })
    hits = active(run_lint(root, rules=["R7"]), "R7")
    assert len(hits) == 1
    assert hits[0].path == f"{PACKAGE}/serve/kv.py"
    assert "jax" in hits[0].message and "policy" in hits[0].message

def test_r7_host_side_policy_is_legal(tmp_path):
    root = make_tree(tmp_path, {
        f"{PACKAGE}/serve/policy.py": (
            "import math\n"
            "def key(req, now):\n"
            "    return (0, now, req.rid)\n"),
        # jax elsewhere in serve/ is fine — R7 roots at policy.py only
        f"{PACKAGE}/serve/engine.py": "import jax\n",
    })
    assert active(run_lint(root, rules=["R7"]), "R7") == []


# -- pragmas ------------------------------------------------------------------

def test_pragma_suppresses_with_reason_trailing_and_standalone(tmp_path):
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(p):
            # graftlint: allow[R2] deferred commit fetch, safe by design
            a = jax.device_get(p)
            b = jax.device_get(p)  # graftlint: allow[R2] same fetch, trailing form
            return a, b
        """})
    result = run_lint(root, rules=["R2"])
    assert active(result) == []
    assert len(result.suppressed) == 2
    assert all(f.reason for f in result.suppressed)

def test_pragma_without_reason_is_itself_a_finding(tmp_path):
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return jax.device_get(p)  # graftlint: allow[R2]
        """})
    result = run_lint(root, rules=["R2"])
    rules = sorted(f.rule for f in active(result))
    # the reasonless pragma does NOT suppress, and is flagged itself
    assert rules == ["R2", "pragma"]

def test_pragma_in_string_literal_is_inert(tmp_path):
    # pragma syntax QUOTED in prose (docstring/string) is neither a
    # phantom suppression nor a malformed-pragma finding — only real
    # comment tokens count
    root = make_tree(tmp_path, {_ENGINE: '''\
        """Suppress with `# graftlint: allow[R2] reason` — and a
        reasonless example: `# graftlint: allow[R2]` (also inert)."""
        import jax
        DOC = "# graftlint: allow[R2] not a comment either"
        def _commit_decode(p):
            return jax.device_get(p)
        '''})
    result = run_lint(root, rules=["R2"])
    assert [f.rule for f in active(result)] == ["R2"]
    assert result.suppressed == []

def test_pragma_for_wrong_rule_does_not_suppress(tmp_path):
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return jax.device_get(p)  # graftlint: allow[R3] wrong rule id
        """})
    assert len(active(run_lint(root, rules=["R2"]), "R2")) == 1


def test_unused_pragma_is_itself_a_finding(tmp_path):
    """ISSUE 16: a pragma whose rule does NOT fire on its line is a
    `pragma` finding — stale suppressions are landmines that silently
    swallow the next real finding on that line. The fixture pair: the
    same pragma on a line where R2 DOES fire stays a clean, counted
    suppression."""
    used = make_tree(tmp_path / "used", {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return jax.device_get(p)  # graftlint: allow[R2] deferred fetch
        """})
    result = run_lint(used, rules=["R2"])
    assert active(result) == []
    assert len(result.suppressed) == 1

    stale = make_tree(tmp_path / "stale", {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return p + 1  # graftlint: allow[R2] fetch long since removed
        """})
    result = run_lint(stale, rules=["R2"])
    assert [f.rule for f in active(result)] == ["pragma"]
    assert "unused pragma allow[R2]" in active(result)[0].message
    assert result.suppressed == []


def test_unused_pragma_only_flagged_for_selected_rules(tmp_path):
    """A pragma can only be judged stale by RUNNING its rule: under
    --rules R2 an allow[R3] pragma is unjudgeable (R3 never ran) and
    must not be flagged; selecting R3 over the same tree flags it."""
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return p + 1  # graftlint: allow[R3] stale sync claim
        """})
    assert active(run_lint(root, rules=["R2"])) == []
    assert [f.rule for f in active(run_lint(root, rules=["R3"]))] \
        == ["pragma"]


def test_unused_pragma_detected_on_stdin_snippets():
    """The `obsctl lint -` path judges stale pragmas too — but only
    for the rules that CAN fire on a bare snippet (R2/R3); a zone or
    registry pragma is not judgeable without the tree."""
    result = lint_text(
        "def _step(x):\n"
        "    return x + 1  # graftlint: allow[R2] no fetch here anymore\n")
    assert [f.rule for f in active(result)] == ["pragma"]
    # the same pragma id on a genuinely-firing line suppresses cleanly
    fired = lint_text(
        "import jax\n"
        "def _step(x):\n"
        "    return jax.device_get(x)  # graftlint: allow[R2] safe fetch\n")
    assert active(fired) == []
    assert len(fired.suppressed) == 1
    # tree-anchored rules (e.g. R1 zones) are never judged on stdin
    zone = lint_text("x = 1  # graftlint: allow[R1] zone claim\n")
    assert active(zone) == []


# -- determinism --------------------------------------------------------------

def test_output_byte_identical_across_input_orderings(tmp_path):
    files = {
        f"{PACKAGE}/serve/engine.py": (
            "import jax\n\ndef _decode_all(x):\n"
            "    return jax.device_get(x)\n"),
        f"{PACKAGE}/a.py": "import jax\nf = jax.jit(lambda x: x)\n",
        f"{PACKAGE}/obs/__init__.py": "import jax\n",
    }
    root = make_tree(tmp_path, files)
    paths = sorted(files) + [f"{PACKAGE}/__init__.py"]
    fwd = run_lint(root, paths=list(paths))
    rev = run_lint(root, paths=list(reversed(paths)))
    assert render_json(fwd) == render_json(rev)
    assert render_text(fwd) == render_text(rev)
    assert render_json(fwd) == render_json(
        run_lint(root, paths=list(paths)))   # and stable across runs


# -- bad input ----------------------------------------------------------------

def test_unparseable_source_is_bad_input(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": "def broken(:\n"})
    with pytest.raises(LintInputError):
        run_lint(root)

def test_missing_path_is_bad_input(tmp_path):
    root = make_tree(tmp_path, {})
    with pytest.raises(LintInputError):
        run_lint(root, paths=["nope.py"])

def test_unknown_rule_is_bad_input(tmp_path):
    root = make_tree(tmp_path, {})
    with pytest.raises(LintInputError):
        run_lint(root, rules=["R99"])


# -- stdin / file-local mode --------------------------------------------------

def test_lint_text_runs_file_local_rules():
    result = lint_text(
        "import jax\n"
        "def _commit_decode(p):\n"
        "    return jax.device_get(p)\n")
    assert [f.rule for f in active(result)] == ["R2"]

def test_lint_text_clean_snippet():
    assert active(lint_text("x = 1\n")) == []

def test_lint_text_unknown_rule_is_bad_input():
    # same 0/1/2 contract as file mode: a typoed --rules must not
    # produce a vacuous clean pass on stdin
    with pytest.raises(LintInputError):
        lint_text("x = 1\n", rules=["R99"])

def test_explicit_paths_see_full_tree_context(tmp_path):
    """Linting a file SELECTION keeps cross-file rules correct: the
    whole tree loads for context (schema for R4, README/code for R5),
    findings filter to the selection — so per-file lint of a clean
    tree is clean, R5 orphan noise from unselected files included."""
    root = make_tree(tmp_path, {
        _SCHEMA: _SCHEMA_SRC,
        f"{PACKAGE}/serve/engine.py": (
            "from {p} import obs\n"
            "obs.serve('admit', request=1, slot=3)\n".format(p=PACKAGE)),
        f"{PACKAGE}/other.py": (
            "import os\nA = os.environ.get('HSTD_DOCUMENTED')\n"),
    }, readme="| `HSTD_DOCUMENTED` | a knob |\n")
    # R4 needs the schema even though only engine.py is selected
    hits = run_lint(root, paths=[f"{PACKAGE}/serve/engine.py"])
    assert [f.rule for f in active(hits)] == ["R4"]
    # R5's readme row is satisfied by the UNSELECTED other.py — no
    # orphan false positive; and nothing anchors in unselected files
    assert all(f.path == f"{PACKAGE}/serve/engine.py"
               for f in active(hits))
    clean = run_lint(root, paths=[f"{PACKAGE}/other.py"])
    assert active(clean) == []

def test_absolute_path_selection_keys_repo_relative(tmp_path):
    """An ABSOLUTE path argument must resolve to the same repo-relative
    key as the relative form — otherwise every path-keyed rule (R2's
    engine file, R4's schema home, R6's paged_kv exemption) silently
    misses the selected file and real violations report clean."""
    root = make_tree(tmp_path, {_ENGINE: """\
        import jax

        def _commit_decode(p):
            return jax.device_get(p)
        """})
    rel = run_lint(root, paths=[_ENGINE], rules=["R2"])
    abs_ = run_lint(root, paths=[os.path.join(root, *_ENGINE.split("/"))],
                    rules=["R2"])
    assert [f.rule for f in active(abs_)] == ["R2"]
    assert render_json(abs_) == render_json(rel)
    with pytest.raises(LintInputError):
        run_lint(root, paths=[os.path.join(os.path.dirname(root),
                                           "outside.py")])

def test_cli_single_file_on_clean_tree_is_clean():
    # the docstring's own example usage: per-file lint of the real
    # tree must not manufacture findings from the partial view
    proc = _cli([f"{PACKAGE}/serve/engine.py", "--format", "json"])
    assert proc.returncode == 0, proc.stdout
    doc = json.loads(proc.stdout)
    assert doc["total"] == 0
    assert doc["suppressed"]          # engine's allow[] sites report


# -- CLI exit codes (the obsctl-diff shape) -----------------------------------

def _cli(args, stdin=None, cwd=_REPO):
    return subprocess.run([sys.executable, _GRAFTLINT, *args],
                          input=stdin, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, cwd=cwd)

def test_cli_clean_tree_exits_0_findings_exit_2(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": "x = 1\n"})
    assert _cli(["--root", root]).returncode == 0
    root2 = make_tree(tmp_path / "dirty", {
        f"{PACKAGE}/m.py": "import jax\nf = jax.jit(lambda x: x)\n"})
    proc = _cli(["--root", root2, "--format", "json"])
    assert proc.returncode == 2
    doc = json.loads(proc.stdout)
    assert doc["total"] == 1 and doc["counts"] == {"R3": 1}

def test_cli_bad_input_exits_1(tmp_path):
    root = make_tree(tmp_path, {f"{PACKAGE}/m.py": "def broken(:\n"})
    proc = _cli(["--root", root])
    assert proc.returncode == 1 and "syntax error" in proc.stderr

def test_cli_stdin(tmp_path):
    proc = _cli(["-"], stdin="import jax\n"
                            "def _decode_all(x):\n"
                            "    return jax.device_get(x)\n")
    assert proc.returncode == 2
    assert "<stdin>" in proc.stdout

def test_obsctl_lint_subcommand_stdin_json():
    proc = subprocess.run(
        [sys.executable, _OBSCTL, "lint", "-", "--format", "json"],
        input="x = 1\n", stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=_REPO)
    assert proc.returncode == 0
    assert json.loads(proc.stdout)["total"] == 0


# -- the real tree: tier-1 gates ----------------------------------------------

def test_full_package_lints_clean():
    """THE gate: zero unsuppressed findings over the installed tree,
    and every suppression carries a reason string."""
    result = run_lint(_REPO)
    assert active(result) == [], "\n" + "\n".join(
        f.render() for f in active(result))
    assert result.suppressed, "expected the documented allow[] sites"
    assert all(f.reason and f.reason.strip()
               for f in result.suppressed)

def test_no_jax_zone_static_reachability_primary_gate():
    """R1's static reachability IS the no-jax contract now: the
    import-time closure of obs/, analysis/ and the obsctl/schema CLIs
    contains no jax/flax import — complete over all imports, where the
    old subprocess poison run only covered imported-today paths (one
    subprocess smoke remains as the slow-tier backstop)."""
    project = load_project(_REPO)
    assert check_r1(project) == []
    reached = set(r1_reachability(project))
    # the gate is not vacuous: the zone really spans the jax-less
    # tooling surface, CLIs included
    for must in (f"{PACKAGE}/obs/report.py",
                 f"{PACKAGE}/obs/timeline.py",
                 f"{PACKAGE}/obs/schema.py",
                 f"{PACKAGE}/analysis/lint.py",
                 f"{PACKAGE}/analysis/rules.py",
                 "scripts/obsctl.py",
                 "scripts/check_telemetry_schema.py",
                 "scripts/graftlint.py"):
        assert must in reached, must
    assert f"{PACKAGE}/obs/__init__.py" in r1_zone_roots(project)

def test_rule_catalog_complete():
    assert sorted(RULES) == ["R1", "R2", "R3", "R4", "R5", "R6", "R7"]
    for rule in RULES.values():
        assert rule.title and rule.rationale

def test_linter_itself_runs_without_jax():
    """The poison contract extended over analysis/ (ISSUE 15
    satellite): the full CLI runs with jax import poisoned."""
    code = ("import sys, runpy; sys.modules['jax'] = None; "
            "sys.argv = ['graftlint', '--format', 'json']; "
            "runpy.run_path(%r, run_name='__main__')" % _GRAFTLINT)
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, proc.stdout
    assert json.loads(proc.stdout)["total"] == 0
