#!/usr/bin/env python
"""graftlint — in-repo static analysis enforcing the engine's
compile-flatness, host-sync, and contract invariants.

Usage::

    # lint the whole tree (package + scripts/ + launch.py)
    python scripts/graftlint.py
    # specific files, machine-readable output
    python scripts/graftlint.py huggingface_sagemaker_tensorflow_distributed_tpu/serve/engine.py --format json
    # lint a snippet from stdin (file-local rules only)
    cat patch.py | python scripts/graftlint.py -
    # the rule catalog
    python scripts/graftlint.py --list-rules

Rules (R1–R6; see README "Static analysis" for the full catalog):
jax-free zones, host-sync-in-hot-path, jit-static-key-hygiene,
telemetry-field-contract, env-knob-registry, blockmanager-discipline.
Suppress one finding with ``# graftlint: allow[R2] reason`` on the
offending line (or alone on the line above); the reason is mandatory.

Exit codes match ``obsctl diff``: 0 clean, 1 bad input, 2 unsuppressed
findings. Output is byte-deterministic for a given tree.

Pure stdlib by construction (``analysis`` imports nothing outside the
standard library): runs on boxes without jax — and rule R1 keeps it
that way.
"""

from __future__ import annotations

import argparse
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (  # noqa: E402
    LintInputError,
    lint_text,
    render_json,
    render_text,
    run_lint,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (  # noqa: E402
    RULES,
)


def _list_rules() -> int:
    for rid in sorted(RULES):
        rule = RULES[rid]
        print(f"{rid}  {rule.title}")
        print(f"    {rule.rationale}")
    return 0


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(prog="graftlint",
                                     description=__doc__)
    parser.add_argument("paths", nargs="*",
                        help="repo-relative files to lint (default: "
                             "the whole tree); '-' reads one source "
                             "from stdin and runs the file-local "
                             "rules")
    parser.add_argument("--format", choices=("text", "json"),
                        default="text")
    parser.add_argument("--rules", default=None,
                        help="comma-separated rule ids to run "
                             "(default: all)")
    parser.add_argument("--root", default=_REPO_ROOT,
                        help=argparse.SUPPRESS)
    parser.add_argument("--verbose", action="store_true",
                        help="also list suppressed findings (text "
                             "format; JSON always carries them)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        return _list_rules()

    rules = ([r.strip() for r in args.rules.split(",") if r.strip()]
             if args.rules else None)
    try:
        if args.paths == ["-"]:
            result = lint_text(sys.stdin.read(), rules=rules)
        elif "-" in args.paths:
            print("graftlint: '-' cannot be combined with file paths",
                  file=sys.stderr)
            return 1
        else:
            result = run_lint(args.root, paths=args.paths or None,
                              rules=rules)
    except LintInputError as e:
        print(f"graftlint: {e}", file=sys.stderr)
        return 1
    if args.format == "json":
        sys.stdout.write(render_json(result))
    else:
        sys.stdout.write(render_text(result, verbose=args.verbose))
    return 2 if result.active else 0


if __name__ == "__main__":
    sys.exit(main())
