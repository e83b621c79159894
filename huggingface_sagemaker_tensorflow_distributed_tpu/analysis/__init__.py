"""``analysis``: in-repo static analysis (graftlint).

A stdlib-only, jax-less ``ast``-based lint pass that enforces the
engine's hardest-won invariants *in the diff* instead of minutes later
in a test: compile flatness (jit static-key hygiene), the
dispatch-ahead hot path's no-new-host-sync contract, the jax-free
tooling zones (``obs``/``obsctl``/this package itself), the typed
telemetry schema, the README env-knob registry, and BlockManager
refcount discipline.

Everything here must stay importable on boxes without jax — the same
contract ``obs`` carries, enforced by rule R1 over this package too.

Entry points: ``scripts/graftlint.py`` and ``obsctl lint``; the rule
engine is :func:`~.lint.run_lint`, the rules live in
:mod:`~.rules`.
"""

from __future__ import annotations

from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (  # noqa: F401
    Finding,
    LintInputError,
    LintResult,
    lint_text,
    load_project,
    render_json,
    render_text,
    run_lint,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (  # noqa: F401
    RULES,
)
