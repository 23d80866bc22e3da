"""jaxlint — a JAX-aware static-analysis pass for this codebase.

Generic linters cannot see the invariants this framework's correctness
and speed hinge on: no hidden host↔device syncs inside the hot loop, no
PRNG key reuse, no reads of donated buffers, no Python branching on
traced values or side effects under ``jit``, no unhashable static args,
no timing spans that measure async dispatch instead of device work, no
legacy or private jax spellings, and no
``PartitionSpec`` literals naming axes outside the mesh catalog. This
package codifies them as machine-checked rules. (The semantic layer —
validating a whole launch configuration abstractly — is the
``analysis.shardcheck`` subpackage, which DOES import jax and therefore
stays out of this module's imports.)

Entry points:

* ``tools/jaxlint.py`` — CLI (``--strict`` is the CI gate wired into
  ``format.sh``).
* :func:`lint_paths` / :func:`lint_source` — programmatic API used by
  ``tests/test_jaxlint.py``.

The engine is pure-stdlib AST analysis: importing it never touches a jax
backend, so it is safe (and fast) in any CI image.
"""

from pyrecover_tpu.analysis.engine import (
    DEFAULT_CONFIG,
    Finding,
    LintConfig,
    LintResult,
    lint_paths,
    lint_source,
)
from pyrecover_tpu.analysis.report import render_json, render_text, summarize
from pyrecover_tpu.analysis.rules import RULES

__all__ = [
    "DEFAULT_CONFIG",
    "Finding",
    "LintConfig",
    "LintResult",
    "RULES",
    "lint_paths",
    "lint_source",
    "render_json",
    "render_text",
    "summarize",
]
