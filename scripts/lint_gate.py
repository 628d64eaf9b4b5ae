#!/usr/bin/env python
"""Lint gate for scripts/tier1.sh (ISSUE 4 satellite).

Two stages, both mandatory:

**Generic lint.**  Prefers a real linter when the environment has
one (``ruff check``, then ``pyflakes``); otherwise falls back to the
bundled minimal checker so the gate is never silently skipped:

- every file must parse (``ast.parse`` — a stronger version of the
  ``compileall`` syntax gate, with real line numbers);
- module-level imports must be USED: a name bound by ``import``/
  ``from .. import`` that never occurs again in the file is dead
  weight at best and a refactor leftover at worst.  Conservative by
  construction: usage is a word-boundary text search (so ``__all__``
  strings, docstring references and string-typed annotations all
  count), ``__init__.py`` re-export files are skipped, and a
  ``# noqa`` on the import line opts out.

**tmcheck** (ISSUE 12): the project-native static-analysis suite —
lock discipline, ABBA lock-order, held-lock side effects, JAX
hot-path sanitizer (``python -m theanompi_tpu.analysis``; catalog in
docs/ANALYSIS.md).  Runs REGARDLESS of which generic linter ran —
ruff knows nothing about our lock registry.  ``--changed-only``
passes the fast mode through (files changed vs HEAD).

Exit 0 = clean, 1 = findings, 2 = could not run.
"""

from __future__ import annotations

import ast
import re
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
TARGETS = ["theanompi_tpu", "tests", "scripts"]


def _external_linter() -> int | None:
    """Run ruff or pyflakes when available; None = neither exists."""
    if shutil.which("ruff"):
        return subprocess.call(
            ["ruff", "check", *TARGETS], cwd=REPO
        )
    for probe in ("pyflakes",):
        if subprocess.call(
            [sys.executable, "-c", f"import {probe}"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ) == 0:
            return subprocess.call(
                [sys.executable, "-m", probe, *TARGETS], cwd=REPO
            )
    return None


def _bound_names(node: ast.stmt) -> list[tuple[str, int]]:
    """Names an import statement binds at module level."""
    out = []
    if isinstance(node, ast.Import):
        for a in node.names:
            name = a.asname or a.name.split(".")[0]
            out.append((name, node.lineno))
    elif isinstance(node, ast.ImportFrom):
        if node.module == "__future__":
            return []  # compiler directive, used by existing
        for a in node.names:
            if a.name == "*":
                continue
            out.append((a.asname or a.name, node.lineno))
    return out


def _check_file(path: Path) -> list[str]:
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    if path.name == "__init__.py":
        return []  # re-export surface: imports ARE the point
    lines = src.splitlines()
    findings = []
    for node in tree.body:
        for name, lineno in _bound_names(node):
            if name.startswith("_"):
                continue
            line = lines[lineno - 1] if lineno <= len(lines) else ""
            if "noqa" in line:
                continue
            # word-boundary occurrences anywhere but the import
            # statement's own lines
            node_lines = set(
                range(node.lineno, (node.end_lineno or node.lineno) + 1)
            )
            pat = re.compile(rf"\b{re.escape(name)}\b")
            used = any(
                pat.search(text)
                for i, text in enumerate(lines, 1)
                if i not in node_lines
            )
            if not used:
                findings.append(
                    f"{path.relative_to(REPO)}:{lineno}: "
                    f"unused import {name!r}"
                )
    return findings


def _generic_lint() -> int:
    rc = _external_linter()
    if rc is not None:
        return rc
    findings = []
    for target in TARGETS:
        p = REPO / target
        files = [p] if p.suffix == ".py" else sorted(p.rglob("*.py"))
        for f in files:
            if "__pycache__" in f.parts:
                continue
            findings.extend(_check_file(f))
    for f in findings:
        print(f)
    if findings:
        print(f"lint_gate: {len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


def _tmcheck(changed_only: bool) -> int:
    """The project-native suite as a subprocess: its jax import must
    not slow the generic stage, and a crash is exit 2, not a
    traceback through the gate."""
    cmd = [sys.executable, "-m", "theanompi_tpu.analysis"]
    if changed_only:
        cmd.append("--changed-only")
    try:
        return subprocess.call(cmd, cwd=REPO)
    except OSError:
        return 2


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    changed_only = "--changed-only" in argv
    rc_lint = _generic_lint()
    rc_tm = _tmcheck(changed_only)
    if rc_tm != 0:
        print("lint_gate: tmcheck stage failed "
              "(see findings above; docs/ANALYSIS.md has the "
              "catalog)", file=sys.stderr)
    return max(rc_lint, rc_tm)


if __name__ == "__main__":
    raise SystemExit(main())
