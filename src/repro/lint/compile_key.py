"""Compile-key completeness: every trace-influencing ``ExperimentSpec``
field must join the engine compile key.

This is the PR 6/7/8 bug class, mechanized. Three consecutive PRs each
added a field that changes the traced program (``taps``, faultedness,
``workload``) and each forgot one copy of the key, so a stale jitted
artifact kept dispatching for specs that described a different program.
The key is now one frozen record, ``EngineKey`` in
``repro.core.experiment``, built by keyword, so no copy of it can drift.
What is left to check is that every ``ExperimentSpec`` field is classified
as exactly one of:

- a field of ``EngineKey``;
- ``engine``, which the key carries as its ``kind``;
- annotated ``# lint: runtime-only(reason)`` on its declaration line: it
  only selects runtime inputs. Such a field may not also be a field of
  ``EngineKey``.

Adding a spec field therefore forces a decision: join the key, or declare
(with a reason) that it never changes the program.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional

from .project import Project, Violation

EXPERIMENT_PATH = "src/repro/core/experiment.py"


def _fields(tree: ast.Module, cls: str) -> Optional[Dict[str, int]]:
    """Dataclass field name -> declaration line (None: no such class)."""
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == cls:
            return {n.target.id: n.lineno for n in node.body
                    if isinstance(n, ast.AnnAssign)
                    and isinstance(n.target, ast.Name)}
    return None


def check(project: Project) -> List[Violation]:
    sf = project.file(EXPERIMENT_PATH)
    if sf is None or sf.tree is None:
        return [Violation(EXPERIMENT_PATH, 1, "compile-key",
                          "cannot parse repro/core/experiment.py — the "
                          "compile-key contract is unverifiable")]
    rel = sf.relpath
    spec = _fields(sf.tree, "ExperimentSpec")
    key = _fields(sf.tree, "EngineKey")
    if spec is None or key is None:
        return [Violation(rel, 1, "compile-key",
                          "`ExperimentSpec` or `EngineKey` not found — the "
                          "compile-key contract this checker enforces has "
                          "moved; update repro.lint.compile_key")]
    out: List[Violation] = []
    for field, line in spec.items():
        runtime_only = project.pragma_at(rel, line, "runtime-only") is not None
        if runtime_only:
            project.use_pragma(rel, line)
        if field in key and runtime_only:
            out.append(Violation(
                rel, line, "compile-key",
                f"spec field `{field}` is declared runtime-only but IS an "
                "EngineKey field — one of the two is wrong"))
        elif field not in key and field != "engine" and not runtime_only:
            out.append(Violation(
                rel, line, "compile-key",
                f"ExperimentSpec field `{field}` is in no compile key: add "
                "it to EngineKey (and _engine_key) if it can change the "
                "traced program, or annotate the field "
                "`# lint: runtime-only(reason)` if it only selects runtime "
                "inputs (the PR 6/7/8 stale-artifact bug class)"))
    return out
