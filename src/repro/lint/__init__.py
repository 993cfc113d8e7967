"""``repro.lint`` — the repo's own static-analysis pass.

Six static checkers over the codebase's load-bearing invariants, plus a
runtime sanitizer:

==============  ============================================================
checker         invariant
==============  ============================================================
``purity``      nothing host-side (clocks, ``np.random``, ``.item()``,
                global mutation, un-pragma'd callbacks) is reachable from
                jitted roots
``compile-key`` every ``ExperimentSpec`` field is an ``EngineKey`` field,
                ``engine`` or declared runtime-only (the PR 6/7/8
                stale-artifact bug class)
``pytree``      ``EnvParams`` / ``FaultTrace`` / ``CapabilityBundle`` match
                their declared shape schemas; construction is total
``taps``        every ``obs.tap("...")`` literal is a declared tap name
``units``       units of measure propagate consistently through the
                simulator core: no ``$/kWh + kg/kWh``, no bare magic scale
                factors, ``_usd``/``_kg``/``_ms`` metric keys carry their
                suffix unit, declared signatures/field tables hold
``bounds``      traced divisions are guarded positive; routing tensors are
                normalized along the declared simplex axis; nonnegativity
                tables match the pytree schemas
``pragma``      suppressions are justified and still suppress something
==============  ============================================================

Run it: ``python -m repro.lint`` (or ``make lint``). The static side never
imports the modules it checks — no jax required. Suppressions:
``# lint: host-ok(reason)`` on a deliberate host call in traced code,
``# lint: runtime-only(reason)`` on a spec field that only selects runtime
inputs, ``# lint: unit(U)`` declaring a conversion constant's unit,
``# lint: unit-ok(reason)`` on a deliberate unit/bounds escape.

Runtime helpers (these do touch jax, lazily): :func:`validate` checks a
live pytree against its schema (shape unification, float64/weak-type
leaves); :func:`validate_bounds` checks nonnegativity/simplex bounds;
:func:`expect_compiles` / :func:`trace_count` pin compile counts in tests.
"""
from __future__ import annotations

from typing import List, Optional

from . import bounds, compile_key, purity, pytrees, taps, units
from .bounds import validate_bounds
from .project import Pragma, Project, Violation
from .pytrees import SCHEMAS, validate
from .runtime import expect_compiles, trace_count

__all__ = [
    "CHECKERS", "Pragma", "Project", "SCHEMAS", "Violation",
    "expect_compiles", "lint_project", "lint_repo", "trace_count",
    "validate", "validate_bounds",
]

#: slug -> checker, in report order
CHECKERS = {
    "purity": purity.check,
    "compile-key": compile_key.check,
    "pytree": pytrees.check,
    "taps": taps.check,
    "units": units.check,
    "bounds": bounds.check,
}


def lint_project(project: Project) -> List[Violation]:
    """Run every checker over an already-loaded project. Pragma accounting
    (stale/malformed suppressions) runs last, once all checkers have had
    the chance to consume their pragmas."""
    out: List[Violation] = list(project.parse_violations())
    for check in CHECKERS.values():
        out.extend(check(project))
    out.extend(project.pragma_violations())
    return sorted(out, key=lambda v: (v.path, v.line, v.check, v.message))


def lint_repo(root: Optional[str] = None) -> List[Violation]:
    """Load the repo at ``root`` (default: this checkout) and lint it."""
    project = Project.load(root or Project.default_root())
    return lint_project(project)
