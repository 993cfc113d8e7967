"""``repro.lint``'s own tests: the tree is green, seeded regressions trip
the right checker (via ``Project.overlay`` — the working tree is never
touched), every static ``ExperimentSpec`` field forks the compile key while
runtime-only fields do not, the engines compile exactly once under repeated
identical ``run()`` calls, and live pytrees validate against their schemas.
"""
import jax.numpy as jnp
import pytest

from repro import lint
from repro.core import ExperimentSpec, run
from repro.core import experiment as X
from repro.core.force_directed import FDConfig
from repro.dcsim import env as E

PROJECT = lint.Project.load(lint.Project.default_root())

ENV = E.build_env(4, seed=0)
FD_CFG = FDConfig(iters=40)
SPEC = ExperimentSpec(technique="fd", objective="carbon", hours=6, cfg=FD_CFG)


def _seed(relpath: str, old: str, new: str) -> "lint.Project":
    """Overlay one source edit; the anchor must exist exactly once so the
    seeded regression is the edit we think it is."""
    sf = PROJECT.file(relpath)
    assert sf is not None, relpath
    assert sf.text.count(old) == 1, (relpath, old)
    return PROJECT.overlay(relpath, sf.text.replace(old, new))


def _hits(project, check: str, needle: str):
    return [v for v in lint.lint_project(project)
            if v.check == check and needle in v.message]


# ---------------------------------------------------------------------------
# the tree is green
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    violations = lint.lint_repo()
    assert violations == [], "\n".join(v.render() for v in violations)


# ---------------------------------------------------------------------------
# seeded regressions: each checker trips on the bug class it owns
# ---------------------------------------------------------------------------

def test_dropping_workload_from_static_key_trips_compile_key():
    # the key is the EngineKey record: drop its `workload` field
    p = _seed("src/repro/core/experiment.py",
              "    guard: bool\n    workload: str\n    faulted: bool\n",
              "    guard: bool\n    faulted: bool\n")
    assert _hits(p, "compile-key", "workload")


def test_tap_typo_trips_taps_checker():
    p = _seed("src/repro/core/experiment.py",
              'obs.tap("engine/hour"', 'obs.tap("engine/huor"')
    assert _hits(p, "taps", "engine/huor")          # undeclared emission
    assert _hits(p, "taps", "engine/hour")          # declared, never emitted


def test_host_clock_in_traced_root_trips_purity():
    p = _seed("src/repro/faults/failover.py",
              "    renv = realized_env(env, trace, tau)",
              "    import time\n    t0 = time.time()\n"
              "    renv = realized_env(env, trace, tau)")
    assert _hits(p, "purity", "time.time")


def test_np_random_in_solver_trips_purity():
    p = _seed("src/repro/core/gt_drl.py",
              '    """Run the game for one epoch: rounds',
              '    _bad = np.random.rand()\n'
              '    """Run the game for one epoch: rounds')
    assert _hits(p, "purity", "numpy.random")


def test_unclassified_spec_field_trips_compile_key():
    p = _seed("src/repro/core/experiment.py",
              '    technique: str = "fd"',
              '    precision: str = "f32"\n    technique: str = "fd"')
    assert _hits(p, "compile-key", "precision")


def test_syntax_error_is_reported_not_crashed():
    p = PROJECT.overlay("src/repro/core/gt_drl.py", "def broken(:\n")
    assert any(v.check == "parse" for v in lint.lint_project(p))


def test_pragma_without_reason_is_a_violation():
    p = PROJECT.overlay("src/repro/_seeded_pragma.py",
                        "x = 1  # lint: host-ok()\n")
    assert _hits(p, "pragma", "needs a justification")


def test_unknown_pragma_directive_is_a_violation():
    p = PROJECT.overlay("src/repro/_seeded_pragma.py",
                        "x = 1  # lint: hostok(typo'd directive)\n")
    assert _hits(p, "pragma", "unknown pragma directive")


def test_stale_pragma_is_a_violation():
    p = PROJECT.overlay("src/repro/_seeded_pragma.py",
                        "x = 1  # lint: host-ok(nothing here needs it)\n")
    assert _hits(p, "pragma", "stale pragma")


# ---------------------------------------------------------------------------
# seeded regressions: units & bounds
# ---------------------------------------------------------------------------

def test_unit_mismatched_add_trips_units():
    # kgCO2/kWh + W: adding a grid intensity to a power draw
    p = _seed("src/repro/dcsim/env.py",
              "de = env.carbon[:, tau] * dp / W_PER_KW",
              "de = (env.carbon[:, tau] + dp) / W_PER_KW")
    assert _hits(p, "units", "unit mismatch")


def test_undeclared_magic_factor_trips_units():
    p = _seed("src/repro/dcsim/env.py",
              "energy_cost = env.eprice[:, tau] * a * dp / W_PER_KW",
              "energy_cost = env.eprice[:, tau] * a * dp / 1000.0")
    assert _hits(p, "units", "magic scale factor")


def test_dropped_conversion_trips_suffix_contract():
    # dropping the W→kW conversion leaves carbon_kg carrying kgCO2·W/kWh
    p = _seed("src/repro/dcsim/env.py",
              "de = env.carbon[:, tau] * dp / W_PER_KW",
              "de = env.carbon[:, tau] * dp")
    assert _hits(p, "units", "`carbon_kg`")


def test_usd_suffix_key_carrying_kg_trips_units():
    p = _seed("src/repro/dcsim/env.py",
              '"sla_miss_cost_usd": jnp.sum(sla),',
              '"sla_miss_cost_usd": jnp.sum(de),')
    assert _hits(p, "units", "`sla_miss_cost_usd`")


def test_unit_table_drift_trips_units():
    p = _seed("src/repro/dcsim/env.py",
              "        eprice: USD/kWh\n", "")
    assert _hits(p, "units", "drifted")


def test_simplex_axis_flip_trips_bounds():
    p = _seed("src/repro/faults/failover.py",
              "w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), _EPS)",
              "w = w / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), _EPS)")
    assert _hits(p, "bounds", "axis")


def test_unguarded_division_trips_bounds():
    p = _seed("src/repro/dcsim/env.py",
              "frac = ar / jnp.maximum(capacity_at(env, tau), 1e-9)",
              "frac = ar / capacity_at(env, tau)")
    assert _hits(p, "bounds", "not provably positive")


def test_stale_unit_ok_pragma_is_a_violation():
    p = PROJECT.overlay("src/repro/_seeded_pragma.py",
                        "x = 1  # lint: unit-ok(nothing here needs it)\n")
    assert _hits(p, "pragma", "stale pragma")


# ---------------------------------------------------------------------------
# compile-key behavior of the live spec (what the static checker guards)
# ---------------------------------------------------------------------------

STATIC_FORKS = [
    ("technique", "nash"),
    ("objective", "cost"),
    ("engine", "batched"),
    ("hours", 12),
    ("cfg", FDConfig(iters=41)),
    ("routed", True),
    ("guard", True),
    ("workload", "llm-mix"),
    ("taps", ("engine/hour",)),
]


@pytest.mark.parametrize("field,value", STATIC_FORKS,
                         ids=[f for f, _ in STATIC_FORKS])
def test_static_field_forks_engine_key(field, value):
    assert X._engine_key(SPEC.replace(**{field: value})) != X._engine_key(SPEC)


RUNTIME_ONLY = [
    ("seed", 7),
    ("seeds", (0, 1)),
    ("days", 3),
    ("pretrain", False),
]


@pytest.mark.parametrize("field,value", RUNTIME_ONLY,
                         ids=[f for f, _ in RUNTIME_ONLY])
def test_runtime_field_does_not_fork_engine_key(field, value):
    assert X._engine_key(SPEC.replace(**{field: value})) == X._engine_key(SPEC)


def test_failover_forks_only_when_faulted():
    alt = SPEC.replace(failover="spill_nearest")
    assert X._engine_key(alt) == X._engine_key(SPEC)
    assert (X._engine_key(alt, faulted=True)
            != X._engine_key(SPEC, faulted=True))


# ---------------------------------------------------------------------------
# runtime sanitizer: exact compile counts per engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,envs", [
    (SPEC.replace(hours=3), ENV),
    (SPEC.replace(hours=3, engine="batched", seeds=(0, 1)), [ENV, ENV]),
    (SPEC.replace(hours=3, engine="month", days=2), ENV),
], ids=["scan", "batched", "month"])
def test_engine_compiles_once_then_only_hits(spec, envs):
    X._clear_compile_caches()
    with lint.expect_compiles(1):
        first = run(spec, envs)
    with lint.expect_compiles(0):
        again = run(spec, envs)
    assert first["totals"].keys() == again["totals"].keys()
    tc = lint.trace_count(spec)
    if tc is not None:   # probe availability depends on the jax version
        assert tc == 1, f"intra-key retrace: jit traced {tc} programs"


def test_expect_compiles_fixture_names_the_forking_key(expect_compiles):
    X._clear_compile_caches()
    with pytest.raises(AssertionError, match="keys that missed"):
        with expect_compiles(0):
            run(SPEC.replace(hours=2), ENV)


# ---------------------------------------------------------------------------
# runtime pytree validation
# ---------------------------------------------------------------------------

def test_validate_env_params_green():
    assert lint.validate(ENV) is ENV


def test_validate_flags_wrong_ndim():
    bad = ENV._replace(avail=jnp.ones((4,)))
    with pytest.raises(TypeError, match="avail"):
        lint.validate(bad)


def test_validate_flags_axis_contradiction():
    bad = ENV._replace(rtt=jnp.zeros((5, 5), jnp.float32))
    with pytest.raises(TypeError, match="contradicts"):
        lint.validate(bad)


def test_validate_flags_weak_typed_leaf():
    bad = ENV._replace(avail=jnp.full((4, 24), 1.0))   # no dtype: weak
    with pytest.raises(TypeError, match="weak-typed"):
        lint.validate(bad)


def test_validate_rejects_undeclared_class():
    with pytest.raises(TypeError, match="no pytree schema"):
        lint.validate(object())
