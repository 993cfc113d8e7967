"""ExperimentSpec façade: spec-built engines reproduce the legacy entry
points bit-for-bit, the compile cache is shared across call sites, sweeps
at identity grid points equal the unswept run, sharded == unsharded on one
device, and external techniques plug in through the registry."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios as S
from repro.core import (ExperimentSpec, register_technique, run, run_day,
                        run_days_batched, run_month, sweep, technique_names)
from repro.core import experiment as X
from repro.core import schedulers as SCH
from repro.core.force_directed import FDConfig
from repro.core.game import SolveResult, get_technique, uniform_fractions
from repro.dcsim import env as E

ENV = E.build_env(4, seed=0)
FD_CFG = FDConfig(iters=40)
SPEC = ExperimentSpec(technique="fd", objective="carbon", hours=6, cfg=FD_CFG)


# ---------------------------------------------------------------------------
# the spec itself
# ---------------------------------------------------------------------------

def test_spec_is_frozen_hashable_and_replaceable():
    assert hash(SPEC) == hash(ExperimentSpec(technique="fd", hours=6, cfg=FD_CFG))
    assert SPEC.replace(hours=3).hours == 3
    assert SPEC.replace(hours=3) != SPEC
    with pytest.raises(dataclasses.FrozenInstanceError):
        SPEC.hours = 12
    # seeds normalize to a tuple so the spec stays hashable
    s = ExperimentSpec(seeds=[0, 1, 2])
    assert s.seeds == (0, 1, 2)
    hash(s)


def test_spec_validates_engine_and_objective_eagerly():
    with pytest.raises(ValueError):
        ExperimentSpec(engine="Batched")
    with pytest.raises(ValueError):
        ExperimentSpec(objective="co2")
    with pytest.raises(KeyError):
        run(ExperimentSpec(technique="not-a-solver"), ENV)


def test_run_rejects_mismatched_options():
    with pytest.raises(ValueError):
        run(SPEC, ENV, shard=True)  # shard needs engine="batched"
    with pytest.raises(ValueError):
        run(SPEC.replace(engine="batched"), [ENV],
            solver=lambda *a: None)  # prebuilt solver needs engine="loop"


# ---------------------------------------------------------------------------
# bit-for-bit parity: legacy entry points == the spec path
# ---------------------------------------------------------------------------

def test_spec_scan_matches_run_day_bit_for_bit():
    legacy = run_day(ENV, "fd", seed=0, hours=6, cfg_override=FD_CFG)
    spec = run(SPEC, ENV)
    assert legacy["totals"] == spec["totals"]
    assert legacy["per_epoch"] == spec["per_epoch"]


def test_spec_batched_matches_run_days_batched_bit_for_bit():
    envs = [e for _, e in S.build_suite("baseline", ENV)][:3]
    legacy = run_days_batched(envs, "fd", hours=6, cfg_override=FD_CFG)
    spec = run(SPEC.replace(engine="batched"), envs)
    for k in legacy["totals"]:
        np.testing.assert_array_equal(legacy["totals"][k], spec["totals"][k])
    assert legacy["seeds"] == spec["seeds"]


def test_spec_month_matches_run_month_bit_for_bit():
    legacy = run_month(ENV, "fd", days=3, seed=0, hours=6, cfg_override=FD_CFG)
    spec = run(SPEC.replace(engine="month", days=3), ENV)
    for k in legacy["day_totals"]:
        np.testing.assert_array_equal(legacy["day_totals"][k],
                                      spec["day_totals"][k])
    np.testing.assert_array_equal(legacy["peak_w"], spec["peak_w"])


def test_spec_loop_matches_run_day_loop_bit_for_bit():
    legacy = run_day(ENV, "fd", seed=0, hours=3, cfg_override=FD_CFG,
                     engine="loop")
    spec = run(SPEC.replace(engine="loop", hours=3), ENV)
    assert legacy["totals"] == spec["totals"]


# ---------------------------------------------------------------------------
# the spec-keyed compile cache is shared across call sites
# ---------------------------------------------------------------------------

def test_compile_cache_shared_across_entry_points():
    """run_day, run(spec) and compare_techniques with the same static fields
    must all reuse ONE compiled day — no per-call-site compile paths."""
    spec = SPEC.replace(hours=4)
    run(spec, ENV)
    size0 = X._compiled.cache_info().currsize
    hits0 = X._compiled.cache_info().hits
    run_day(ENV, "fd", seed=3, hours=4, cfg_override=FD_CFG)  # legacy shim
    run(spec.replace(seed=7, pretrain=False), ENV)  # runtime fields differ
    info = X._compiled.cache_info()
    assert info.currsize == size0          # no new compiled artifact
    assert info.hits >= hits0 + 2          # both calls hit the shared cache
    assert X.compiled_engine(spec) is X.compiled_engine(spec.replace(seed=9))


def test_non_static_fields_never_key_a_new_compile():
    """Every spec field outside ``EngineKey`` (seed, seeds, days, pretrain) is
    a runtime input: varying them must hit the SAME compiled artifact, and
    the obs accounting must agree (one miss total, the rest hits)."""
    from repro import obs
    spec = SPEC.replace(hours=3)
    key = X._engine_key(spec)
    before = obs.engine_stat(key) or {"misses": 0, "hits": 0}
    fn = X.compiled_engine(spec)
    for other in (spec.replace(seed=41), spec.replace(pretrain=False),
                  spec.replace(seed=7, pretrain=False)):
        assert X.compiled_engine(other) is fn
    mspec = spec.replace(engine="month", days=2)
    mfn = X.compiled_engine(mspec)
    assert X.compiled_engine(mspec.replace(days=5, seed=3)) is mfn
    # the obs ledger tells the same story: at most one fresh miss on the day
    # key, and every non-static variation above counted as a hit
    st = obs.engine_stat(key)
    assert st["misses"] <= before["misses"] + 1
    assert st["hits"] >= before["hits"] + 3


def test_overwrite_eviction_lands_in_cache_stats():
    """``register_technique(overwrite=True)`` clears the compile caches; the
    obs accounting must surface that as evictions + a fresh miss, not keep
    counting hits against a dead artifact."""
    from repro import obs
    register_technique("evict-test", _uniform_solve)
    try:
        spec = ExperimentSpec(technique="evict-test", hours=2)
        run(spec, ENV)
        key = X._engine_key(spec)
        assert obs.engine_stat(key)["misses"] == 1
        ev0 = obs.cache_stats()["evictions"]
        register_technique("evict-test", _uniform_solve, overwrite=True)
        assert obs.cache_stats()["evictions"] > ev0
        assert obs.engine_stat(key)["evicted"]
        run(spec, ENV)  # recompiles: the ledger shows a second miss
        assert obs.engine_stat(key)["misses"] == 2
    finally:
        from repro.core import unregister_technique
        unregister_technique("evict-test")


# ---------------------------------------------------------------------------
# severity sweeps
# ---------------------------------------------------------------------------

def test_expand_grid_scalars_map_to_severity_knobs():
    pts = S.expand_grid({"wan_degradation": (1.0, 3.0),
                         "origin_shift": ({"weight": 0.5, "toward": (1,)},)})
    assert pts == [
        {"wan_degradation": {"factor": 1.0},
         "origin_shift": {"weight": 0.5, "toward": (1,)}},
        {"wan_degradation": {"factor": 3.0},
         "origin_shift": {"weight": 0.5, "toward": (1,)}},
    ]
    with pytest.raises(KeyError):
        S.expand_grid({"not_a_transform": (1.0,)})
    with pytest.raises(ValueError):
        S.severity_knob("identity")  # no declared knob -> explicit dicts only


def test_sweep_identity_point_matches_unswept_run():
    """An origin_shift weight-0 grid point is the identity transform, so its
    curve must equal the unswept batched run on the same base env."""
    base = (S.Scenario("sla_tighten", {"tighten": 0.8}),
            S.Scenario("wan_degradation", {"factor": 2.0, "extra_ms": 20.0}))
    spec = SPEC.replace(objective="cost_sla", routed=True, hours=4)
    res = sweep(spec, {"origin_shift": (0.0, 0.7)}, base_env=ENV,
                base_scenarios=base)
    unswept = run(spec.replace(engine="batched", seeds=(spec.seed,)),
                  S.apply_all(ENV, base))
    for k in ("carbon_kg", "cost_usd", "sla_miss_cost_usd"):
        np.testing.assert_allclose(res["results"]["fd"]["totals"][k][0],
                                   unswept["totals"][k][0], rtol=1e-6)
    # the shifted point must actually differ (the routed game sees origins)
    assert not np.allclose(res["results"]["fd"]["totals"]["sla_miss_cost_usd"][0],
                           res["results"]["fd"]["totals"]["sla_miss_cost_usd"][1])


def test_sweep_returns_per_point_curves_for_each_technique():
    grid = {"wan_degradation": (1.0, 4.0), "origin_shift": (0.0, 0.8)}
    from repro.core.nash import NashConfig
    spec = SPEC.replace(objective="cost_sla", routed=True, hours=3)
    res = sweep(spec, grid, base_env=ENV, techniques=("fd", "nash"),
                cfg_overrides={"nash": NashConfig(sweeps=2, inner_steps=10)},
                base_scenarios=(S.Scenario("sla_tighten", {"tighten": 0.7}),))
    assert res["labels"] == ["wan_degradation=1.0|origin_shift=0.0",
                             "wan_degradation=1.0|origin_shift=0.8",
                             "wan_degradation=4.0|origin_shift=0.0",
                             "wan_degradation=4.0|origin_shift=0.8"]
    for t in ("fd", "nash"):
        assert res["results"][t]["per_epoch"]["cost_usd"].shape == (4, 3)
        assert res["results"][t]["totals"]["sla_miss_cost_usd"].shape == (4,)
    # severity curves are monotone here: a 4x-degraded WAN costs more SLA
    sla = res["results"]["fd"]["totals"]["sla_miss_cost_usd"]
    assert sla[2] > sla[0] and sla[3] > sla[1]


# ---------------------------------------------------------------------------
# device-sharded batched engine
# ---------------------------------------------------------------------------

def test_sharded_run_matches_unsharded_on_one_device():
    envs = [e for _, e in S.build_suite("baseline", ENV)][:3]
    spec = SPEC.replace(engine="batched", hours=4)
    plain = run(spec, envs)
    sharded = run(spec, envs, shard=True)
    for k in plain["totals"]:
        np.testing.assert_array_equal(plain["totals"][k], sharded["totals"][k])
    for k in plain["per_epoch"]:
        np.testing.assert_array_equal(plain["per_epoch"][k],
                                      sharded["per_epoch"][k])


def test_pad_env_batch_repeats_last_row_and_validates():
    env_b = E.stack_envs([ENV, S.make("carbon_spike")(ENV)])
    padded = E.pad_env_batch(env_b, 5)
    assert padded.er.shape[0] == 5
    np.testing.assert_array_equal(np.asarray(padded.carbon[4]),
                                  np.asarray(padded.carbon[1]))
    assert E.pad_env_batch(env_b, 2) is env_b
    with pytest.raises(ValueError):
        E.pad_env_batch(env_b, 1)


# ---------------------------------------------------------------------------
# technique registry: external solvers plug into the spec by name
# ---------------------------------------------------------------------------

def _uniform_solve(key, ctx, peak_state, cfg=None):
    return SolveResult(uniform_fractions(ctx), {})


def test_register_technique_plugs_into_every_engine():
    register_technique("uniform-test", _uniform_solve)
    try:
        assert "uniform-test" in technique_names()
        spec = ExperimentSpec(technique="uniform-test", hours=3)
        day = run(spec, ENV)
        assert day["totals"]["violation"] < 1e-3
        bat = run(spec.replace(engine="batched"), [ENV, ENV])
        np.testing.assert_array_equal(bat["totals"]["carbon_kg"][0],
                                      bat["totals"]["carbon_kg"][1])
        cmp_res = SCH.compare_techniques([ENV], ("uniform-test",), hours=3)
        np.testing.assert_allclose(cmp_res["uniform-test"]["mean"],
                                   day["totals"]["carbon_kg"], rtol=1e-6)
        # loop engine resolves registered names through get_scheduler too
        loop = run(spec.replace(engine="loop"), ENV)
        np.testing.assert_allclose(loop["totals"]["carbon_kg"],
                                   day["totals"]["carbon_kg"], rtol=1e-5)
    finally:
        from repro.core import unregister_technique
        unregister_technique("uniform-test")


def test_register_technique_rejects_duplicates_and_bad_shapes():
    with pytest.raises(KeyError):
        register_technique("fd", _uniform_solve)
    with pytest.raises(ValueError):
        register_technique("both", _uniform_solve, step=lambda *a: None)
    with pytest.raises(ValueError):
        register_technique("neither")
    with pytest.raises(KeyError):
        get_technique("never-registered")


def test_reregistration_with_overwrite_clears_compile_caches():
    register_technique("overwrite-test", _uniform_solve)
    try:
        spec = ExperimentSpec(technique="overwrite-test", hours=2)
        base = run(spec, ENV)["totals"]["carbon_kg"]

        def degenerate(key, ctx, peak_state, cfg=None):
            f = jnp.zeros(ctx.joint_shape()).at[..., 0].set(1.0)
            return SolveResult(f, {})

        register_technique("overwrite-test", degenerate, overwrite=True)
        rebound = run(spec, ENV)["totals"]["carbon_kg"]
        assert rebound != base  # stale compiled engine would return `base`
    finally:
        from repro.core import unregister_technique
        unregister_technique("overwrite-test")


def test_external_stateful_technique_scan_matches_loop():
    """Scan and loop engines must build an external stateful technique's
    carry with the SAME key discipline (pretrain flag included), so the
    all-engines-match contract holds beyond gt-drl."""
    import jax

    def _init(key, env, objective, cfg, routed, pretrain):
        # key-derived carry: any engine key-discipline divergence shows up
        return jax.random.normal(key, (E.num_dcs(env),))

    def _step(key, state, ctx, peak_state, cfg):
        row = jnp.broadcast_to(jax.nn.softmax(state), ctx.joint_shape())
        return state + 0.1, SolveResult(row, {})

    register_technique("stateful-test", step=_step, init_state=_init,
                       stateful=True)
    try:
        spec = ExperimentSpec(technique="stateful-test", hours=3, seed=5,
                              pretrain=False)
        scan = run(spec, ENV)
        loop = run(spec.replace(engine="loop"), ENV)
        for k in ("carbon_kg", "cost_usd", "violation"):
            np.testing.assert_allclose(loop["totals"][k], scan["totals"][k],
                                       rtol=1e-5)
    finally:
        from repro.core import unregister_technique
        unregister_technique("stateful-test")
