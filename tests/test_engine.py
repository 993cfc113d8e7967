"""Compiled evaluation pipeline: GT-DRL half-compute rounds (against the
plain reference, dispatch counting), deploy-once scan-vs-loop parity,
batched ``compare_techniques`` vs the loop reference, ``run_month`` day-0
agreement and monotone monthly peaks, and zero-denominator state guards."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios as S
from repro.core import gt_drl
from repro.core import schedulers as SCH
from repro.core.force_directed import FDConfig
from repro.core.game import GameContext, uniform_fractions
from repro.core.nash import NashConfig
from repro.core.ppo import PPOConfig
from repro.dcsim import env as E

ENV = E.build_env(4, seed=0)
PEAK = jnp.zeros((4,))
KEY = jax.random.PRNGKey(0)
CTX = GameContext(env=ENV, tau=jnp.int32(18), objective="carbon")

FAST_GTDRL = gt_drl.GTDRLConfig(
    ppo=PPOConfig(horizon=4, episodes=16, iters=2, update_epochs=2),
    rounds=2, polish_steps=15, pretrain_iters=4, pretrain_batch=2)
FD_CFG = FDConfig(iters=60)
NASH_CFG = NashConfig(sweeps=3, inner_steps=20)


# ---------------------------------------------------------------------------
# GT-DRL red-black half-update: gathered I/2 dispatch
# ---------------------------------------------------------------------------

def test_half_update_gather_matches_masked_reference():
    """The gathered half-update (the only implementation) against the plain
    reference of the round (``chipbench/reference_gtdrl.py``, players one
    after another in float32 at the highest matmul precision): both halves
    of round 1, player by player, and the epoch's best game value."""
    from chipbench import compare_gtdrl as C

    cfg = dataclasses.replace(FAST_GTDRL, rounds=1)
    agents = gt_drl.init_agents(KEY, ENV, cfg)
    rep = C.compare_hour(ENV, agents, KEY, 18, cfg, "carbon", False,
                         dtypes=("float32",))["default"]["float32"]
    assert rep["passes"], rep
    assert rep["skipped"] < E.num_players(ENV), rep


def test_half_update_dispatches_half_the_players(monkeypatch):
    """The gathered half-update pays _one_player_round for I/2 players per
    half — I per round. Count the actual per-player dispatches with a debug
    callback (one call per vmap lane)."""
    i_n = E.num_players(ENV)
    calls = []
    orig = gt_drl._one_player_round

    def counting(key, agent, *args, i, **kw):
        jax.debug.callback(lambda ii: calls.append(int(ii)), i)
        return orig(key, agent, *args, i=i, **kw)

    monkeypatch.setattr(gt_drl, "_one_player_round", counting)
    cfg = dataclasses.replace(FAST_GTDRL, rounds=1)
    agents = gt_drl.init_agents(KEY, ENV, cfg)

    jax.block_until_ready(gt_drl.solve_epoch(KEY, agents, CTX, PEAK, cfg))
    jax.effects_barrier()
    assert len(calls) == i_n            # I/2 red + I/2 black
    assert sorted(calls) == list(range(i_n))  # every player responded once


def test_batched_pretrain_is_finite_and_improves():
    agents = gt_drl.pretrain(KEY, ENV, "carbon", FAST_GTDRL)
    for leaf in jax.tree_util.tree_leaves(agents):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    _, res = gt_drl.solve_epoch(KEY, agents, CTX, PEAK, FAST_GTDRL)
    from repro.core.game import cloud_objective
    v = float(cloud_objective(CTX, res.fractions, PEAK))
    assert v < float(cloud_objective(CTX, uniform_fractions(CTX), PEAK))


# ---------------------------------------------------------------------------
# zero-denominator guards for state_mode="env"
# ---------------------------------------------------------------------------

def test_ctx_features_finite_under_zero_fields():
    """Zero-carbon grid / dead renewables / free power must not NaN the
    state features (renewable_drought scale=0 and friends hit this)."""
    dead = ENV._replace(carbon=jnp.zeros_like(ENV.carbon),
                        eprice=jnp.zeros_like(ENV.eprice),
                        rp=jnp.zeros_like(ENV.rp))
    f = gt_drl._ctx_features(dead, jnp.int32(3), 0)
    assert bool(jnp.all(jnp.isfinite(f)))


def test_env_state_mode_finite_under_renewable_drought():
    env = S.make("renewable_drought", scale=0.0)(ENV)._replace(
        carbon=jnp.zeros_like(ENV.carbon))
    cfg = dataclasses.replace(FAST_GTDRL, state_mode="env", rounds=1)
    agents = gt_drl.init_agents(KEY, env, cfg)
    ctx = GameContext(env=env, tau=jnp.int32(12), objective="carbon")
    _, res = gt_drl.solve_epoch(KEY, agents, ctx, PEAK, cfg)
    assert bool(jnp.all(jnp.isfinite(res.fractions)))


# ---------------------------------------------------------------------------
# deploy-once GT-DRL: scan engine vs the loop reference
# ---------------------------------------------------------------------------

def test_gtdrl_deploy_once_scan_matches_loop():
    agents0 = gt_drl.init_agents(jax.random.PRNGKey(7), ENV, FAST_GTDRL)
    sched = SCH.GTDRLScheduler(ENV, "carbon", FAST_GTDRL, agents=agents0)
    loop = SCH.run_day(ENV, "gt-drl", seed=0, hours=4,
                       solver=sched.solve_epoch, engine="loop")
    scan = SCH.run_day(ENV, "gt-drl", seed=0, hours=4, engine="scan",
                       cfg_override=FAST_GTDRL, solver_state0=agents0)
    for k in ("carbon_kg", "cost_usd", "violation"):
        a, b = loop["totals"][k], scan["totals"][k]
        assert abs(a - b) <= 1e-4 * max(abs(a), 1.0), (k, a, b)


# ---------------------------------------------------------------------------
# batched compare_techniques vs the loop reference
# ---------------------------------------------------------------------------

def test_compare_techniques_batched_matches_loop():
    suite = S.build_suite("baseline", ENV)
    envs = [e for _, e in suite][:3]
    kw = dict(objective="carbon", hours=6, seed0=0,
              cfg_overrides={"fd": FD_CFG, "nash": NASH_CFG})
    loop = SCH.compare_techniques(envs, ("fd", "nash"), engine="loop", **kw)
    bat = SCH.compare_techniques(envs, ("fd", "nash"), engine="batched", **kw)
    for t in ("fd", "nash"):
        np.testing.assert_allclose(bat[t]["mean"], loop[t]["mean"], rtol=1e-4)
        np.testing.assert_allclose(bat[t]["stderr"], loop[t]["stderr"],
                                   rtol=1e-3, atol=1e-6)
        np.testing.assert_allclose(bat[t]["curve_mean"], loop[t]["curve_mean"],
                                   rtol=1e-3)


def test_compare_techniques_gtdrl_deploy_once_batched_matches_loop():
    envs = [ENV, S.Scenario("arrival_resample", {"seed": 1}).apply(ENV)]
    kw = dict(objective="carbon", hours=3, seed0=0,
              cfg_overrides={"gt-drl": FAST_GTDRL})
    loop = SCH.compare_techniques(envs, ("gt-drl",), engine="loop", **kw)
    bat = SCH.compare_techniques(envs, ("gt-drl",), engine="batched", **kw)
    np.testing.assert_allclose(bat["gt-drl"]["mean"], loop["gt-drl"]["mean"],
                               rtol=1e-4)
    np.testing.assert_allclose(bat["gt-drl"]["curve_mean"],
                               loop["gt-drl"]["curve_mean"], rtol=1e-3)


def test_compare_techniques_rejects_unknown_engine():
    with pytest.raises(ValueError):
        SCH.compare_techniques([ENV], ("fd",), engine="Batched")


# ---------------------------------------------------------------------------
# run_month: day-0 parity, monotone peaks, agent threading
# ---------------------------------------------------------------------------

def test_run_month_day0_matches_run_day():
    m = SCH.run_month(ENV, "fd", days=3, seed=0, hours=24, cfg_override=FD_CFG)
    d0 = SCH.run_day(ENV, "fd", seed=0, hours=24, cfg_override=FD_CFG)
    np.testing.assert_allclose(m["day_totals"]["carbon_kg"][0],
                               d0["totals"]["carbon_kg"], rtol=1e-5)
    np.testing.assert_allclose(m["per_day"]["cost_usd"][0],
                               [e["cost_usd"] for e in d0["per_epoch"]],
                               rtol=1e-4)


def test_run_month_peak_state_is_monotone_and_charged_once():
    month = S.build_month(ENV, days=5, seed=0)
    res = SCH.run_month(month, "fd", cfg_override=FD_CFG)  # (name, env) rows ok
    peaks = res["peak_w"]  # (days, D) end-of-day monthly peaks
    assert peaks.shape == (5, 4)
    assert np.all(np.diff(peaks, axis=0) >= -1e-5)  # never decreases
    np.testing.assert_allclose(peaks[-1], res["final_peak_w"], rtol=1e-6)
    # once the monthly peak is established, later days stop paying for it:
    # day 0 (which sets most of the peak) bears a strictly larger peak charge
    peak_cost = res["per_day"]["peak_cost_usd"].sum(axis=1)
    assert peak_cost[0] > peak_cost[1:].max()


def test_run_month_shapes_and_total_consistency():
    res = SCH.run_month(ENV, "fd", days=2, hours=24, cfg_override=FD_CFG)
    assert res["days"] == 2
    assert res["per_day"]["carbon_kg"].shape == (2, 24)
    np.testing.assert_allclose(
        res["totals"]["carbon_kg"],
        res["day_totals"]["carbon_kg"].sum(), rtol=1e-6)
    with pytest.raises(ValueError):
        SCH.run_month([ENV, ENV], "fd", days=3)


def test_stack_and_tile_env_helpers():
    st = E.stack_envs([ENV, ENV])
    assert st.er.shape == (2,) + ENV.er.shape
    ti = E.tile_env(ENV, 3)
    assert ti.car.shape == (3,) + ENV.car.shape
    np.testing.assert_array_equal(np.asarray(ti.car[1]), np.asarray(ENV.car))
