"""Evaluation-pipeline throughput: the paper's ``compare_techniques``
protocol (hour-loop reference vs one-compile batched engine), GT-DRL
best-response round cost (gathered half dispatch), and month-scale
episodes.

Rows (name, us_per_call, derived):
  engine/compare_loop_<t>     us per 5-env suite evaluation, loop reference
  engine/compare_batched_<t>  us per 5-env suite evaluation; speedup derived
  engine/gtdrl_round_half     us per game round, I/2 gathered dispatch
  engine/month_day_<t>        us per simulated day inside run_month
  engine/day_scan_fd_cost     us per compiled day, plain cost objective
  engine/day_scan_fd_cost_sla us per compiled day with the latency/SLA terms
                              (overhead vs plain cost derived)
  engine/day_scan_routed      us per compiled day over the (S, I, D) routing
                              tensor (overhead vs the unrouted SLA day
                              derived — the cost of the per-source axis)
  engine/day_scan_tap_overhead us per compiled day with the engine/hour tap
                              streaming (overhead vs the silent taps-off
                              artifact derived — the price of live telemetry)
  engine/day_batched_sharded  us per batched fleet evaluation through the
                              shard_map-sharded env axis (overhead vs the
                              plain vmapped engine derived; on one device
                              the two run the identical program)
  engine/sweep_grid           us per severity-sweep grid (ExperimentSpec
                              ``sweep``: stacked grid envs, one batched
                              compile per technique)
  engine/day_scan_faulted     us per compiled day through the plan/execute
                              split (realized FaultTrace + failover
                              re-projection each hour; overhead vs the
                              unfaulted day derived — the price of
                              executing on the realized env)
  engine/sweep_resume         us per journaled severity-sweep grid
                              (chunked execution, one checkpoint per
                              chunk; overhead vs the one-compile in-memory
                              sweep derived — the price of crash safety)
  engine/build_env_llm        us per token-grounded env build (the llm
                              capability layer: roofline derivation over
                              the model zoo x accelerator mix; overhead vs
                              the aibench constant tables derived)
  engine/day_scan_llm         us per compiled day on the derived llm env
                              (I = model families instead of the paper's
                              task types; overhead vs the aibench day
                              derived — the engines are workload-agnostic,
                              so this tracks the I-axis cost alone)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import scenarios as S
from repro.core import gt_drl
from repro.core import schedulers as SCH
from repro.core.force_directed import FDConfig
from repro.core.game import GameContext
from repro.core.nash import NashConfig
from repro.dcsim import env as E

from .common import HOURS, QUICK, Timer, emit

CFGS = {"fd": FDConfig(iters=60), "nash": NashConfig(sweeps=3, inner_steps=20)}

# paper-default PPO inner loop (the FLOP-dominated regime the half dispatch
# targets; tiny configs are overhead-bound and hide the win), few rounds
GTDRL_BENCH = gt_drl.GTDRLConfig(rounds=2, pretrain_iters=2)


def run(rows):
    env = E.build_env(4, seed=0)
    suite = S.build_suite("baseline", env)  # the paper's 5 resampled-arrival days
    envs = [e for _, e in suite]
    n = len(envs)
    techniques = ("fd",) if QUICK else ("fd", "nash")

    # -- compare_techniques: loop reference vs one-compile batched engine ----
    for t in techniques:
        kw = dict(objective="carbon", hours=HOURS, seed0=0,
                  cfg_overrides={t: CFGS[t]})
        SCH.compare_techniques(envs, (t,), engine="loop", **kw)   # warm jits
        with Timer() as tm:
            res_loop = SCH.compare_techniques(envs, (t,), engine="loop", **kw)
        loop_s = tm.seconds
        emit(rows, f"engine/compare_loop_{t}", loop_s,
             f"envs={n};mean={res_loop[t]['mean']:.0f}")

        SCH.compare_techniques(envs, (t,), engine="batched", **kw)  # warm
        with Timer() as tm:
            res_b = SCH.compare_techniques(envs, (t,), engine="batched", **kw)
        emit(rows, f"engine/compare_batched_{t}", tm.seconds,
             f"envs={n};speedup_vs_loop={loop_s / max(tm.seconds, 1e-9):.0f}x;"
             f"mean={res_b[t]['mean']:.0f}")

    # -- GT-DRL round cost: gathered half dispatch ----------------------------
    key = jax.random.PRNGKey(0)
    ctx = GameContext(env=env, tau=jnp.int32(12), objective="carbon")
    peak = jnp.zeros((E.num_dcs(env),))
    cfg = GTDRL_BENCH
    agents = gt_drl.init_agents(key, env, cfg)
    fn = jax.jit(functools.partial(gt_drl.solve_epoch, cfg=cfg))
    jax.block_until_ready(fn(key, agents, ctx, peak))  # warm
    with Timer() as tm:
        jax.block_until_ready(fn(key, agents, ctx, peak))
    emit(rows, "engine/gtdrl_round_half", tm.seconds / cfg.rounds,
         f"rounds={cfg.rounds};players={E.num_players(env)}")

    # -- month-scale episodes: second-level scan threading the peak state ----
    days = 3 if QUICK else 7
    month = S.build_month(env, days=days, seed=0)
    menvs = [e for _, e in month]
    mkw = dict(objective="carbon", hours=HOURS, seed=0, cfg_override=CFGS["fd"])
    SCH.run_month(menvs, "fd", **mkw)  # warm
    with Timer() as tm:
        res_m = SCH.run_month(menvs, "fd", **mkw)
    emit(rows, "engine/month_day_fd", tm.seconds / days,
         f"days={days};peak_final_kw={res_m['final_peak_w'].max() / 1e3:.0f}")

    # -- SLA-enabled compiled day: the latency/SLA terms must stay cheap -----
    sla_env = S.make("wan_degradation")(S.make("sla_tighten", tighten=0.8)(env))
    day_s = {}
    for obj in ("cost", "cost_sla"):
        kw = dict(objective=obj, hours=HOURS, seed=0, cfg_override=CFGS["fd"])
        SCH.run_day(sla_env, "fd", **kw)  # warm
        with Timer() as tm:
            res_d = SCH.run_day(sla_env, "fd", **kw)
        day_s[obj] = tm.seconds
        emit(rows, f"engine/day_scan_fd_{obj}", tm.seconds,
             f"hours={HOURS};sla_usd={res_d['totals']['sla_miss_cost_usd']:.0f}"
             + (f";overhead_vs_cost={day_s['cost_sla'] / max(day_s['cost'], 1e-9):.2f}x"
                if obj == "cost_sla" else ""))

    # -- tap overhead: the telemetry-streaming day vs the silent artifact ----
    from repro import obs
    from repro.core import experiment as X
    tap_spec = X.ExperimentSpec(technique="fd", objective="cost", hours=HOURS,
                                cfg=CFGS["fd"], taps=())
    X.run(tap_spec, sla_env)  # warm the taps-off artifact
    with Timer() as tm:
        X.run(tap_spec, sla_env)
    off_s = tm.seconds
    tapped = tap_spec.replace(taps=("engine/hour",))
    X.run(tapped, sla_env)  # warm the tapped artifact (separate compile key)
    with obs.capture("engine/hour") as buf, Timer() as tm:
        X.run(tapped, sla_env)
    emit(rows, "engine/day_scan_tap_overhead", tm.seconds,
         f"hours={HOURS};events={len(buf.events)};"
         f"overhead_vs_off={tm.seconds / max(off_s, 1e-9):.2f}x")

    # -- routed day: the (S, I, D) routing tensor's compile/runtime cost -----
    route_env = S.make("origin_shift", toward=(0,), weight=0.8)(sla_env)
    rkw = dict(objective="cost_sla", hours=HOURS, seed=0,
               cfg_override=CFGS["fd"], routed=True)
    SCH.run_day(route_env, "fd", **rkw)  # warm (includes the routed compile)
    with Timer() as tm:
        res_r = SCH.run_day(route_env, "fd", **rkw)
    emit(rows, "engine/day_scan_routed", tm.seconds,
         f"hours={HOURS};sources={E.num_sources(route_env)};"
         f"sla_usd={res_r['totals']['sla_miss_cost_usd']:.0f};"
         f"overhead_vs_unrouted={tm.seconds / max(day_s['cost_sla'], 1e-9):.2f}x")

    # -- spec-driven engines: device-sharded batched day + severity sweep ----
    spec = X.ExperimentSpec(technique="fd", objective="carbon", engine="batched",
                            hours=HOURS, cfg=CFGS["fd"])
    env_b = E.stack_envs(envs)
    X.run(spec, env_b)  # warm (shares the spec-keyed cache with compare above)
    with Timer() as tm:
        X.run(spec, env_b)
    plain_s = tm.seconds
    X.run(spec, env_b, shard=True)  # warm the shard_map compile
    with Timer() as tm:
        res_sh = X.run(spec, env_b, shard=True)
    emit(rows, "engine/day_batched_sharded", tm.seconds,
         f"devices={jax.device_count()};envs={n};"
         f"overhead_vs_vmap={tm.seconds / max(plain_s, 1e-9):.2f}x;"
         f"mean={res_sh['totals']['carbon_kg'].mean():.0f}")

    grid = {"wan_degradation": (1.0, 3.0), "origin_shift": (0.0, 0.7)}
    sweep_spec = X.ExperimentSpec(technique="fd", objective="cost_sla",
                                  engine="batched", routed=True, hours=HOURS,
                                  cfg=CFGS["fd"])
    base = (S.Scenario("sla_tighten", {"tighten": 0.7}),)
    skw = dict(base_env=env, base_scenarios=base)
    X.sweep(sweep_spec, grid, **skw)  # warm
    with Timer() as tm:
        res_g = X.sweep(sweep_spec, grid, **skw)
    sweep_s = tm.seconds
    n_pts = len(res_g["labels"])
    emit(rows, "engine/sweep_grid", sweep_s,
         f"points={n_pts};hours={HOURS};"
         f"us_per_point={sweep_s * 1e6 / n_pts:.0f};"
         f"sla_usd_max={res_g['results']['fd']['totals']['sla_miss_cost_usd'].max():.0f}")

    # -- token-grounded llm workload: capability derivation + compiled day --
    E.build_env(4, seed=0, workload="llm")  # warm (config imports etc.)
    with Timer() as tm:
        for _ in range(3):
            E.build_env(4, seed=0, workload="llm")
    build_llm_s = tm.seconds / 3
    with Timer() as tm:
        for _ in range(3):
            E.build_env(4, seed=0)
    build_aib_s = tm.seconds / 3
    emit(rows, "engine/build_env_llm", build_llm_s,
         f"families={E.build_env(4, seed=0, workload='llm').er.shape[0]};"
         f"overhead_vs_aibench={build_llm_s / max(build_aib_s, 1e-9):.2f}x")

    llm_env = E.build_env(4, seed=0, workload="llm")
    lspec = X.ExperimentSpec(technique="fd", objective="cost", hours=HOURS,
                             cfg=CFGS["fd"], workload="llm")
    X.run(lspec, llm_env)  # warm (separate compile key: workload + I retrace)
    with Timer() as tm:
        res_l = X.run(lspec, llm_env)
    emit(rows, "engine/day_scan_llm", tm.seconds,
         f"hours={HOURS};families={llm_env.er.shape[0]};"
         f"cost={res_l['totals']['cost_usd']:.0f};"
         f"overhead_vs_aibench={tm.seconds / max(day_s['cost'], 1e-9):.2f}x")

    # -- realized faults: the plan/execute split vs the plain compiled day --
    from repro import faults as FL
    day_spec = X.ExperimentSpec(technique="fd", objective="cost",
                                hours=HOURS, cfg=CFGS["fd"])
    trace = FL.compose(FL.dc_crash(sla_env, dc=1, start=HOURS // 3,
                                   duration=HOURS // 2),
                       FL.wan_partition(sla_env, a=0, b=2, extra_ms=300.0))
    X.run(day_spec, sla_env)  # warm the unfaulted artifact
    with Timer() as tm:
        X.run(day_spec, sla_env)
    plain_day_s = tm.seconds
    X.run(day_spec, sla_env, faults=trace)  # warm the faulted artifact
    with Timer() as tm:
        res_f = X.run(day_spec, sla_env, faults=trace)
    emit(rows, "engine/day_scan_faulted", tm.seconds,
         f"hours={HOURS};moved={res_f['totals']['failover_moved']:.0f};"
         f"overhead_vs_plain={tm.seconds / max(plain_day_s, 1e-9):.2f}x")

    # -- resumable sweep: journaled chunk execution vs the in-memory sweep --
    import shutil
    import tempfile
    journal = tempfile.mkdtemp(prefix="bench_sweep_resume_")
    try:
        with Timer() as tm:
            X.sweep(sweep_spec, grid, resume_dir=journal, **skw)
        emit(rows, "engine/sweep_resume", tm.seconds,
             f"points={n_pts};chunks={n_pts};"
             f"overhead_vs_inmem={tm.seconds / max(sweep_s, 1e-9):.2f}x")
    finally:
        shutil.rmtree(journal, ignore_errors=True)
