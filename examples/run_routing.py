"""Per-source request routing: the (S, I, D) decision surface in action.

Runs the ``routing`` scenario suite (origins shifted east/west, regional
flash crowds, degraded WAN, priced SLAs) with the routed engines — each
technique is ONE compiled ``run_days_batched`` call over the whole suite —
and then demonstrates the headline claim: on a non-uniform ``origin_shift``
day, optimizing *which region's* requests go to which DC measurably cuts
the SLA-miss bill versus the source-blind (I, D) split PR 3 could express,
with both priced by the same routed simulator.

    PYTHONPATH=src python examples/run_routing.py
    PYTHONPATH=src python examples/run_routing.py --techniques fd,nash,gt-drl
    PYTHONPATH=src python examples/run_routing.py --hours 12 --scenario west-evening
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, scenarios as S
from repro.core import schedulers as SCH
from repro.core.game import GameContext
from repro.dcsim import env as E


def run_source_blind_day(env, technique, objective, *, seed=0, hours=24,
                         cfg=None):
    """PR 3's decision surface priced under the routed simulator.

    Solves the unrouted (I, D) game each hour and broadcasts the split to
    every source region — every region's requests get the same treatment —
    then bills the day with the per-(source, task) SLA pricing. The routed
    engine must beat this to prove the new axis earns its keep.
    """
    solver = SCH.get_scheduler(technique, env, objective,
                               **({"cfg": cfg} if cfg is not None else {}))
    s, d = E.num_sources(env), E.num_dcs(env)
    key = jax.random.PRNGKey(seed)
    _, key = jax.random.split(key)
    peak = jnp.zeros((d,))
    totals = {"cost_usd": 0.0, "sla_miss_cost_usd": 0.0, "carbon_kg": 0.0}
    for tau in range(hours):
        key, ks = jax.random.split(key)
        ctx = GameContext(env=env, tau=jnp.int32(tau), objective=objective)
        res = solver(ks, ctx, peak)
        blind = jnp.broadcast_to(res.fractions, (s,) + res.fractions.shape)
        ar3 = E.project_feasible_routed(env, blind, jnp.int32(tau))
        peak, m = E.step_epoch(env, peak, ar3, jnp.int32(tau))
        for k in totals:
            totals[k] += float(m[k])
    return totals


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--hours", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--techniques", default="fd,nash")
    ap.add_argument("--scenario", default="east-business-day",
                    help="suite day for the routed-vs-source-blind duel")
    args = ap.parse_args()

    base = E.build_env(args.dcs, seed=args.seed)
    suite = S.build_suite("routing", base)
    names = [n for n, _ in suite]
    envs = [e for _, e in suite]
    techniques = args.techniques.split(",")
    print(f"suite=routing days={names} objective=cost_sla routed=True\n")

    print(f"{'technique':9s} {'cost_usd':>14s} {'sla_usd':>12s} "
          f"{'carbon_kg':>12s} {'mean_lat_ms':>12s} {'wall_s':>7s}")
    for t in techniques:
        t0 = time.time()
        res = SCH.run_days_batched(envs, t, "cost_sla", hours=args.hours,
                                   seeds=[args.seed] * len(envs), routed=True)
        wall = time.time() - t0
        tot, pe = res["totals"], res["per_epoch"]
        print(f"{t:9s} {tot['cost_usd'].mean():14.1f} "
              f"{tot['sla_miss_cost_usd'].mean():12.1f} "
              f"{tot['carbon_kg'].mean():12.1f} "
              f"{pe['latency_ms'].mean():12.1f} {wall:7.1f}")

    # -- the duel: routed vs source-blind on a shifted-origin day ------------
    duel_env = envs[names.index(args.scenario)]
    t = techniques[0]
    print(f"\nrouting vs source-blind ({t}, scenario={args.scenario}, "
          f"{args.hours}h, same routed simulator):")
    routed = SCH.run_day(duel_env, t, "cost_sla", seed=args.seed,
                         hours=args.hours, routed=True)["totals"]
    blind = run_source_blind_day(duel_env, t, "cost_sla", seed=args.seed,
                                 hours=args.hours)
    for k in ("sla_miss_cost_usd", "cost_usd", "carbon_kg"):
        r, b = routed[k], blind[k]
        cut = 100.0 * (b - r) / max(abs(b), 1e-9)
        print(f"  {k:18s} blind={b:14.1f}  routed={r:14.1f}  ({cut:+5.1f}%)")
    assert routed["sla_miss_cost_usd"] < blind["sla_miss_cost_usd"], (
        "routing toward nearby DCs must cut the SLA-miss bill")
    print("\nrouting toward nearby DCs cut the SLA-miss bill — the RTT "
          "matrix is a real decision surface now.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
