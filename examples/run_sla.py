"""SLA/latency evaluation: all six techniques on the ``cost_sla`` objective
over the ``latency`` scenario suite — each technique is ONE compiled
``run_days_batched`` call (the paper's protocol plus the beyond-paper
performance term: queueing latency and priced SLA misses).

    PYTHONPATH=src python examples/run_sla.py
    PYTHONPATH=src python examples/run_sla.py --techniques fd,nash --hours 12
    PYTHONPATH=src python examples/run_sla.py --objective cost   # SLA-blind

Prints, per technique, the suite-mean daily cost (which includes the SLA
bill), the SLA-miss bill alone, carbon, and the request-weighted mean
latency — so the carbon/cost-vs-performance trade the paper claims "without
compromising computational performance" is finally measurable.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

from repro import compile_cache, scenarios as S
from repro.core.schedulers import TECHNIQUES, run_days_batched
from repro.dcsim import env as E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", choices=E.OBJECTIVES,
                    default="cost_sla")
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--hours", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--techniques", default=",".join(TECHNIQUES))
    args = ap.parse_args()

    base = E.build_env(args.dcs, seed=args.seed)
    suite = S.build_suite("latency", base)
    names = [n for n, _ in suite]
    envs = [e for _, e in suite]
    print(f"suite=latency days={names} objective={args.objective}\n")

    print(f"{'technique':9s} {'cost_usd':>14s} {'sla_usd':>12s} "
          f"{'carbon_kg':>12s} {'mean_lat_ms':>12s} {'wall_s':>7s}")
    for t in args.techniques.split(","):
        t0 = time.time()
        res = run_days_batched(envs, t, args.objective, hours=args.hours,
                               seeds=[args.seed] * len(envs))
        wall = time.time() - t0
        tot, pe = res["totals"], res["per_epoch"]
        lat = pe["latency_ms"].mean()  # suite × epoch mean of the hourly means
        print(f"{t:9s} {tot['cost_usd'].mean():14.1f} "
              f"{tot['sla_miss_cost_usd'].mean():12.1f} "
              f"{tot['carbon_kg'].mean():12.1f} {lat:12.1f} {wall:7.1f}")

    print("\nper scenario-day SLA bill (last technique):")
    for i, n in enumerate(names):
        print(f"  {n:18s} sla_usd={tot['sla_miss_cost_usd'][i]:12.1f} "
              f"mean_lat_ms={pe['latency_ms'][i].mean():8.1f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
