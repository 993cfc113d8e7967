"""Evaluate one scheduler across a named scenario suite — the whole suite is
simulated in ONE compiled vmapped call (run_days_batched):

    PYTHONPATH=src python examples/stress_suite.py --suite stress --technique fd
    PYTHONPATH=src python examples/stress_suite.py --suite grid_events --technique nash

Prints a per-scenario carbon / cost / violation table plus the fleet totals.
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
    ap.add_argument("--suite", choices=S.suite_names(), default="stress")
    ap.add_argument("--technique", choices=TECHNIQUES, default="fd")
    ap.add_argument("--objective", choices=("carbon", "cost"), default="carbon")
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    base = E.build_env(args.dcs, seed=args.seed)
    suite = S.build_suite(args.suite, base)
    names = [n for n, _ in suite]
    envs = [e for _, e in suite]

    t0 = time.time()
    res = run_days_batched(envs, args.technique, args.objective,
                           seeds=[args.seed] * len(envs))
    dt = time.time() - t0

    print(f"suite={args.suite} technique={args.technique} "
          f"objective={args.objective} days={len(envs)} wall={dt:.1f}s")
    print(f"{'scenario':20s} {'carbon_kg':>12s} {'cost_usd':>12s} {'violation':>10s}")
    for i, name in enumerate(names):
        print(f"{name:20s} {res['totals']['carbon_kg'][i]:12.1f} "
              f"{res['totals']['cost_usd'][i]:12.1f} "
              f"{res['totals']['violation'][i]:10.2f}")
    print(f"{'TOTAL':20s} {res['totals']['carbon_kg'].sum():12.1f} "
          f"{res['totals']['cost_usd'].sum():12.1f} "
          f"{res['totals']['violation'].sum():10.2f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
