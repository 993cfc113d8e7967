"""Reproduce the paper's day-scale experiment interactively (Figs. 7/9/11):
all six techniques through 24 hourly epochs; per-epoch carbon and the
monthly-peak cost dynamics printed as a table.

    PYTHONPATH=src python examples/schedule_day.py --objective carbon --dcs 4
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse

from repro import compile_cache
from repro.core.schedulers import TECHNIQUES, run_day
from repro.dcsim import env as E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--objective", choices=E.OBJECTIVES, default="carbon")
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--pattern", choices=("sinusoidal", "flat", "weekday",
                                          "weekend", "bursty"),
                    default="sinusoidal")
    ap.add_argument("--techniques", default=",".join(TECHNIQUES))
    args = ap.parse_args()

    env = E.build_env(args.dcs, pattern=args.pattern, seed=0)
    metric = "carbon_kg" if args.objective == "carbon" else "cost_usd"
    results = {}
    for t in args.techniques.split(","):
        res = run_day(env, t, args.objective, seed=0, hours=24)
        results[t] = res
        print(f"{t:7s} total {metric}: {res['totals'][metric]:12.1f}")

    print("\nper-epoch", metric)
    header = "hour | " + " | ".join(f"{t:>8s}" for t in results)
    print(header)
    for h in range(24):
        row = f"{h:4d} | " + " | ".join(
            f"{results[t]['per_epoch'][h][metric]:8.1f}" for t in results)
        print(row)


if __name__ == "__main__":
    compile_cache.enable()
    main()
