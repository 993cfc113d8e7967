"""Month-scale episode: one compiled call scans a scheduler across a whole
month of days (weekday/weekend traffic, per-day arrival resamples), threading
the monthly peak-demand state — the peak charge becomes a planning signal:

    PYTHONPATH=src python examples/run_month.py --technique fd --days 30
    PYTHONPATH=src python examples/run_month.py --technique nash --objective cost

Prints per-day carbon / cost / running monthly peak, then the month totals.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

from repro import compile_cache, scenarios as S
from repro.core.schedulers import TECHNIQUES, run_month
from repro.dcsim import env as E


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--technique", choices=TECHNIQUES, default="fd")
    ap.add_argument("--objective", choices=E.OBJECTIVES, default="carbon")
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--days", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    base = E.build_env(args.dcs, seed=args.seed)
    month = S.build_month(base, days=args.days, seed=args.seed)
    names = [n for n, _ in month]
    envs = [e for _, e in month]

    t0 = time.time()
    res = run_month(envs, args.technique, args.objective, seed=args.seed)
    dt = time.time() - t0

    print(f"technique={args.technique} objective={args.objective} "
          f"days={args.days} wall={dt:.1f}s ({dt / args.days * 1e3:.0f} ms/day)")
    print(f"{'day':16s} {'carbon_kg':>12s} {'cost_usd':>12s} {'peak_kw':>10s}")
    for i, name in enumerate(names):
        print(f"{name:16s} {res['day_totals']['carbon_kg'][i]:12.1f} "
              f"{res['day_totals']['cost_usd'][i]:12.1f} "
              f"{res['peak_w'][i].max() / 1e3:10.1f}")
    print(f"{'MONTH':16s} {res['totals']['carbon_kg']:12.1f} "
          f"{res['totals']['cost_usd']:12.1f} "
          f"{res['final_peak_w'].max() / 1e3:10.1f}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
