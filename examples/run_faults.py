"""Realized faults end to end: plan/execute split, failover policies,
kill/resume sweeps.

Part 1 runs one day where DC 1 hard-crashes mid-afternoon and the 0↔2 WAN
link degrades — but the planner never hears about it: solvers keep
optimizing the healthy env while ``repro.faults.execute_hour`` re-projects
each hour's allocation against realized capacity. The same trace replays
under each failover policy, so the table shows what the policy choice is
worth: ``renormalize``/``spill_nearest`` serve the displaced load at a
degradation cost, ``drop`` sheds it as unserved demand.

Part 2 journals a severity sweep to disk, kills it mid-grid with the
deterministic ``inject_kill_after`` switch, then re-runs the same call:
the journal restores the completed chunks and only the remainder computes,
and the totals match an unkilled run exactly.

    PYTHONPATH=src python examples/run_faults.py
    PYTHONPATH=src python examples/run_faults.py --quick   # make faults-smoke
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import shutil
import tempfile
import time

import numpy as np

from repro import compile_cache, faults
from repro.core import ExperimentSpec, run, sweep
from repro.dcsim import env as E


def faulted_day(env, hours, technique):
    trace = faults.compose(
        faults.dc_crash(env, dc=1, start=hours // 3, duration=hours // 2),
        faults.wan_partition(env, a=0, b=2, extra_ms=300.0),
    )
    planned = run(ExperimentSpec(technique=technique, hours=hours), env)
    print(f"{'policy':15s} {'carbon_kg':>10s} {'unserved':>12s} "
          f"{'moved':>12s} {'degraded_sla$':>14s}")
    print(f"{'(no faults)':15s} {planned['totals']['carbon_kg']:10.1f} "
          f"{'—':>12s} {'—':>12s} {'—':>14s}")
    results = {}
    for policy in faults.POLICIES:
        res = run(ExperimentSpec(technique=technique, hours=hours,
                                 failover=policy), env, faults=trace)
        t = res["totals"]
        assert all(np.isfinite(v) for v in t.values()), policy
        results[policy] = t
        print(f"{policy:15s} {t['carbon_kg']:10.1f} "
              f"{t['unserved_demand']:12.1f} {t['failover_moved']:12.1f} "
              f"{t['degraded_sla_cost_usd']:14.1f}")
    assert results["drop"]["failover_moved"] == 0.0
    assert results["drop"]["unserved_demand"] > 0.0
    assert results["renormalize"]["failover_moved"] > 0.0
    return results


def kill_resume_sweep(env, hours):
    grid = {"wan_degradation": (1.0, 2.0, 4.0)}
    spec = ExperimentSpec(technique="fd", hours=hours)
    journal = tempfile.mkdtemp(prefix="faults_resume_")
    try:
        reference = sweep(spec, grid, base_env=env)
        try:
            with faults.inject_kill_after(2):
                sweep(spec, grid, base_env=env, resume_dir=journal)
            raise AssertionError("the injected kill did not fire")
        except faults.KilledMidSweep:
            pass
        resumed = sweep(spec, grid, base_env=env, resume_dir=journal)
        meta = resumed["resume"]
        print(f"killed after {meta['restored']} of {meta['chunks']} chunks; "
              f"resume computed the remaining {meta['computed']} "
              f"(retries={meta['retries']})")
        for k, v in reference["results"]["fd"]["totals"].items():
            assert np.allclose(resumed["results"]["fd"]["totals"][k], v), k
        print("resumed totals identical to the unkilled sweep")
    finally:
        shutil.rmtree(journal, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--hours", type=int, default=24)
    ap.add_argument("--technique", default="fd")
    ap.add_argument("--quick", action="store_true",
                    help="6-hour day (the `make faults-smoke` setting)")
    args = ap.parse_args()
    if args.quick:
        args.hours = 6

    env = E.build_env(args.dcs, seed=0)
    t0 = time.time()
    print("— realized faults: DC 1 crash + 0↔2 WAN partition, "
          f"{args.hours}h day, technique={args.technique} —")
    faulted_day(env, args.hours, args.technique)
    print("\n— kill/resume severity sweep —")
    kill_resume_sweep(env, args.hours)
    print(f"\nall good in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
