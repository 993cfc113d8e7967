"""Benchmark harness: one benchmark per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (and tees a summary). Set
REPRO_BENCH_QUICK=1 for a fast smoke pass. ``--json PATH`` additionally
writes the rows as machine-readable JSON (the perf-trajectory workflow:
``make bench-smoke`` commits ``BENCH_engine.json`` so every perf PR records
its loop-vs-scan-vs-batched timings).

    PYTHONPATH=src python -m benchmarks.run [--only carbon,costs,...] [--json out.json]
"""
from __future__ import annotations

import argparse
import json
import time

from repro import compile_cache

ALL = ("carbon", "scalability", "arrival", "renewables", "costs", "scenarios",
       "engine", "roofline", "micro")


def rows_to_json(rows, which, wall_s: float) -> dict:
    """Parse the CSV rows into the BENCH_*.json payload."""
    from repro import obs

    from .common import HOURS, QUICK, RUNS
    entries = []
    for r in rows[1:]:  # skip the header
        name, us, derived = r.split(",", 2)
        entries.append({"name": name, "us_per_call": float(us),
                        "derived": derived})
    return {
        "meta": {
            "which": list(which),
            "quick": QUICK,
            "hours": HOURS,
            "runs": RUNS,
            "wall_s": round(wall_s, 1),
            "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            # provenance: a perf number without the machine/toolchain that
            # produced it is not comparable across PRs
            **obs.run_info(),
        },
        "rows": entries,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as JSON to PATH")
    args = ap.parse_args()
    compile_cache.enable()
    which = tuple(args.only.split(",")) if args.only else ALL

    rows = ["name,us_per_call,derived"]
    print(rows[0], flush=True)
    t0 = time.time()

    carbon_res = None
    if "carbon" in which:
        from . import bench_carbon
        carbon_res = bench_carbon.run(rows)
    if "scalability" in which:
        from . import bench_scalability
        bench_scalability.run(rows, carbon_4dc=carbon_res)
    if "arrival" in which:
        from . import bench_arrival
        bench_arrival.run(rows)
    if "renewables" in which:
        from . import bench_renewables
        bench_renewables.run(rows)
    if "costs" in which:
        from . import bench_costs
        bench_costs.run(rows)
    if "scenarios" in which:
        from . import bench_scenarios
        bench_scenarios.run(rows)
    if "engine" in which:
        from . import bench_engine
        bench_engine.run(rows)
    if "roofline" in which:
        from . import bench_roofline
        bench_roofline.run(rows)
    if "micro" in which:
        from . import bench_microbench
        bench_microbench.run(rows)

    wall = time.time() - t0
    print(f"# total benchmark wall time: {wall:.0f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows_to_json(rows, which, wall), f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
