"""Quickstart: the paper's GT-DRL scheduler end to end in ~a minute on CPU.

Builds the 4-DC geo-distributed cloud, solves one day of hourly epochs with
GT-DRL and the NASH baseline, and prints the carbon/cost ledger — the
minimal version of the paper's Fig. 7 experiment.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp

from repro import compile_cache
from repro.core.game import GameContext, cloud_objective, uniform_fractions
from repro.core.schedulers import run_day
from repro.dcsim import env as E


def main():
    env = E.build_env(num_dcs=4, month=6, pattern="sinusoidal", seed=0)
    print(f"cloud: {E.num_dcs(env)} data centers, {E.num_players(env)} task types")
    ctx = GameContext(env=env, tau=jnp.int32(18), objective="carbon")
    v0 = float(cloud_objective(ctx, uniform_fractions(ctx), jnp.zeros((4,))))
    print(f"uniform split at 6 PM UTC: {v0:.1f} kg CO2/h")

    for technique in ("nash", "gt-drl"):
        res = run_day(env, technique, objective="carbon", seed=0, hours=24)
        t = res["totals"]
        print(f"{technique:7s}: day carbon {t['carbon_kg']:9.1f} kg, "
              f"violations {t['violation']:.2e}")
    print("done — see benchmarks/ for the full paper protocol.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
