"""Observability end to end: tapped runs, JSONL run records, the scoreboard.

Runs the paper's techniques through the spec API with the ``engine/hour``
tap streaming, checks the telemetry contract on the way (taps-on totals ==
taps-off totals exactly; the tap series == the engine's own per-epoch
curve), appends one spec-keyed RunRecord per technique, and renders the
markdown scoreboard from the records alone — the committed
``runs/records.jsonl`` + ``SCOREBOARD.md`` pair is regenerated by::

    PYTHONPATH=src python examples/run_obs.py            # full: 5 techniques
    PYTHONPATH=src python -m repro.obs runs/records.jsonl -o SCOREBOARD.md

``--quick`` is the CI obs-smoke: 2 techniques, 3 hours, a throwaway record
file, and the same parity assertions.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import numpy as np

from repro import compile_cache, obs
from repro.core import ExperimentSpec, run
from repro.core import experiment as X
from repro.core.ddpg import DDPGConfig
from repro.core.force_directed import FDConfig
from repro.core.genetic import GAConfig
from repro.core.nash import NashConfig
from repro.core.ppo import PPOConfig
from repro.core.ppo_joint import JointPPOConfig
from repro.dcsim import env as E

# trimmed solver budgets: the scoreboard tracks telemetry plumbing, not
# paper-table numbers (benchmarks/ own those)
CFGS = {
    "fd": FDConfig(iters=60),
    "ga": GAConfig(population=16, generations=40),
    "nash": NashConfig(sweeps=3, inner_steps=20),
    "ddpg": DDPGConfig(steps=60, warmup=16),
    "ppo": JointPPOConfig(ppo=PPOConfig(horizon=4, episodes=16, iters=8)),
}


def parity_check(spec: ExperimentSpec, env) -> None:
    """taps-on == taps-off bit-for-bit, and the streamed series is the
    engine's own per-epoch metric — the tentpole's core contract."""
    base = run(spec.replace(taps=()), env)
    with obs.capture("engine/hour") as buf:
        tapped = run(spec.replace(taps=("engine/hour",)), env)
    for k, v in base["totals"].items():
        assert tapped["totals"][k] == v, (k, tapped["totals"][k], v)
    series = buf.series("engine/hour", "carbon_kg")
    expected = np.asarray([row["carbon_kg"] for row in base["per_epoch"]])
    np.testing.assert_array_equal(series, expected)
    print(f"  parity: taps-on == taps-off exactly over {spec.hours}h "
          f"({len(buf.events)} events)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: 2 techniques, 3 hours, throwaway records")
    ap.add_argument("--hours", type=int, default=None)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="records JSONL (default runs/records.jsonl, "
                         "runs/smoke.jsonl with --quick)")
    ap.add_argument("--scoreboard", default=None, metavar="PATH",
                    help="also render the markdown scoreboard here")
    args = ap.parse_args()

    techniques = ("fd", "ga") if args.quick else tuple(CFGS)
    hours = args.hours or (3 if args.quick else 6)
    out = args.out or (os.path.join("runs", "smoke.jsonl") if args.quick
                       else os.path.join("runs", "records.jsonl"))
    if os.path.exists(out):
        os.remove(out)  # records append; regenerate from scratch

    env = E.build_env(4, seed=0)
    print(f"techniques={techniques} hours={hours} -> {out}")

    parity_check(ExperimentSpec(technique=techniques[0], hours=hours,
                                cfg=CFGS[techniques[0]]), env)

    t0 = time.time()
    for t in techniques:
        spec = ExperimentSpec(technique=t, objective="carbon", hours=hours,
                              cfg=CFGS.get(t), taps=("engine/hour",))
        with obs.capture("engine/hour") as buf:
            res = run(spec, env, record=out)
        st = obs.engine_stat(X._engine_key(spec)) or {}
        print(f"  {t:6s} carbon={res['totals']['carbon_kg']:9.1f} kg  "
              f"events={len(buf.events)}  "
              f"compile={st.get('first_dispatch_s', 0.0):.2f}s  "
              f"dispatch={st.get('last_dispatch_s', 0.0) * 1e3:.0f}ms")
    print(f"wrote {len(techniques)} records to {out} "
          f"({time.time() - t0:.0f}s)")

    # default title so `make scoreboard` regenerates the identical file
    md = obs.report(obs.load_records(out))
    board = args.scoreboard
    if board is None and not args.quick:
        board = "SCOREBOARD.md"
    if board:
        with open(board, "w") as f:
            f.write(md)
        print(f"wrote {board}")
    else:
        print(md)

    stats = obs.cache_stats()
    print(f"engine cache: {stats['misses']} compiles, {stats['hits']} hits, "
          f"{stats['live_keys']} live artifacts")


if __name__ == "__main__":
    compile_cache.enable()
    main()
