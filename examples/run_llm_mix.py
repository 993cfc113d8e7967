"""The token-grounded llm workload through all six techniques.

``build_env(workload="llm")`` replaces the paper's hand-set AIBench task
constants with model families from the ``configs/`` zoo: each DC's tasks/h,
W and ms are *derived* from the roofline constants applied to that DC's
accelerator mix (``dcsim/capability.py`` — tokens/sec/chip from the
compute/memory/collective bottleneck, J/token from node power, KV-cache
occupancy batching). Task classes become model families, so the
``workload_mix_shift`` day evaluated here — traffic tilting from the small
chat models toward the 480B MoE mid-day — is a *workload* severity axis
orthogonal to grid events: total arrivals per hour are unchanged, but the
fleet-wide J/token of the demanded mix moves, and schedulers that chase
carbon/price signals must now also respect wildly different per-family
capability tables.

    PYTHONPATH=src python examples/run_llm_mix.py
    PYTHONPATH=src python examples/run_llm_mix.py --quick   # make llm-smoke
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import time

import numpy as np

from repro import compile_cache, scenarios as S
from repro.core import ExperimentSpec, run
from repro.core import gt_drl
from repro.core.ddpg import DDPGConfig
from repro.core.force_directed import FDConfig
from repro.core.genetic import GAConfig
from repro.core.nash import NashConfig
from repro.core.ppo import PPOConfig
from repro.core.ppo_joint import JointPPOConfig
from repro.core.schedulers import TECHNIQUES
from repro.dcsim import capability as C
from repro.dcsim import env as E

_SMOKE_PPO = PPOConfig(horizon=2, episodes=8, iters=2, update_epochs=1)
SMOKE_CFGS = {
    "fd": FDConfig(iters=20),
    "ga": GAConfig(population=8, generations=10),
    "nash": NashConfig(sweeps=1, inner_steps=10),
    "ddpg": DDPGConfig(steps=16, batch=8, buffer=64, warmup=8),
    "ppo": JointPPOConfig(ppo=_SMOKE_PPO),
    "gt-drl": gt_drl.GTDRLConfig(ppo=_SMOKE_PPO, rounds=2, polish_steps=5,
                                 pretrain_iters=4, pretrain_batch=2),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dcs", type=int, default=4, choices=(4, 8, 16))
    ap.add_argument("--hours", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--weight", type=float, default=0.5,
                    help="workload_mix_shift blend toward the 480B MoE")
    ap.add_argument("--quick", action="store_true",
                    help="6 hours, tiny solver budgets (`make llm-smoke`)")
    args = ap.parse_args()
    hours = 6 if args.quick else args.hours

    env = E.build_env(args.dcs, seed=args.seed, workload="llm")
    fams = dict(C.LLM_FAMILIES)
    names = tuple(fams)
    moe = names.index("moe-480b")

    cap = C.LLMWorkload().capabilities(args.dcs, args.seed)
    print(f"llm capability layer: {len(names)} model families x "
          f"{args.dcs} DCs (accelerator mixes from topology.accel_mix)\n")
    print(f"{'family':14s} {'arch':20s} {'tok/s/chip':>11s} {'J/token':>9s} "
          f"{'chips':>6s} {'bound':>10s}")
    for i, n in enumerate(names):
        print(f"{n:14s} {fams[n].arch:20s} "
              f"{cap.meta['tokens_per_s_chip'][i].mean():11.0f} "
              f"{cap.meta['j_per_token'][i].mean():9.3f} "
              f"{cap.meta['n_chips'][i].max():6d} "
              f"{cap.meta['bottleneck'][i, 0]:>10s}")

    # the workload-mix day: traffic tilts toward the 480B MoE mid-day
    day = S.make("workload_mix_shift", toward=(moe,), weight=args.weight,
                 start=8, duration=10)(env)

    print("\nsix techniques on the mix-shift day "
          f"(weight={args.weight} toward moe-480b, hours={hours}):\n")
    print(f"{'technique':10s} {'carbon_kg':>11s} {'cost_usd':>11s} "
          f"{'violation':>10s} {'wall_s':>7s}")
    totals = {}
    for t in TECHNIQUES:
        spec = ExperimentSpec(technique=t, objective="carbon", hours=hours,
                              seed=args.seed, workload="llm",
                              cfg=SMOKE_CFGS[t] if args.quick else None)
        t0 = time.time()
        res = run(spec, day)
        wall = time.time() - t0
        totals[t] = res["totals"]
        print(f"{t:10s} {res['totals']['carbon_kg']:11.1f} "
              f"{res['totals']['cost_usd']:11.1f} "
              f"{res['totals']['violation']:10.3f} {wall:7.1f}")

    for t in TECHNIQUES:
        assert np.isfinite(totals[t]["carbon_kg"]), t
        assert np.isfinite(totals[t]["cost_usd"]), t
    base = run(ExperimentSpec(technique="fd", objective="carbon", hours=hours,
                              seed=args.seed, workload="llm",
                              cfg=SMOKE_CFGS["fd"] if args.quick else None),
               env)
    print(f"\nfd on the unshifted day: {base['totals']['carbon_kg']:.1f} kg "
          "(mix shift moves the demanded J/token, same hourly arrivals); "
          f"all six techniques finite on the derived I={len(names)} env.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
