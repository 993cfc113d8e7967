"""Control plane meets data plane: GT-DRL routes real inference traffic.

Stands up a miniature serving fleet (3 architectures × 2 data centers,
reduced configs), lets the paper's GT-DRL scheduler compute the arrival-rate
split for the current hour, and dispatches actual batched prefill+decode
requests according to that split — the full loop the paper's CWM/DWM
architecture describes.

    PYTHONPATH=src python examples/serve_fleet.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.core import gt_drl
from repro.core.game import GameContext, fractions_to_ar
from repro.dcsim import env as E
from repro.launch.serve import Fleet


def main():
    archs = ["llama3.2-1b", "qwen2-moe-a2.7b", "recurrentgemma-9b"]
    num_dcs = 2
    print(f"fleet: {archs} x {num_dcs} DCs (reduced configs)")
    fleet = Fleet(archs, num_dcs, smoke=True, batch_size=4, cache_len=64)

    env = E.build_env(4, seed=0)
    ctx = GameContext(env=env, tau=jnp.int32(14), objective="cost")
    cfg = gt_drl.GTDRLConfig(rounds=2, pretrain_iters=0)
    agents = gt_drl.init_agents(jax.random.PRNGKey(0), env, cfg)
    agents, res = gt_drl.solve_epoch(
        jax.random.PRNGKey(1), agents, ctx, jnp.zeros((4,)), cfg)
    ar = fractions_to_ar(ctx, res.fractions)
    print("GT-DRL arrival-rate split (tasks/h), first 3 types x first 2 DCs:")
    print(jnp.round(ar[:3, :2]).astype(int))

    report = fleet.route(ar[: len(archs), :num_dcs], requests_per_unit=2,
                         prompt_len=12, max_new=4)
    print(f"dispatched {report['total']} requests")
    for (i, d), n in sorted(report["dispatched"].items()):
        print(f"  arch={archs[i]:18s} dc={d}: {n} requests")
    for k, tps in report["per_server_tps"].items():
        print(f"  server {k}: {tps:.1f} tok/s decode")


if __name__ == "__main__":
    compile_cache.enable()
    main()
