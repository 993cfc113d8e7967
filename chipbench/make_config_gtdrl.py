"""Write the GT-DRL deployment's configuration file.

    PYTHONPATH=src python chipbench/make_config_gtdrl.py us16-aibench-gtdrl > chipbench/configs/us16-aibench-gtdrl.json

The deployment is the paper's own: its scheduler, GT-DRL (arXiv:2404.01459
section 5.3: one PPO agent per task type, best responses in red-black
rounds), on the fleet that ``make_config`` writes (section 6), with the
agents trained once and reused every day. The fleet, its arrivals, the
objective, the precision and the guarantees are ``make_config.make``'s,
number for number. ``scheduler`` adds the learner as the program's
registered gt-drl default runs it, which is the configuration the cell's
traffic asks for; the paper gives none of these sizes, so all are listed
under ``assumed``. The benchmark never runs this: it reads the committed
file, and a test holds that file to this function.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import make_config  # noqa: E402

SOURCE = ("https://arxiv.org/abs/2404.01459 section 5.3 (GT-DRL: PPO best "
          "responses in red-black rounds, deploy-once) on the section 6 "
          "fleet (16 US DCs, Fig. 5, Table 2 tasks)")


def scheduler() -> dict:
    """The learner's sizes as the program's registered default runs them on
    this fleet: players, state and action widths, the actor and critic
    layer widths, the matmul precision, and every ``GTDRLConfig`` field."""
    import numpy as np

    from repro.core import game, gt_drl, networks
    from repro.dcsim import env as E

    cfg = game.get_technique("gt-drl").default_cfg
    env = E.build_env(make_config.NUM_DCS, seed=0, workload="aibench")
    sd = gt_drl.state_dim(env, cfg.state_mode, True)
    ad = int(np.prod(gt_drl._row_shape(env, True)))
    hidden = list(cfg.ppo.hidden)
    return {
        "technique": "gt-drl",
        "players": int(E.num_players(env)),
        "state_dim": sd,
        "action_dim": ad,
        "actor_widths": [sd, *hidden, ad],
        "critic_widths": [sd, *hidden, 1],
        "learner_matmul_precision": networks.PRECISION.name,
        "gtdrl": json.loads(json.dumps(dataclasses.asdict(cfg))),
    }


def make(name: str) -> dict:
    out = make_config.make(name)
    sched = scheduler()
    out.update(
        source=SOURCE,
        deployment=("the paper's GT-DRL scheduler, agents pretrained once "
                    "and reused every day, on: " + out["deployment"]),
        scheduler=sched,
        assumed=out["assumed"] + [
            "learner sizes (scheduler): the program's GTDRLConfig and "
            "PPOConfig defaults; the paper gives no hidden widths, PPO "
            "sizes, rounds or pretraining length",
        ])
    return out


if __name__ == "__main__":
    json.dump(make(sys.argv[1]), sys.stdout)
    sys.stdout.write("\n")
