"""Helpers for the benchmark's own tests (CPU only; no device at import).

``write_bench`` writes a throwaway benchmark into a temporary directory: a
4-DC cut of the ``us16-aibench`` deployment, 4 hours, and mixes that only
exist there: fd sweeps (plain, faulted, journaled), a sharded batched fd
``run`` and a gt-drl day. The harness finds them by name as it finds the
committed ones.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

D = 4
HOURS = 4
MIXES = {
    "tiny-fd-sweep": {"call": "sweep", "spec": {"technique": "fd"},
                      "pool": 2, "arrival_std": 0.2,
                      "grid": {"wan_degradation": [1.0, 4.0],
                               "sla_tighten": [1.0, 0.05]}},
    "tiny-fd-faults": {"call": "sweep",
                       "spec": {"technique": "fd",
                                "failover": "spill_nearest"},
                       "pool": 2, "arrival_std": 0.2,
                       "faults": {"n_events": 3},
                       "grid": {"wan_degradation": [1.0, 3.0]}},
    "tiny-fd-batch-shard": {"call": "run",
                            "spec": {"technique": "fd", "engine": "batched",
                                     "failover": "spill_nearest"},
                            "kwargs": {"shard": True}, "batch": 3,
                            "pool": 2, "arrival_std": 0.2,
                            "faults": {"n_events": 2}},
    "tiny-fd-sweep-resume": {"call": "sweep", "spec": {"technique": "fd"},
                             "kwargs": {"resume_dir": "per_call",
                                        "chunk_points": 2},
                             "pool": 2, "arrival_std": 0.2,
                             "grid": {"sla_tighten": [1.0, 0.8, 0.6]}},
    "tiny-gtdrl-day": {"call": "run", "spec": {"technique": "gt-drl"},
                       "deploy": True, "pool": 2, "arrival_std": 0.2},
}
CELLS = {"small-fd-sweep": "tiny-fd-sweep", "small-fd-faults": "tiny-fd-faults",
         "small-fd-batch-shard": "tiny-fd-batch-shard",
         "small-fd-sweep-resume": "tiny-fd-sweep-resume",
         "small-gtdrl-day": "tiny-gtdrl-day"}


def tiny_gtdrl(monkeypatch) -> None:
    """Make gt-drl's registered default config a tiny one, so that a test
    can deploy and run it in seconds."""
    from repro.core import game
    from repro.core.gt_drl import GTDRLConfig
    from repro.core.ppo import PPOConfig

    tiny = GTDRLConfig(ppo=PPOConfig(horizon=2, episodes=2, iters=1,
                                     update_epochs=1),
                       rounds=1, polish_steps=2, pretrain_iters=2,
                       pretrain_batch=2)
    t = game.get_technique("gt-drl")
    monkeypatch.setitem(game._TECHNIQUES, "gt-drl",
                        t._replace(default_cfg=tiny))


def small_config() -> dict:
    """The first ``D`` data centers of the committed 16-DC deployment."""
    with open(os.path.join(BENCH, "configs", "us16-aibench.json")) as f:
        cfg = json.load(f)
    env = {k: np.asarray(v) for k, v in cfg["env"].items()}
    cut = {}
    for k, v in env.items():
        if k in ("er",):
            v = v[:, :D]
        elif k == "rtt":
            v = v[:D, :D]
        elif k == "origin":
            v = v[:D] / v[:D].sum(axis=0, keepdims=True)
        elif v.ndim >= 1 and v.shape[0] == 16:
            v = v[:D]
        cut[k] = v.tolist()
    from repro.dcsim import latency

    return {**cfg, "name": "small-aibench", "num_dcs": D, "hours": HOURS,
            "env": cut,
            "scenario_rtt_ms": latency.rtt_matrix(num_dcs=D).tolist()}


def write_bench(root, limits=None) -> str:
    """A benchmark root holding ``BENCHMARK.json`` and a ``chipbench``
    directory with the committed readers and the small cells' files."""
    root = str(root)
    bench = os.path.join(root, "chipbench")
    for part in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, part), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"))
    with open(os.path.join(bench, "configs", "small-aibench.json"), "w") as f:
        json.dump(small_config(), f)
    for name, mix in MIXES.items():
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    # at 4 DCs and 4 hours fd's greedy path forks more easily than at the
    # cells' size: sound runs read plan_gap up to ~3e-2 on the tests' seed;
    # half of a sweep's batch left out reads 6e-2 or more, the bfloat16
    # control 0.12 or more
    lim = limits or {"demand_gap": 1e-4, "sum_gap": 1e-5, "plan_gap": 4e-2}
    for cell in CELLS:
        with open(os.path.join(bench, "limits", cell + ".json"), "w") as f:
            json.dump(lim, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"] = [{"name": "small-aibench", "source": "test",
                            "file": "chipbench/configs/small-aibench.json",
                            "reduced": ["num_dcs", "hours"], "why": "test"}]
    manifest["workloads"] = [{"name": cell, "config": "small-aibench",
                              "traffic": mix, "chips": 1, "why": "test"}
                             for cell, mix in CELLS.items()]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root

