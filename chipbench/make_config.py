"""Write the deployment's configuration file from the program's env builder.

    PYTHONPATH=src python chipbench/make_config.py us16-aibench > chipbench/configs/us16-aibench.json

The benchmark never runs this: it reads the committed file. The file holds
every array the planner and simulator see (``EnvParams``), so both the
program and the plain reference run on exactly these numbers, and a later
change to the env builder does not move the benchmark's inputs.

The deployment is the paper's largest fleet at the paper's settings
(arXiv:2404.01459 section 6): 16 US data centers on an even east/west mix
(Fig. 5), each with 4,320 nodes of up to three Xeon types, the ten AIBench
inference task types (Table 2), sinusoidal consumer arrivals (Fig. 6), no
WAN delay and no SLA price (the paper has neither). The program's routed
game plays it with a uniform demand origin per region (S = 16), which at
these settings is the paper's model exactly; the objective ``cost_sla`` is
the paper's cost objective while no SLA is priced. Builder settings that
the repository gives without a citation are listed under ``assumed``.

``scenario_rtt_ms`` is not part of the deployment: it is where a sweep's
WAN-degradation event starts on a fleet that has no WAN delay, the
great-circle RTTs between the 16 regions (fiber at c/1.5, path stretch 1.4,
2 ms per hop), as the program's transform seeds it.
"""
from __future__ import annotations

import json
import sys

import numpy as np

NUM_DCS = 16
SOURCE = ("https://arxiv.org/abs/2404.01459 section 6 (16 US DCs, Fig. 5), "
          "Table 2 (AIBench tasks), Fig. 6 (sinusoidal arrivals, "
          "N(CAR, 0.2 CAR) per run)")


def make(name: str) -> dict:
    from repro.dcsim import env as E
    from repro.dcsim import latency

    env = E.build_env(NUM_DCS, seed=0, workload="aibench")
    wan = latency.rtt_matrix(num_dcs=NUM_DCS)
    arrays = {k: np.asarray(v, np.float32).tolist()
              for k, v in env._asdict().items()}
    return {
        "name": name,
        "source": SOURCE,
        "deployment": ("16 US data centers (paper section 6, largest fleet), "
                       "the paper's ten AIBench task types on the Xeon "
                       "fleet; no WAN delay, no SLA price; demand routed "
                       "per source region (S = 16) from a uniform origin; "
                       "24 one-hour epochs"),
        "workload": "aibench",
        "num_dcs": NUM_DCS,
        "routed": True,
        "objective": "cost_sla",
        "hours": 24,
        "precision": "float32",
        "guarantees": {
            "demand_placed": "every hour's demand is placed (eq. 1)",
            "capacity": "no DC runs above its realized capacity (eq. 2)",
            "violation_share": 1e-5,
            "physical_bounds": 1e-5,
        },
        "reduced": [],
        "assumed": [
            "month: June (solar and wind scale of the renewables model)",
            "peak utilization 0.45 of each task type's capacity, task mix "
            "Dirichlet(3) with seed 1234",
            "on-site renewables sized at 0.8 of idle + half dynamic IT draw",
            "infrastructure draws (supply temperature, PSU efficiency, "
            "renewable noise) from seed 0",
        ],
        "env": arrays,
        # not the deployment's: where the traffic's WAN-degradation events
        # start on a fleet with no WAN delay (the program's transform seeds
        # it from the same great-circle geometry)
        "scenario_rtt_ms": np.asarray(wan, np.float64).tolist(),
    }


if __name__ == "__main__":
    json.dump(make(sys.argv[1]), sys.stdout)
    sys.stdout.write("\n")
