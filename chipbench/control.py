"""The control: the plain reference put in the program's place, in bfloat16.

    python chipbench/control.py --workload <cell> --seeds 1,2,3

The configurations state float32, so the control computes in the nearest
precision below it. For each seed it builds the cell's inputs as a run
does, plays every answer of ``calls`` calls (by default one per pool day,
which is every distinct answer a window can hold) with the bfloat16
reference fd day standing in for the program (its day totals summed in
bfloat16 too), and hands them to ``chipbench.correct.check`` as a run
hands its own. ``correct`` has to come
out false on every seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import correct as C  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench.manifest import Manifest  # noqa: E402
from chipbench.traffic import Inputs  # noqa: E402


def control_run(inputs: Inputs, limits, seed: int,
                calls: Optional[int] = None) -> Dict[str, Any]:
    """``correct``, its numbers and the failed count, with the bfloat16
    reference's answers in the program's place."""
    import jax
    import jax.numpy as jnp

    if inputs.technique != "fd":
        raise ValueError("the control stands the fd reference in for the "
                         f"program; {inputs.technique!r} has none")
    calls = calls or len(inputs.pool)
    keys = [(k, r) for k in range(calls) for r in range(inputs.rows)]
    envs = [inputs.env_of(k, r) for k, r in keys]
    traces = (None if inputs.traces is None
              else [inputs.trace_of(k, r) for k, r in keys])
    with jax.enable_x64(True):
        low = R.fd_days(envs, inputs.hours, jnp.bfloat16, traces=traces)
    answers = [(k, r, {"per_epoch": per,
                       "totals": {m: float(np.sum(np.asarray(v, jnp.bfloat16)))
                                  for m, v in per.items()}})
               for (k, r), per in zip(keys, low)]
    checks, failed = C.check(inputs, answers, limits, seed)
    return {"correct": all(v <= lim for _, v, lim in checks) and failed == 0,
            "failed": failed, "attempted": len(answers),
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from chipbench.run import with_host_cpu

    with_host_cpu()
    m = Manifest(ROOT)
    cell = m.cell(args.workload)
    config, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    limits = m.limits(args.workload)
    verdicts = []
    for s in (int(x) for x in args.seeds.split(",")):
        out = control_run(Inputs(config, mix, s), limits, s)
        print(json.dumps({"seed": s, **out}), flush=True)
        verdicts.append(out["correct"])
    return 1 if any(verdicts) else 0


if __name__ == "__main__":
    sys.exit(main())
