"""Shared helpers for the benchmark harness (paper experiment protocol)."""
from __future__ import annotations

import os
from typing import List

from repro import obs
from repro.dcsim import env as E

QUICK = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"

RUNS = 2 if QUICK else int(os.environ.get("REPRO_BENCH_RUNS", "2"))  # paper: 5 runs
HOURS = 6 if QUICK else 24        # paper: 24 one-hour epochs
TECHNIQUES = ("fd", "ga", "nash", "ddpg", "ppo", "gt-drl")


def build_envs(num_dcs: int, runs: int = RUNS, pattern: str = "sinusoidal",
               month: int = 6) -> List[E.EnvParams]:
    """One env per run: same infrastructure, resampled arrival rates
    (the paper's normal resampling with 20% std)."""
    return [E.build_env(num_dcs, seed=r, pattern=pattern, month=month)
            for r in range(runs)]


class Timer(obs.Span):
    """A bench region timer; now an ``obs.Span`` so benchmark timings land
    in the same span stream as the engine telemetry (``obs.all_spans()``)."""

    def __init__(self):
        super().__init__(name="bench")


def emit(rows: List[str], name: str, seconds: float, derived: str):
    """CSV row: name, microseconds per call, derived metric string."""
    rows.append(f"{name},{seconds * 1e6:.0f},{derived}")
    print(rows[-1], flush=True)
