"""The one traffic generator: a mix file of parameters -> public API calls.

A mix (``chipbench/traffic/<mix>.json``) says what one call of the closed
loop is. Keys:

- ``call``: the public entry point, ``"run"`` or ``"sweep"``
  (``repro.core``).
- ``spec``: ``ExperimentSpec`` fields (``technique``, ``engine``,
  ``failover``, ``guard``, ``taps``, ...). The configuration gives
  ``objective``, ``routed``, ``hours`` and ``workload``; ``cfg`` is the
  technique's registered default.
- ``kwargs``: passed to the call as they stand (``shard``,
  ``chunk_points``, ``max_retries``, ...). ``resume_dir`` takes
  ``"per_call"`` (a new journal directory for every call) or ``"per_run"``
  (one for the run, so later calls restore what the first computed).
- ``batch`` (``run`` only): env days per call; more than one needs the
  batched engine.
- ``grid`` (``sweep`` only): the severity grid, passed to ``sweep``.
- ``deploy``: build the stateful solver's carry once in set-up (gt-drl's
  pretraining, the paper's deploy-once protocol) and pass it to every
  ``run`` as ``solver_state0``.
- ``pool``, ``arrival_std``: the calls cycle through ``pool`` days whose
  hourly arrivals are the configuration's, each resampled as
  N(car, std * car) floored at 5 % (the paper's run-to-run variation).
- ``faults``: ``{"n_events": n}`` gives every row of every pool day its
  own random day of trouble (crashes, brownouts, WAN partitions, stale
  telemetry), executed under the spec's ``failover``.

A call's rows are its grid points (``sweep``) or its env days (``run``).
Everything is drawn from ``--seed``: the pool's arrivals, the fault traces,
the solver keys of each call and of the deploy step. Every seed gives the
same sizes and the same number of rows per call; only the values differ.
"""
from __future__ import annotations

import os
import shutil
import tempfile
from typing import Any, Dict, List, Mapping, Optional

import numpy as np

from chipbench import reference as R

SEED_SPAN = 2 ** 30   # program seeds stay inside int32 whatever --seed is
FAULT_KINDS = ("dc_crash", "brownout", "wan_partition", "telemetry_dropout")


def random_trace(rng: np.random.Generator, d: int, n_events: int,
                 hours: int = 24) -> Dict[str, np.ndarray]:
    """A random day of trouble as the fault trace's four arrays: each event
    picks a kind, a target, a start hour and a 2-12 h window."""
    avail = np.ones((d, hours))
    rtt = np.zeros((d, d, hours))
    price = np.ones((d, hours))
    carbon = np.ones((d, hours))
    for _ in range(n_events):
        kind = FAULT_KINDS[int(rng.integers(len(FAULT_KINDS)))]
        start = int(rng.integers(0, hours))
        w = (((np.arange(hours) - start) % hours)
             < int(rng.integers(2, 13))).astype(np.float64)
        if kind == "dc_crash":
            avail[int(rng.integers(d))] *= 1.0 - w
        elif kind == "brownout":
            dc = int(rng.integers(d))
            avail[dc] *= 1.0 - float(rng.uniform(0.2, 0.8)) * w
        elif kind == "wan_partition":
            a, b = rng.choice(d, size=2, replace=False)
            extra = float(rng.uniform(100, 800)) * w
            rtt[a, b] += extra
            rtt[b, a] += extra
        else:
            dc = int(rng.integers(d))
            price[dc] *= 1.0 + (float(rng.uniform(0.5, 2.5)) - 1.0) * w
            carbon[dc] *= 1.0 + (float(rng.uniform(0.5, 2.5)) - 1.0) * w
    return {k: v.astype(np.float32) for k, v in (
        ("avail_mult", avail), ("rtt_extra_ms", rtt), ("price_mult", price),
        ("carbon_mult", carbon))}


class Inputs:
    """The data of one run, made from the seed: numpy only, shared by the
    program side and the reference."""

    def __init__(self, config: Mapping[str, Any], mix: Mapping[str, Any],
                 seed: int):
        self.config, self.mix = config, mix
        rng = np.random.default_rng(seed)
        base = {k: np.asarray(v, np.float32)
                for k, v in config["env"].items()}
        std = float(mix.get("arrival_std", 0.0))
        self.pool: List[Dict[str, np.ndarray]] = []
        for _ in range(int(mix.get("pool", 1))):
            car = base["car"].astype(np.float64)
            if std:
                car = np.clip(rng.normal(car, std * car), 0.05 * car, None)
            self.pool.append({**base, "car": car.astype(np.float32)})
        self.key_seed = int(rng.integers(SEED_SPAN))
        self.deploy_seed = int(rng.integers(SEED_SPAN))
        self.hours = int(config["hours"])
        spec = mix.get("spec", {})
        self.technique = spec.get("technique", "fd")
        self.failover = spec.get("failover", "renormalize")
        if mix["call"] == "sweep":
            self.points: Optional[List[dict]] = R.grid_points(mix["grid"])
            self.rows = len(self.points)
        else:
            self.points = None
            self.rows = int(mix.get("batch", 1))
        self.traces = None
        if mix.get("faults"):
            d = base["er"].shape[1]
            n = int(mix["faults"]["n_events"])
            per_day = self.rows if self.points is not None else 1
            self.traces = [[random_trace(rng, d, n) for _ in range(per_day)]
                           for _ in self.pool]

    @property
    def fleet_hours_per_call(self) -> int:
        return self.rows * self.hours

    def seed_of(self, k: int, row: int = 0) -> int:
        """The program seed of call ``k``'s ``row`` (a sweep's points share
        their call's)."""
        if self.points is not None:
            return (self.key_seed + k) % SEED_SPAN
        return (self.key_seed + k * self.rows + row) % SEED_SPAN

    def pool_of(self, k: int, row: int = 0) -> int:
        """Which pool day call ``k``'s ``row`` plans on: a sweep's base day,
        or the next day of the pool for each env of a ``run``."""
        if self.points is not None:
            return k % len(self.pool)
        return (k * self.rows + row) % len(self.pool)

    def trace_of(self, k: int, row: int = 0):
        """The fault trace of call ``k``'s ``row``, or None."""
        if self.traces is None:
            return None
        return self.traces[self.pool_of(k, row)][
            row if self.points is not None else 0]

    def env_of(self, k: int, row: int = 0) -> Dict[str, np.ndarray]:
        """The float32 env that call ``k``'s ``row`` plans on, as the
        reference sees it (sweep points transformed in float64)."""
        env = self.pool[self.pool_of(k, row)]
        if self.points is None:
            return env
        return R.scenario({k_: np.asarray(v, np.float64)
                           for k_, v in env.items()}, self.points[row],
                          self.config.get("scenario_rtt_ms"))


class Caller:
    """Turns ``Inputs`` into the program's own inputs and makes calls."""

    def __init__(self, inputs: Inputs):
        import jax.numpy as jnp

        from repro.core import ExperimentSpec, game
        from repro.dcsim.env import EnvParams

        self.inputs = inputs
        cfg, mix = inputs.config, inputs.mix
        fields = dict(mix.get("spec", {}))
        if fields.get("taps") is not None:
            fields["taps"] = tuple(fields["taps"])
        technique = fields.get("technique", "fd")
        self.spec = ExperimentSpec(
            objective=cfg["objective"], routed=bool(cfg["routed"]),
            hours=inputs.hours, workload=cfg["workload"],
            cfg=game.get_technique(technique).default_cfg, **fields)
        self.technique = self.spec.technique
        self.envs = [EnvParams(**{k: jnp.asarray(v) for k, v in e.items()})
                     for e in inputs.pool]
        self.kwargs = dict(mix.get("kwargs", {}))
        self.scratch = None
        if "resume_dir" in self.kwargs:
            self.scratch = tempfile.mkdtemp(prefix="chipbench-resume-")
        self.state0 = None
        self.traces = None
        if inputs.traces is not None:
            from repro.faults import FaultTrace

            self.traces = [[FaultTrace(**{k: jnp.asarray(v)
                                          for k, v in t.items()})
                            for t in day] for day in inputs.traces]

    def close(self) -> None:
        """Remove what the calls wrote (sweep journals)."""
        if self.scratch is not None:
            shutil.rmtree(self.scratch, ignore_errors=True)
            self.scratch = None

    def deploy(self) -> None:
        """Build the stateful solver's carry once (set-up)."""
        if not self.inputs.mix.get("deploy"):
            return
        import jax

        from repro.core import game

        spec = self.spec
        t = game.get_technique(spec.technique)
        self.state0 = t.init_state(
            jax.random.PRNGKey(self.inputs.deploy_seed), self.envs[0],
            spec.objective, spec.cfg, spec.routed, True)
        jax.block_until_ready(self.state0)

    def _kwargs(self, k: int) -> Dict[str, Any]:
        kw = dict(self.kwargs)
        where = kw.get("resume_dir")
        if where is not None:
            if where not in ("per_call", "per_run"):
                raise ValueError(f"resume_dir {where!r}: per_call or per_run")
            kw["resume_dir"] = os.path.join(
                self.scratch, f"call{k}" if where == "per_call" else "run")
        return kw

    def call(self, k: int) -> Dict[str, Any]:
        """Make call ``k`` through the public API; returns its result."""
        from repro.core import run, sweep
        from repro.faults import stack_traces

        inp, mix = self.inputs, self.inputs.mix
        rows = range(inp.rows)
        if mix["call"] == "sweep":
            faults = (None if self.traces is None
                      else self.traces[inp.pool_of(k)])
            return sweep(self.spec.replace(seed=inp.seed_of(k)), mix["grid"],
                         base_env=self.envs[inp.pool_of(k)], faults=faults,
                         **self._kwargs(k))
        envs = [self.envs[inp.pool_of(k, r)] for r in rows]
        faults = None
        if self.traces is not None:
            faults = [self.traces[inp.pool_of(k, r)][0] for r in rows]
        if self.spec.engine == "scan":
            return run(self.spec.replace(seed=inp.seed_of(k)), envs[0],
                       solver_state0=self.state0,
                       faults=None if faults is None else faults[0],
                       **self._kwargs(k))
        seeds = tuple(inp.seed_of(k, r) for r in rows)
        return run(self.spec.replace(seeds=seeds), envs,
                   solver_state0=self.state0,
                   faults=None if faults is None else stack_traces(faults),
                   **self._kwargs(k))

    def answers(self, result: Mapping[str, Any]) -> List[Dict[str, Any]]:
        """One answer per row of a call's result: its per-epoch metrics
        (hours,) and its totals, as the public API returned them."""
        node = result.get("results", {}).get(self.technique, result)
        per_epoch, totals = node["per_epoch"], node["totals"]
        if isinstance(per_epoch, list):      # one day, as rows of hours
            per_epoch = {k: np.asarray([h[k] for h in per_epoch])[None]
                         for k in per_epoch[0] if k != "tau"}
            totals = {k: np.asarray([v]) for k, v in totals.items()}
        return [{"per_epoch": {k: np.asarray(v[r])
                               for k, v in per_epoch.items()},
                 "totals": {k: np.asarray(v[r]) for k, v in totals.items()}}
                for r in range(self.inputs.rows)]
