"""The comparison that decides ``correct``.

Every answer a window produced (one day of one row: its per-epoch metrics
and totals) is held to the plain reference (``chipbench.reference``):

- ``demand_gap``: each hour's network bill against the bill of placing that
  hour's whole demand. It does not depend on the plan, so it holds for every
  technique: the solver placed all demand (eq. 1), the water-fill kept it,
  and the simulator billed it.
- ``sum_gap``: each total against the exact sum of its per-epoch values
  (the host's aggregation).
- ``violation_share``: the day's constraint violation over its demand, and
  ``physical_bounds``: carbon, grid power and energy cost each hour, and the
  day's peak charge, inside what any plan could score (every DC between
  idle and full load). Their limits are the configuration's guarantees.
- ``plan_gap`` (fd only): on a sample of answers drawn from the seed, the
  day's carbon, cost and SLA-miss totals (and, under faults, the tasks
  failover moved or left unserved) against the reference fd day computed
  in float64. gt-drl's plan has no reference (it learns on the chip); its
  answers are held to the four numbers above.

A number's value is its worst over the answers it covers; each has a limit
(``chipbench/limits/<cell>.json``, or the configuration's guarantees).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import numpy as np

from chipbench import reference as R

PLAN_KEYS = ("carbon_kg", "cost_usd", "sla_miss_cost_usd")
RATE_KEYS = ("unserved_demand", "failover_moved")   # tasks/h, over demand
PLAN_SAMPLE = 8          # answers per run re-solved by the fd reference
_SAMPLE_SALT = 0x5EED


def _rel(a: float, b: float, scale: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(scale)), 1e-30)


def bounds(env: Mapping[str, np.ndarray], hours: int) -> Dict[str, np.ndarray]:
    """Per hour (lo, hi) of carbon, grid power and energy cost, and the
    day's (lo, hi) peak charge, over every DC load between idle and full."""
    e = {k: np.asarray(env[k], np.float64) for k in R.FIELDS}
    a_t = e["avail"][:, :hours]
    cop = R._cop(e)[:, None]

    def power(rho):
        it = (e["it_idle"] + e["it_dyn"] * rho)[:, None] * a_t
        crac = np.minimum(it / cop, R.CRAC_W * a_t)
        return (it + crac) * e["eff"][:, None] - e["rp"][:, :hours]

    out = {}
    for tag, dp in (("lo", power(0.0)), ("hi", power(1.0))):
        a = np.where(dp > 0, 1.0, e["alpha"][:, None])
        out[f"carbon_kg.{tag}"] = np.sum(
            e["carbon"][:, :hours] * dp, axis=0) / R.W_PER_KW
        out[f"grid_power_w.{tag}"] = np.sum(np.maximum(dp, 0.0), axis=0)
        out[f"energy_cost_usd.{tag}"] = np.sum(
            e["eprice"][:, :hours] * a * dp, axis=0) / R.W_PER_KW
        out[f"peak_cost_usd.{tag}"] = np.sum(
            e["peak_price"] * np.max(np.maximum(dp, 0.0), axis=1)) / R.W_PER_KW
    return out


def _outside(x, lo, hi) -> float:
    x, lo, hi = (np.asarray(v, np.float64) for v in (x, lo, hi))
    over = np.maximum(np.maximum(lo - x, x - hi), 0.0)
    return float(np.max(over / np.maximum(np.maximum(np.abs(lo), np.abs(hi)),
                                          1e-30)))


def realized_day(env: Mapping[str, np.ndarray], trace) -> Dict[str, Any]:
    """The fleet as a fault trace left it, hour by hour (capacity, prices
    and carbon; the bounds need nothing else)."""
    if trace is None:
        return env
    return {**env, "avail": env["avail"] * trace["avail_mult"],
            "eprice": env["eprice"] * trace["price_mult"],
            "carbon": env["carbon"] * trace["carbon_mult"]}


def answer_numbers(answer: Mapping[str, Any], env: Mapping[str, np.ndarray],
                   hours: int, trace=None) -> Dict[str, float]:
    """The plan-free numbers of one answer. Under faults, an hour that left
    demand unserved is out of ``demand_gap``, and ``violation_share``
    counts what the answer did not report as unserved."""
    per, tot = answer["per_epoch"], answer["totals"]
    demand = R.demand_cost(env, hours)
    net = np.asarray(per["network_cost_usd"], np.float64)
    served = np.asarray(per.get("unserved_demand", np.zeros(hours))) <= 0.0
    gap = np.abs(net - demand) / demand
    out = {"demand_gap": float(np.max(np.where(served, gap, 0.0)))}
    gaps = []
    for k, t in tot.items():
        xs = np.asarray(per[k], np.float64)
        exact = math.fsum(xs.tolist())
        gaps.append(_rel(t, exact, max(math.fsum(np.abs(xs).tolist()),
                                       1e-30)))
    out["sum_gap"] = max(gaps)
    car = np.asarray(env["car"], np.float64)[:, :hours]
    unserved = float(tot.get("unserved_demand", 0.0))
    out["violation_share"] = (max(float(tot["violation"]) - unserved, 0.0)
                              / float(car.sum()))
    b = bounds(realized_day(env, trace), hours)
    day_peak = math.fsum(np.asarray(per["peak_cost_usd"], np.float64).tolist())
    out["physical_bounds"] = max(
        max(_outside(per[k], b[f"{k}.lo"], b[f"{k}.hi"])
            for k in ("carbon_kg", "grid_power_w", "energy_cost_usd")),
        _outside(day_peak, b["peak_cost_usd.lo"], b["peak_cost_usd.hi"]))
    return out


def plan_numbers(answer: Mapping[str, Any], ref: Mapping[str, np.ndarray],
                 demand: float) -> float:
    """Worst gap of the plan-dependent day totals to the reference day's:
    relative for money and carbon, over the day's demand for the tasks/h
    that failover moved or left unserved."""
    gaps = [_rel(answer["totals"][k], np.sum(ref[k]),
                 max(abs(float(np.sum(ref[k]))), 1.0)) for k in PLAN_KEYS]
    gaps += [_rel(answer["totals"][k], np.sum(ref[k]), demand)
             for k in RATE_KEYS if k in ref]
    return max(gaps)


def fd_reference(envs, hours: int, dtype, traces=None):
    """Reference fd days on the host CPU (float64 needs x64 on)."""
    import jax

    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        return R.fd_days(envs, hours, dtype, traces=traces)


def day_demand(env, hours: int) -> float:
    return float(np.asarray(env["car"], np.float64)[:, :hours].sum())


def sample(n_answers: int, seed: int, k: int = PLAN_SAMPLE) -> List[int]:
    """Indices of the answers the plan reference re-solves: drawn from the
    seed, always including the last one the window finished."""
    rng = np.random.default_rng([seed % (2 ** 63), _SAMPLE_SALT])
    pick = rng.choice(n_answers, size=min(k, n_answers), replace=False)
    return sorted(set(int(i) for i in pick[:-1]) | {n_answers - 1})


def check(inputs, answers: Sequence[Tuple[int, int, Mapping[str, Any]]],
          limits: Mapping[str, float], seed: int
          ) -> Tuple[List[Tuple[str, float, float]], int]:
    """Hold every answer ``(call, row, answer)`` to the reference.

    Returns ``[(name, worst value, limit)]`` and the number of answers that
    broke a limit."""
    guarantees = inputs.config["guarantees"]
    lim = {"demand_gap": limits["demand_gap"], "sum_gap": limits["sum_gap"],
           "violation_share": guarantees["violation_share"],
           "physical_bounds": guarantees["physical_bounds"]}
    worst = {k: 0.0 for k in lim}
    bad = set()
    for idx, (call, row, ans) in enumerate(answers):
        nums = answer_numbers(ans, inputs.env_of(call, row), inputs.hours,
                              inputs.trace_of(call, row))
        for k, v in nums.items():
            worst[k] = max(worst[k], v) if np.isfinite(v) else math.inf
            if not v <= lim[k]:
                bad.add(idx)
    if inputs.technique == "fd" and answers:
        if inputs.traces is not None and inputs.failover != "spill_nearest":
            raise ValueError("the reference fails over by spill_nearest "
                             f"only, not {inputs.failover!r}")
        lim["plan_gap"] = limits["plan_gap"]
        worst["plan_gap"] = 0.0
        picked = sample(len(answers), seed)
        envs = [inputs.env_of(answers[i][0], answers[i][1]) for i in picked]
        traces = (None if inputs.traces is None else
                  [inputs.trace_of(answers[i][0], answers[i][1])
                   for i in picked])
        refs = fd_reference(envs, inputs.hours, np.float64, traces)
        for idx, env, ref in zip(picked, envs, refs):
            v = plan_numbers(answers[idx][2], ref,
                             day_demand(env, inputs.hours))
            worst["plan_gap"] = (max(worst["plan_gap"], v)
                                 if np.isfinite(v) else math.inf)
            if not v <= lim["plan_gap"]:
                bad.add(idx)
    return [(k, worst[k], lim[k]) for k in lim], len(bad)
