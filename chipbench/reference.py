"""Plain reference of the fleet model, written from the paper's equations.

It imports nothing of the program. Arrays are plain dicts of the
``EnvParams`` field names, and every function takes a dtype: float64 is the
reference, bfloat16 its control (the nearest precision below the float32
that the configurations state).

- ``scenario`` applies the grid transforms a sweep point names
  (``wan_degradation``, ``origin_shift``, ``sla_tighten``).
- ``fd_days`` plays one day of the force-directed solver (paper technique (a))
  on the routed ``cost_sla`` game and scores each hour's plan with the
  detailed epoch model (eqs. 1-18 with the SLA and routing extensions): it
  is the reference for every fd cell.
  With a fault trace, each hour's plan runs on the realized fleet through
  the ``spill_nearest`` failover.
- ``demand_cost`` is the hourly network bill of placing all demand, which
  does not depend on the plan: eq. (1) says every task is placed, and the
  bill is ``nprice * sizes * AR`` summed over DCs.

Run under ``jax.enable_x64(True)`` on the host CPU device for float64.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

W_PER_KW = 1000.0
MS_PER_H = 3.6e6
CRAC_W = 4 * 120_000.0          # four CRAC units of 120 kW per DC
RHO_MAX = 0.995
SLA_SOFTNESS = 0.1
EPS = 1e-9
WATERFILL_ROUNDS = 4
FD_ITERS = 120                  # force-directed moves per hour
FD_QUANTUM = 0.06               # share of a row's load moved per step

FIELDS = ("er", "it_idle", "it_dyn", "tsupply", "eff", "rp", "carbon",
          "eprice", "peak_price", "alpha", "nprice", "sizes", "nn_total",
          "car", "avail", "rtt", "sla_ms", "sla_price", "sla_weight",
          "origin")


# ---------------------------------------------------------------------------
# scenario transforms (numpy, float64)
# ---------------------------------------------------------------------------

SEVERITY = {"wan_degradation": "factor", "origin_shift": "weight",
            "sla_tighten": "tighten"}


def grid_points(grid: Mapping[str, Sequence]) -> List[Dict[str, dict]]:
    """The cartesian grid, first axis slowest; a bare number is the
    transform's severity parameter."""
    axes = [[(name, dict(p) if isinstance(p, Mapping)
              else {SEVERITY[name]: p}) for p in pts]
            for name, pts in grid.items()]
    return [dict(combo) for combo in itertools.product(*axes)]


def scenario(env: Dict[str, np.ndarray], point: Mapping[str, dict],
             wan_rtt: np.ndarray = None):
    """``env`` with each named transform applied in order. A WAN event on a
    fleet with no WAN delay starts from ``wan_rtt``, the regions' RTTs."""
    env = dict(env)
    for name, p in point.items():
        if name == "wan_degradation":
            d = env["rtt"].shape[0]
            rtt = env["rtt"]
            if not np.any(rtt):
                rtt = np.asarray(wan_rtt, np.float64)
            env["rtt"] = (rtt * p.get("factor", 3.0)
                          + p.get("extra_ms", 20.0) * (1.0 - np.eye(d)))
        elif name == "origin_shift":
            s, i_n, hours = env["origin"].shape
            target = np.zeros(s)
            toward = list(p.get("toward", (0,)))
            target[toward] = 1.0 / len(toward)
            w = p.get("weight", 0.8)
            env["origin"] = (1.0 - w) * env["origin"] + w * target[:, None, None]
        elif name == "sla_tighten":
            env["sla_ms"] = env["sla_ms"] * p.get("tighten", 1.0)
            env["sla_price"] = np.full_like(env["sla_price"],
                                            p.get("price", 1e-4))
        else:
            raise ValueError(f"the reference has no transform {name!r}")
    return env


# ---------------------------------------------------------------------------
# the fleet model (jax.numpy, any float dtype)
# ---------------------------------------------------------------------------

def as_arrays(env: Mapping[str, np.ndarray], dtype) -> Dict[str, jnp.ndarray]:
    return {k: jnp.asarray(np.asarray(env[k], np.float64), dtype)
            for k in FIELDS}


def _cap(e, tau):
    return e["er"] * e["avail"][:, tau][None, :]


def _cop(e):
    t = e["tsupply"]
    return 0.0068 * t * t + 0.0008 * t + 0.458


def _power(e, ar, tau):
    """Net DC power (D,), eq. (4): IT + cooling, times PSU overhead, less
    on-site renewables."""
    rho = jnp.sum(ar / jnp.maximum(_cap(e, tau), EPS), axis=0)
    a = e["avail"][:, tau]
    it = (e["it_idle"] + e["it_dyn"] * jnp.clip(rho, 0.0, 1.0)) * a
    crac = jnp.minimum(it / _cop(e), CRAC_W * a)
    return (it + crac) * e["eff"] - e["rp"][:, tau]


def _latency3(e, ar, tau):
    """(S, I, D) response time: source RTT + M/M/c-style sojourn."""
    rho = jnp.sum(ar / jnp.maximum(_cap(e, tau), EPS), axis=0)
    rho = jnp.where(e["avail"][:, tau] > 0.0, rho, 1.0)
    service = MS_PER_H * e["nn_total"][None, :] / jnp.maximum(e["er"], EPS)
    sojourn = service / (1.0 - jnp.clip(rho, 0.0, RHO_MAX))[None, :]
    return e["rtt"][:, None, :] + sojourn[None]


def _sla3(e, ar3, tau):
    """(S, I, D) expected SLA-miss cost, $/h."""
    lat = _latency3(e, jnp.sum(ar3, axis=0), tau)
    sla = e["sla_ms"][None, :, None]
    p = jax.nn.sigmoid((lat - sla) / (SLA_SOFTNESS * jnp.maximum(sla, EPS)))
    return e["sla_price"][None, :, None] * ar3 * p


def place(e, fr3, tau):
    """Routing fractions (S, I, D) -> feasible routed rates (S, I, D).

    The demand-weighted (I, D) fractions are water-filled into capacity
    (eqs. 1-2); each feasible cell splits over sources by requested mass."""
    origin = e["origin"][:, :, tau]
    cap = _cap(e, tau)
    ar = jnp.sum(origin[:, :, None] * fr3, axis=0) * e["car"][:, tau][:, None]
    for _ in range(WATERFILL_ROUNDS):
        over = jnp.maximum(ar - cap, 0.0)
        ar = ar - over
        head = jnp.maximum(cap - ar, 0.0)
        w = head / jnp.maximum(jnp.sum(head, axis=1, keepdims=True), EPS)
        ar = ar + jnp.sum(over, axis=1, keepdims=True) * w
    ar = jnp.minimum(ar, cap)
    req3 = (e["car"][:, tau][None, :] * origin)[:, :, None] * fr3
    req = jnp.sum(req3, axis=0)
    ratio = jnp.where(req[None] > EPS, req3 / jnp.maximum(req[None], EPS),
                      origin[:, :, None])
    return ar[None] * ratio


def objective(e, fr3, tau, peak):
    """The routed ``cost_sla`` game value: each player's load-share of
    energy and peak cost, its network bill and its SLA-miss cost."""
    ar3 = place(e, fr3, tau)
    ar = jnp.sum(ar3, axis=0)
    frac = ar / jnp.maximum(_cap(e, tau), EPS)
    share = frac / jnp.maximum(jnp.sum(frac, axis=0), EPS)[None, :]
    dp = _power(e, ar, tau)
    dpe = dp[None, :] * share
    a = jnp.where(dpe > 0, 1.0, e["alpha"][None, :])
    energy = e["eprice"][:, tau][None, :] * a * dpe / W_PER_KW
    new_peak = jnp.maximum(peak, jnp.maximum(dp, 0.0))
    delta = e["peak_price"] * (new_peak - peak) / W_PER_KW
    net = e["nprice"] * e["sizes"][:, None] * ar
    cct = jnp.sum(energy + delta[None, :] * share + net)
    return cct + e["sla_weight"] * jnp.sum(_sla3(e, ar3, tau))


def simulate(e, ar3, tau, peak):
    """Detailed epoch model: (new peak, metrics) of one hour's plan."""
    ar = jnp.sum(ar3, axis=0)
    dp = _power(e, ar, tau)
    a = jnp.where(dp > 0, 1.0, e["alpha"])
    energy = e["eprice"][:, tau] * a * dp / W_PER_KW
    new_peak = jnp.maximum(peak, jnp.maximum(dp, 0.0))
    delta = e["peak_price"] * (new_peak - peak) / W_PER_KW
    net = jnp.sum(e["nprice"] * e["sizes"][:, None] * ar, axis=0)
    sla = jnp.sum(_sla3(e, ar3, tau), axis=(0, 1))
    cap = _cap(e, tau)
    viol = (jnp.sum(jnp.abs(jnp.sum(ar, axis=1) - e["car"][:, tau]))
            + jnp.sum(jnp.maximum(ar - cap, 0.0)))
    return new_peak, {
        "carbon_kg": jnp.sum(e["carbon"][:, tau] * dp / W_PER_KW),
        "cost_usd": jnp.sum(energy + delta + net + sla),
        "energy_cost_usd": jnp.sum(energy),
        "peak_cost_usd": jnp.sum(delta),
        "network_cost_usd": jnp.sum(net),
        "sla_miss_cost_usd": jnp.sum(sla),
        "grid_power_w": jnp.sum(jnp.maximum(dp, 0.0)),
        "violation": viol,
    }


SPILL_RTT_MS = 25.0
FAILOVER_ROUNDS = 4
TRACE_FIELDS = ("avail_mult", "rtt_extra_ms", "price_mult", "carbon_mult")


def realized(e, tr, tau):
    """The hour's realized fleet: capacity, prices, carbon and RTT moved
    by the fault trace."""
    return {**e, "avail": e["avail"] * tr["avail_mult"],
            "eprice": e["eprice"] * tr["price_mult"],
            "carbon": e["carbon"] * tr["carbon_mult"],
            "rtt": e["rtt"] + tr["rtt_extra_ms"][:, :, tau]}


def failover(r, ar3, tau):
    """``spill_nearest``: mass above realized capacity spills to DCs with
    headroom, weighted by headroom and by nearness 1 / (1 + rtt / 25 ms),
    for four rounds; what finds no room is unserved."""
    tot = jnp.sum(ar3, axis=0)
    cap = _cap(r, tau)
    kern = 1.0 / (1.0 + r["rtt"] / SPILL_RTT_MS)
    kept0 = jnp.minimum(tot, cap)
    kept, over = kept0, tot - kept0
    for _ in range(FAILOVER_ROUNDS):
        head = jnp.maximum(cap - kept, 0.0)
        w = head[:, None, :] * kern[None, :, :]
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), EPS)
        inc = jnp.sum(over[:, :, None] * w, axis=1)
        acc = jnp.minimum(inc, head)
        kept, over = kept + acc, inc - acc
    unserved = jnp.maximum(jnp.sum(tot) - jnp.sum(kept), 0.0)
    moved = jnp.maximum(jnp.sum(kept - kept0), 0.0)
    share = jnp.where(tot[None] > EPS, ar3 / jnp.maximum(tot[None], EPS),
                      r["origin"][:, :, tau][:, :, None])
    return kept[None] * share, unserved, moved


def _fd(e, tau, peak, iters, quantum):
    """Force-directed greedy: move a quantum of each (source, task) row's
    load from its highest- to its lowest-marginal-cost DC; keep the best."""
    s, i_n = e["origin"].shape[:2]
    d = e["er"].shape[1]
    f0 = jnp.full((s, i_n, d), 1.0 / d, e["er"].dtype)

    def obj(f):
        return objective(e, f, tau, peak)

    def it(carry, _):
        f, best_f, best_v = carry
        force = jax.grad(obj)(f)
        src = jnp.argmax(jnp.where(f > 1e-6, force, -jnp.inf), axis=-1)
        dst = jnp.argmin(force, axis=-1)
        move = quantum * jnp.take_along_axis(f, src[..., None], axis=-1)[..., 0]
        f = (f - move[..., None] * jax.nn.one_hot(src, d, dtype=f.dtype)
             + move[..., None] * jax.nn.one_hot(dst, d, dtype=f.dtype))
        f = jnp.clip(f, 0.0, None)
        f = f / jnp.sum(f, axis=-1, keepdims=True)
        v = obj(f)
        better = v < best_v
        return (f, jnp.where(better, f, best_f),
                jnp.where(better, v, best_v)), None

    (_, best_f, _), _ = jax.lax.scan(it, (f0, f0, obj(f0)), None,
                                     length=iters)
    return best_f


def _fd_day(e, tr, hours, iters, quantum):
    d = e["er"].shape[1]

    def hour(peak, tau):
        ar3 = place(e, _fd(e, tau, peak, iters, quantum), tau)
        if tr is None:
            return simulate(e, ar3, tau, peak)
        r = realized(e, tr, tau)
        ar_r, unserved, moved = failover(r, ar3, tau)
        peak, m = simulate(r, ar_r, tau, peak)
        return peak, {**m, "unserved_demand": unserved,
                      "failover_moved": moved}

    _, ms = jax.lax.scan(hour, jnp.zeros((d,), e["er"].dtype),
                         jnp.arange(hours))
    return ms


_fd_days_jit = jax.jit(jax.vmap(_fd_day, in_axes=(0, 0, None, None, None)),
                       static_argnums=(2, 3, 4))


def fd_days(envs: Sequence[Mapping[str, np.ndarray]], hours: int, dtype,
            iters: int = FD_ITERS, quantum: float = FD_QUANTUM,
            traces: Sequence[Mapping[str, np.ndarray]] = None
            ) -> List[Dict[str, np.ndarray]]:
    """Per-hour metrics (hours,) of one fd day per env, computed in
    ``dtype`` in one batch; with fault traces (one per env), each hour's
    plan is executed on the realized fleet."""
    rows = [as_arrays(env, dtype) for env in envs]
    e = {k: jnp.stack([r[k] for r in rows]) for k in FIELDS}
    tr = (None if traces is None else
          {k: jnp.asarray(np.stack([np.asarray(t[k], np.float64)
                                    for t in traces]), dtype)
           for k in TRACE_FIELDS})
    ms = {k: np.asarray(v, np.float64)
          for k, v in _fd_days_jit(e, tr, hours, iters, quantum).items()}
    return [{k: v[n] for k, v in ms.items()} for n in range(len(envs))]


def fd_day(env: Mapping[str, np.ndarray], hours: int, dtype,
           iters: int = FD_ITERS, quantum: float = FD_QUANTUM,
           trace: Mapping[str, np.ndarray] = None) -> Dict[str, np.ndarray]:
    """``fd_days`` of one env."""
    return fd_days([env], hours, dtype, iters, quantum,
                   None if trace is None else [trace])[0]


def demand_cost(env: Mapping[str, np.ndarray], hours: int,
                dtype=np.float64) -> np.ndarray:
    """(hours,) network bill of placing every hour's whole demand."""
    car = np.asarray(env["car"], np.float64)[:, :hours].astype(dtype)
    sizes = np.asarray(env["sizes"], np.float64).astype(dtype)
    nprice = np.asarray(env["nprice"], np.float64).astype(dtype)
    return np.asarray(nprice * np.sum(sizes[:, None] * car, axis=0),
                      np.float64)
