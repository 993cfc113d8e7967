"""``FaultTrace``: seedable, composable *realized* event streams.

A trace is what actually happened to the fleet during the day, as opposed
to what the planner was told (``EnvParams``). The scenario transforms in
``repro.scenarios`` bake events into the env the solvers *plan on* —
``scenarios.dc_outage`` is an outage the scheduler saw coming and routed
around from hour 0. A ``FaultTrace`` is the complement: the solvers keep
planning on the unfaulted env, and the execution layer
(``repro.faults.failover``) applies the trace to a realized env view each
hour *inside* the jitted scan, re-projecting the planner's allocation
against realized capacity. That plan/execute split is what DCcluster-Opt
(PAPERS.md) argues robustness benchmarks need: disruptions that arrive
during execution, not in the briefing.

The trace is a pytree of hourly multipliers/addends over the planner's
fields, so it jits, vmaps (one trace shared across a batched env fleet) and
composes (multipliers multiply, RTT penalties add):

======================  =========  =======================================
field                   shape      meaning (realized = planned ∘ trace)
======================  =========  =======================================
``avail_mult``          (D, 24)    realized avail = avail · avail_mult
``rtt_extra_ms``        (D, D, 24) realized rtt = rtt + rtt_extra_ms[..., t]
``price_mult``          (D, 24)    realized $/kWh = eprice · price_mult
``carbon_mult``         (D, 24)    realized kg/kWh = carbon · carbon_mult
======================  =========  =======================================

Event constructors: ``dc_crash`` (hard capacity zero), ``brownout``
(partial capacity loss), ``wan_partition`` (an inter-region link degrades),
``telemetry_dropout`` (the planner's price/carbon feed went stale — the
realized signal differs by a factor). ``random_trace`` samples a seeded
mix. ``no_faults`` is the identity trace: engines fed it produce the
unfaulted numbers (bit-for-bit on the unrouted path).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..dcsim.env import stack_trees

HOURS = 24


class FaultTrace(NamedTuple):
    """Realized per-hour fault multipliers the executor applies to the
    planning env (shapes pinned in ``repro.lint.pytrees.SCHEMAS``).

    Machine-read unit table (repro.lint.units):

        avail_mult: 1
        rtt_extra_ms: ms
        price_mult: 1
        carbon_mult: 1
    """
    avail_mult: jnp.ndarray    # (D, 24) in [0, 1]
    rtt_extra_ms: jnp.ndarray  # (D, D, 24) >= 0
    price_mult: jnp.ndarray    # (D, 24) > 0
    carbon_mult: jnp.ndarray   # (D, 24) > 0


def _ndc(env_or_d) -> int:
    """Number of DCs from an EnvParams or a bare int."""
    if isinstance(env_or_d, (int, np.integer)):
        return int(env_or_d)
    return int(env_or_d.er.shape[-1])


def _window(start: int, duration: int) -> np.ndarray:
    """(24,) float mask for [start, start+duration) mod 24 (the scenario
    transforms' convention)."""
    h = np.arange(HOURS)
    return (((h - start) % HOURS) < duration).astype(np.float64)


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(np.asarray(x, dtype=np.float32))


def no_faults(env_or_d) -> FaultTrace:
    """The identity trace: nothing happened. Engines under it reproduce
    the unfaulted planner numbers (bit-for-bit on the unrouted path; the
    routed failover re-split is allclose — see ``failover.apply_failover``)."""
    d = _ndc(env_or_d)
    return FaultTrace(
        avail_mult=_f32(np.ones((d, HOURS))),
        rtt_extra_ms=_f32(np.zeros((d, d, HOURS))),
        price_mult=_f32(np.ones((d, HOURS))),
        carbon_mult=_f32(np.ones((d, HOURS))),
    )


def dc_crash(env_or_d, dc: int = 0, start: int = 12,
             duration: int = 6) -> FaultTrace:
    """Hard crash: the DC's realized capacity is zero for the window. The
    planner still schedules onto it; the failover policy decides where that
    mass goes."""
    t = no_faults(env_or_d)
    mult = np.array(t.avail_mult)
    mult[dc] = 1.0 - _window(start, duration)
    return t._replace(avail_mult=_f32(mult))


def brownout(env_or_d, dc: int = 0, start: int = 10, duration: int = 8,
             severity: float = 0.5) -> FaultTrace:
    """Capacity brownout: the DC loses ``severity`` of its realized
    capacity in the window (thermal event, partial grid curtailment)."""
    t = no_faults(env_or_d)
    mult = np.array(t.avail_mult)
    mult[dc] = 1.0 - severity * _window(start, duration)
    return t._replace(avail_mult=_f32(mult))


def wan_partition(env_or_d, a: int = 0, b: int = 1, start: int = 0,
                  duration: int = 24, extra_ms: float = 500.0) -> FaultTrace:
    """Link partition/degradation: the a↔b inter-region path gains
    ``extra_ms`` of realized RTT both directions for the window (a severed
    or congested backbone segment). Affects realized SLA pricing and the
    ``spill_nearest`` failover geometry."""
    t = no_faults(env_or_d)
    extra = np.array(t.rtt_extra_ms)
    w = _window(start, duration) * float(extra_ms)
    extra[a, b] += w
    extra[b, a] += w
    return t._replace(rtt_extra_ms=_f32(extra))


def telemetry_dropout(env_or_d, dc: Optional[int] = None, start: int = 0,
                      duration: int = 24, price_factor: float = 1.0,
                      carbon_factor: float = 1.0) -> FaultTrace:
    """Stale telemetry: the planner's price/carbon feed for ``dc`` (all DCs
    when None) stopped updating, and reality drifted by the given factors —
    realized $/kWh = planned · price_factor, realized intensity = planned ·
    carbon_factor in the window. The plan is costed at what the grid
    actually charged/emitted, not what the stale feed claimed."""
    t = no_faults(env_or_d)
    rows = slice(None) if dc is None else dc
    w = _window(start, duration)
    price = np.array(t.price_mult)
    carbon = np.array(t.carbon_mult)
    price[rows] = 1.0 + (price_factor - 1.0) * w
    carbon[rows] = 1.0 + (carbon_factor - 1.0) * w
    return t._replace(price_mult=_f32(price), carbon_mult=_f32(carbon))


def compose(*traces: FaultTrace) -> FaultTrace:
    """Overlay traces: availability/price/carbon multipliers multiply,
    RTT penalties add. Order-independent."""
    if not traces:
        raise ValueError("compose() needs at least one trace")
    out = traces[0]
    for t in traces[1:]:
        out = FaultTrace(
            avail_mult=out.avail_mult * t.avail_mult,
            rtt_extra_ms=out.rtt_extra_ms + t.rtt_extra_ms,
            price_mult=out.price_mult * t.price_mult,
            carbon_mult=out.carbon_mult * t.carbon_mult,
        )
    return out


def stack_traces(traces: Sequence[FaultTrace]) -> FaultTrace:
    """Stack per-point traces leaf-wise into one batched FaultTrace.

    The leading axis lines up with a batched engine's env rows (one realized
    day of trouble per scenario/grid point); ``run``/``sweep`` detect the
    extra axis and vmap the trace alongside the envs instead of replicating
    one shared trace.
    """
    traces = list(traces)
    if not traces:
        raise ValueError("stack_traces() needs at least one trace")
    shapes = {t.avail_mult.shape for t in traces}
    if len(shapes) != 1:
        raise ValueError(f"traces disagree on (D, hours): {sorted(shapes)}")
    return stack_trees(traces)


_KINDS = ("dc_crash", "brownout", "wan_partition", "telemetry_dropout")


def random_trace(env_or_d, seed: int = 0, n_events: int = 3,
                 kinds: Sequence[str] = _KINDS) -> FaultTrace:
    """A seeded random day of trouble: ``n_events`` events drawn from
    ``kinds`` with randomized targets/windows/severities. Deterministic in
    ``seed`` — the same trace replays across techniques and sweeps."""
    d = _ndc(env_or_d)
    rng = np.random.default_rng(seed)
    parts = [no_faults(d)]
    for _ in range(n_events):
        kind = kinds[int(rng.integers(len(kinds)))]
        start = int(rng.integers(0, HOURS))
        duration = int(rng.integers(2, 13))
        if kind == "dc_crash":
            parts.append(dc_crash(d, dc=int(rng.integers(d)), start=start,
                                  duration=duration))
        elif kind == "brownout":
            parts.append(brownout(d, dc=int(rng.integers(d)), start=start,
                                  duration=duration,
                                  severity=float(rng.uniform(0.2, 0.8))))
        elif kind == "wan_partition":
            a, b = rng.choice(d, size=2, replace=False)
            parts.append(wan_partition(d, a=int(a), b=int(b), start=start,
                                       duration=duration,
                                       extra_ms=float(rng.uniform(100, 800))))
        elif kind == "telemetry_dropout":
            parts.append(telemetry_dropout(
                d, dc=int(rng.integers(d)), start=start, duration=duration,
                price_factor=float(rng.uniform(0.5, 2.5)),
                carbon_factor=float(rng.uniform(0.5, 2.5))))
        else:
            raise ValueError(f"unknown fault kind {kind!r}; known: {_KINDS}")
    return compose(*parts)
