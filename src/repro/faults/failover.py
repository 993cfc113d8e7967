"""Realized-hour execution: the plan/execute split's execute side.

``execute_hour`` is what the faulted engines run instead of a bare
``E.step_epoch``: build the hour's *realized* env view from the trace,
re-project the planner's allocation against realized capacity via a
failover policy, then simulate the epoch on the realized env. Everything
is plain jittable array math — it runs inside the engines' ``lax.scan``.

Failover policies (what operators actually do when a DC goes dark under
load):

- ``renormalize``  — shed the over-capacity mass and redistribute it to
  DCs with headroom in proportion to that headroom (the global load
  balancer rebalances; no locality preference).
- ``spill_nearest`` — redistribute headroom-proportionally *weighted by
  realized network nearness* ``1 / (1 + rtt / SPILL_RTT_SCALE_MS)``: mass
  spills to close healthy DCs first, which is cheaper on the realized SLA
  bill but can saturate neighbors. With an all-zero RTT matrix (the paper
  default) this degenerates to ``renormalize``.
- ``drop``         — no failover: over-capacity mass is simply unserved
  (what happens when the failover automation itself is down).

Degradation metrics appended to the epoch's dict (and summed into the
result totals by the engines):

- ``unserved_demand``       tasks/h the realized fleet could not serve;
- ``failover_moved``        tasks/h served at a DC the planner did not
  pick (mass moved by the policy);
- ``degraded_sla_cost_usd`` realized SLA bill minus what the plan would
  have paid on the unfaulted env (can be negative under ``drop``: dropped
  requests pay no SLA charge — they show up in ``unserved_demand``).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..dcsim import env as E
from .trace import FaultTrace

POLICIES = ("renormalize", "spill_nearest", "drop")
DEFAULT_POLICY = "renormalize"

SPILL_RTT_SCALE_MS = 25.0   # nearness kernel scale for spill_nearest  # lint: unit(ms)
REDISTRIBUTE_ROUNDS = 4     # water-fill rounds (project_feasible's budget)

_EPS = 1e-9


def realized_env(env: E.EnvParams, trace: FaultTrace, tau) -> E.EnvParams:
    """The hour's realized env view: planner fields composed with the trace.

    ``avail``/``eprice``/``carbon`` carry their own hourly axis so the full
    (D, 24) products are formed (only column ``tau`` is consumed
    downstream); ``rtt`` is per-hour, indexed here.
    """
    return env._replace(
        avail=env.avail * trace.avail_mult,
        eprice=env.eprice * trace.price_mult,
        carbon=env.carbon * trace.carbon_mult,
        rtt=env.rtt + trace.rtt_extra_ms[:, :, tau],
    )


def _nearness(renv: E.EnvParams, policy: str) -> jnp.ndarray:
    """(D, D) redistribution kernel K[from, to] for the water-fill."""
    d = E.num_dcs(renv)
    if policy == "spill_nearest":
        return 1.0 / (1.0 + renv.rtt / SPILL_RTT_SCALE_MS)
    return jnp.ones((d, d))


def _redistribute(kept: jnp.ndarray, over: jnp.ndarray, cap: jnp.ndarray,
                  kern: jnp.ndarray) -> jnp.ndarray:
    """Iteratively place homeless mass ``over`` (I, D; tagged by the DC it
    was shed from) into headroom, weighted by headroom × kernel. Mass that
    finds no headroom after ``REDISTRIBUTE_ROUNDS`` stays unserved."""
    def body(carry, _):
        kept, over = carry
        head = jnp.maximum(cap - kept, 0.0)                       # (I, D)
        w = head[:, None, :] * kern[None, :, :]                   # (I, Df, Dt)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), _EPS)
        # full f32: at DEFAULT precision a TPU contracts in one bf16 pass
        inc = jnp.einsum("if,ift->it", over, w,
                         precision=jax.lax.Precision.HIGHEST)     # (I, D)
        acc = jnp.minimum(inc, head)
        return (kept + acc, inc - acc), None

    (kept, _), _ = jax.lax.scan(body, (kept, over), None,
                                length=REDISTRIBUTE_ROUNDS)
    return kept


def apply_failover(renv: E.EnvParams, ar: jnp.ndarray, tau,
                   policy: str = DEFAULT_POLICY
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Re-project a planned allocation against *realized* capacity.

    ``ar`` is the planner's (I, D) allocation or routed (S, I, D) tensor.
    Returns ``(ar_realized, unserved, moved)`` — realized same-shape
    allocation, total unserved tasks/h, total tasks/h moved off-plan.

    Routed tensors fail over on their (I, D) totals (capacity is
    source-blind), then each realized cell splits across sources by the
    planned per-source share; mass moved into cells the plan left empty
    splits by the hour's demand-origin mix (``project_feasible_routed``'s
    convention).
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown failover policy {policy!r}; "
                         f"known: {POLICIES}")
    ar3 = ar if ar.ndim == 3 else None
    tot = jnp.sum(ar3, axis=0) if ar3 is not None else ar      # (I, D)
    cap = E.capacity_at(renv, tau)                             # (I, D)
    kept0 = jnp.minimum(tot, cap)
    if policy == "drop":
        kept = kept0
    else:
        kept = _redistribute(kept0, tot - kept0, cap,
                             _nearness(renv, policy))
    # clamped: at 1e9-scale allocations the float32 reductions can land a
    # few hundred tasks/h on either side of zero
    unserved = jnp.maximum(jnp.sum(tot) - jnp.sum(kept), 0.0)
    moved = jnp.maximum(jnp.sum(kept - kept0), 0.0)
    if ar3 is None:
        return kept, unserved, moved
    origin = E.origin_at(renv, tau)                            # (S, I)
    share = jnp.where(tot[None] > _EPS,
                      ar3 / jnp.maximum(tot[None], _EPS),
                      origin[:, :, None])
    return kept[None] * share, unserved, moved


def execute_hour(env: E.EnvParams, trace: FaultTrace, peak_state, ar, tau,
                 policy: str = DEFAULT_POLICY):
    """One realized epoch: failover the planned ``ar`` against the hour's
    realized env, simulate it there, and append the degradation metrics.

    The planner's own SLA bill (planned ``ar`` on the unfaulted ``env``) is
    recomputed here so ``degraded_sla_cost_usd`` is a pure delta — the cost
    of being surprised, not of the SLA terms existing at all.
    """
    renv = realized_env(env, trace, tau)
    ar_r, unserved, moved = apply_failover(renv, ar, tau, policy)
    peak_state, m = E.step_epoch(renv, peak_state, ar_r, tau)
    if ar.ndim == 3:
        planned_sla = jnp.sum(E.sla_cost_routed(env, ar, tau))
    else:
        planned_sla = jnp.sum(E.sla_cost(env, ar, tau))
    m["unserved_demand"] = unserved
    m["failover_moved"] = moved
    m["degraded_sla_cost_usd"] = m["sla_miss_cost_usd"] - planned_sla
    return peak_state, m
