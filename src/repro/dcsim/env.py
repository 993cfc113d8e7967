"""The geo-distributed cloud environment: paper eqs. (1)–(18) in JAX.

Everything is a pure function of an ``EnvParams`` NamedTuple of jnp arrays,
so objectives are jittable, vmappable (batched game episodes, and the
scenario engine's ``run_days_batched`` fleet evaluation) and differentiable
(the NASH best-reply baseline exploits the gradients).

Shapes: I task types × D data centers × 24 UTC hours.

Units (every cost metric is $ per one-hour epoch):

====================  =========  =================================================
field / quantity      shape      unit
====================  =========  =================================================
``er``, ``car``       (I, D)/…   tasks/h
``it_idle``/``dyn``   (D,)       W
``rp``                (D, 24)    W
``eprice``            (D, 24)    $/kWh (applied to W/1000 → $/h)
``peak_price``        (D,)       $/kW-month (applied to peak W/1000)
``nprice``            scalar     $/GB (× ``sizes`` GB/task × AR tasks/h → $/h)
``carbon``            (D, 24)    kg CO₂ / kWh (→ kg/h)
``rtt``               (D, D)     ms round-trip between regions (row = source);
                                 canonical — the old (D,) mean-RTT vector form
                                 is gone (routing needs per-path values)
``sla_ms``            (I,)       ms response-time target per task type
``sla_price``         (I,)       $/task charged per expected SLA miss
``sla_weight``        scalar     weight of the SLA term in ``cost_sla`` rewards
``origin``            (S, I, 24) demand-origin split: fraction of task i's
                                 hour-t arrivals sourced from region s (sums
                                 to 1 over s); S = D (sources = DC regions,
                                 the default) or S = 1 (aggregate source)
latency               (I, D)     ms = access RTT + M/M/c-style queued service
routed latency        (S, I, D)  ms = rtt[s, d] + the same queued sojourn
SLA miss cost         (I, D)     $/h = sla_price · AR · p_miss(latency, sla_ms)
routed SLA miss cost  (S, I, D)  $/h priced per (source, task) path
====================  =========  =================================================

Token units (``workload="llm"``, the capability layer in
``dcsim.capability``): task types are model families and every field above
keeps its unit — only the derivation changes. One "task" is one request of
``prompt_mean + output_mean`` tokens, so ``er`` is requests/h derived from
roofline tokens/sec/chip summed over the DC's accelerator mix, service time
``3.6e6 / er`` ms is the request's prefill + decode walltime, ``it_dyn`` is
the accelerator fleet's peak draw with J/token × tokens/s/chip ==
dynamic W/chip by construction, and ``sizes`` is the request's token payload
in GB. The solvers cannot tell the difference — ``EnvParams`` is the whole
interface.

Beyond-paper extensions for the scenario engine (``repro.scenarios``):
``carbon`` carries an hourly axis (D, 24) so grid carbon-intensity events
(spikes, diurnal marginal-carbon shapes) are expressible, and ``avail``
(D, 24) masks per-DC capacity over the day (outages, demand-response
curtailment). The SLA/latency subsystem (``dcsim.latency``) adds ``rtt``,
``sla_ms``, ``sla_price`` and ``sla_weight``; with the defaults
(``rtt = 0``, ``sla_price = 0``) every SLA term is exactly zero. With
``avail == 1``, a constant carbon profile and the default SLA fields the
model reduces exactly to the paper's.

Per-source request routing (beyond-paper): ``origin`` (S, I, 24) records
*where* each task type's demand comes from, and the routed action space is
an (S, I, D) tensor — which region's requests go to which DC. The routed
functions (``project_feasible_routed``, ``latency_ms_routed``,
``sla_cost_routed``) price response time per (source, task) path instead of
against the fleet-mean access RTT; ``step_epoch``/``player_reward`` accept
either an (I, D) or an (S, I, D) assignment. The degenerate S = 1 aggregate
source reproduces the unrouted model bit-for-bit (its single source row is
the uniform-origin mean RTT), and is the parity reference for the engines.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import capability, latency, renewables, topology
from . import workload as _workload
from .topology import CRAC_MAX_W, CRAC_PER_DC, NETWORK_PRICE
from ..units import W_PER_KW


class EnvParams(NamedTuple):
    """Everything the simulator knows about the fleet, one hour-indexed
    pytree. Shapes are pinned in ``repro.lint.pytrees.SCHEMAS``; the field
    units below are the single source of truth for the dimensional
    analysis — ``repro.lint.units`` parses this table and cross-checks it
    against the field declarations, so doc drift is a lint failure.

    Machine-read unit table (repro.lint.units):

        er: task/h
        it_idle: W
        it_dyn: W
        tsupply: degC
        eff: 1
        rp: W
        carbon: kgCO2/kWh
        eprice: USD/kWh
        peak_price: USD/kW
        alpha: 1
        nprice: USD/GB
        sizes: GB/task
        nn_total: node
        car: task/h
        avail: 1
        rtt: ms
        sla_ms: ms
        sla_price: USD/task
        sla_weight: 1
        origin: 1

    (``peak_price`` is $/kW of monthly peak; the monthly billing period is
    deliberately outside the dimension system — the peak delta is a one-off
    $ charge within the hour it occurs.)
    """
    er: jnp.ndarray          # (I, D) max execution rate, tasks/h (eq. 3)
    it_idle: jnp.ndarray     # (D,) W
    it_dyn: jnp.ndarray      # (D,) W at full utilization
    tsupply: jnp.ndarray     # (D,) CRAC supply temperature °C
    eff: jnp.ndarray         # (D,) PSU overhead ≥ 1
    rp: jnp.ndarray          # (D, 24) renewable W
    carbon: jnp.ndarray      # (D, 24) kg CO2 / kWh (hourly grid intensity)
    eprice: jnp.ndarray      # (D, 24) $/kWh TOU
    peak_price: jnp.ndarray  # (D,) $/kW-month
    alpha: jnp.ndarray       # (D,) net metering fraction
    nprice: jnp.ndarray      # scalar $/GB
    sizes: jnp.ndarray       # (I,) GB per task
    nn_total: jnp.ndarray    # (D,) node count
    car: jnp.ndarray         # (I, 24) cloud arrival rates
    avail: jnp.ndarray       # (D, 24) capacity availability in [0, 1]
    rtt: jnp.ndarray         # (D, D) inter-region RTT ms (canonical; row = source)
    sla_ms: jnp.ndarray      # (I,) response-time SLA target, ms
    sla_price: jnp.ndarray   # (I,) $/task per expected SLA miss (0 = unpriced)
    sla_weight: jnp.ndarray  # scalar weight of the SLA term under "cost_sla"
    origin: jnp.ndarray      # (S, I, 24) demand-origin split, sums to 1 over s


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def build_env(
    num_dcs: int = 4,
    *,
    month: int = 6,
    pattern: str = "sinusoidal",
    seed: int = 0,
    utilization: float = 0.45,
    include_tpu: bool = False,
    renewable_scale: float = 0.8,
    workload: "str | capability.WorkloadModel" = "aibench",
) -> EnvParams:
    """Build one day's EnvParams for ``num_dcs`` data centers.

    ``workload`` selects the capability layer (``dcsim.capability``)
    that derives the per-(task, DC) serving numbers — ``er``, IT power,
    payload ``sizes``, default ``sla_ms`` and the task-type count ``I``:

    - ``"aibench"`` (default): the paper's ten AIBench task types on the
      Xeon fleet; bit-for-bit identical to the pre-capability-layer env.
    - ``"llm"``: model-zoo families on the accelerator fleet, derived from
      the roofline (tokens/sec/chip, J/token — see ``capability.py``).
    - any registered name or a ``WorkloadModel`` instance.
    """
    locs = topology.dc_locations(num_dcs)
    loc_rows = [topology.LOCATIONS[i] for i in locs]
    wl = capability.resolve(workload, include_tpu=include_tpu)
    cap = wl.capabilities(num_dcs, seed)
    er, it_idle, it_dyn = cap.er, cap.it_idle, cap.it_dyn
    num_tasks = er.shape[0]
    rng = np.random.default_rng(seed + 17)
    tsupply = rng.uniform(16.0, 24.0, num_dcs)
    eff = rng.uniform(1.10, 1.25, num_dcs)

    tz = np.array([r[2] for r in loc_rows])
    carbon = np.array([r[3] for r in loc_rows])
    base_price = np.array([r[4] for r in loc_rows])
    peak_price = np.array([r[5] for r in loc_rows])
    alpha = np.array([r[6] for r in loc_rows])
    solar_cap = np.array([r[7] for r in loc_rows])
    wind_cap = np.array([r[8] for r in loc_rows])

    # TOU profile: peak window 2–8 PM local at 1.7×, shoulder 1.2×, off 0.8×
    hours = np.arange(24)
    eprice = np.zeros((num_dcs, 24))
    for d in range(num_dcs):
        local = (hours + tz[d]) % 24
        mult = np.where((local >= 14) & (local < 20), 1.7,
                        np.where((local >= 8) & (local < 14), 1.2, 0.8))
        eprice[d] = base_price[d] * mult

    installed = renewable_scale * (it_idle + 0.5 * it_dyn)
    rp = renewables.renewable_profile(tz, solar_cap, wind_cap, 1.0, month, seed)
    rp = rp * installed[:, None]

    # peak rate per type via workload.base_rates (one source of truth for the
    # Dirichlet task mix): w_i (Σw=1) of its own capacity × utilization, so
    # total utilization Σ_i CAR_i/cap_i peaks near ``utilization``.
    base = _workload.base_rates(np.asarray(er).sum(axis=1), utilization)
    car = _workload.arrival_pattern(pattern, base, seed=seed)

    f = jnp.asarray
    return EnvParams(
        er=f(er), it_idle=f(it_idle), it_dyn=f(it_dyn), tsupply=f(tsupply),
        eff=f(eff), rp=f(rp), carbon=f(np.tile(carbon[:, None], (1, 24))),
        eprice=f(eprice), peak_price=f(peak_price), alpha=f(alpha),
        nprice=jnp.float32(NETWORK_PRICE), sizes=f(cap.sizes),
        nn_total=f(cap.nn_total), car=f(car),
        avail=jnp.ones((num_dcs, 24)),
        # SLA/latency defaults: the paper's model (no WAN delay, misses
        # unpriced). sla_ms is a finite slack target so sla_tighten scales it.
        rtt=jnp.zeros((num_dcs, num_dcs)),
        sla_ms=f(cap.sla_ms),
        sla_price=jnp.zeros(num_tasks),
        sla_weight=jnp.float32(1.0),
        # demand origins: uniform across the DC regions (S = D). Routing only
        # matters once rtt is non-zero and origins are shifted; the default
        # reduces the routed model to the paper's exactly.
        origin=jnp.full((num_dcs, num_tasks, 24), 1.0 / num_dcs, dtype=jnp.float32),
    )


@jax.jit
def stack_trees(trees):
    """Stack a list of same-structure pytrees leaf-wise in one compiled call.

    One program per row count and leaf shapes; the values are those of an
    eager ``jnp.stack`` per leaf, which costs a dispatch per row and leaf.
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


@jax.jit
def first_row(tree):
    """Row 0 of every leaf of a stacked pytree, in one compiled call."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def stack_envs(envs) -> EnvParams:
    """Stack same-shape envs leaf-wise into one batched EnvParams.

    The leading axis is a scenario-day (or calendar-day) batch: vmap over it
    for fleet evaluation (``schedulers.run_days_batched``) or scan over it
    for month-scale episodes (``schedulers.run_month``). Counts the rows as
    ``stacked_rows`` on the current ``obs`` request.
    """
    envs = list(envs)
    obs.count("stacked_rows", len(envs))
    return stack_trees(envs)


def tile_env(env: EnvParams, n: int) -> EnvParams:
    """Broadcast one env to a leading axis of ``n`` identical days."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), env)


def pad_env_batch(env_b: EnvParams, n: int) -> EnvParams:
    """Pad a stacked EnvParams' leading axis to ``n`` rows by repeating the
    last scenario-day.

    The device-sharded batched engine needs the env axis divisible by the
    mesh size; padding with a real row keeps every shard's program identical
    (the caller drops the padded rows' metrics).
    """
    m = int(env_b.er.shape[0])
    if n == m:
        return env_b
    if n < m:
        raise ValueError(f"cannot pad a {m}-row batch down to {n}")
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (n - m,) + x.shape[1:])]), env_b)


def num_players(env: EnvParams) -> int:
    return env.er.shape[0]


def num_dcs(env: EnvParams) -> int:
    return env.er.shape[1]


def capacity_at(env: EnvParams, tau) -> jnp.ndarray:
    """Effective (I, D) execution-rate ceiling ER·avail at hour tau.

    ``avail`` models outages / demand-response curtailment as a fraction of
    each DC's nodes being powered; the paper's setting is avail ≡ 1.
    """
    return env.er * env.avail[:, tau][None, :]


# ---------------------------------------------------------------------------
# per-source request routing: the (S, I, D) decision surface
# ---------------------------------------------------------------------------

def num_sources(env: EnvParams) -> int:
    return env.origin.shape[0]


def origin_at(env: EnvParams, tau) -> jnp.ndarray:
    """(S, I) demand-origin split at hour tau (columns sum to 1 over s)."""
    return env.origin[:, :, tau]


def source_rtt(env: EnvParams) -> jnp.ndarray:
    """(S, D) source-region → DC round trip.

    Sources are either the DC regions themselves (S = D: the RTT matrix
    verbatim) or the degenerate aggregate source (S = 1: the uniform-origin
    row mean — exactly what the unrouted model prices, so S = 1 routing is
    the bit-for-bit parity reference).
    """
    s, d = num_sources(env), num_dcs(env)
    if s == d:
        return env.rtt
    if s == 1:
        return jnp.mean(env.rtt, axis=0, keepdims=True)
    raise ValueError(
        f"origin has {s} source regions; expected {d} (DC regions) or 1")


def aggregate_origin(env: EnvParams) -> EnvParams:
    """Collapse ``origin`` to the degenerate S = 1 aggregate source.

    The routed engines on the result reproduce the unrouted (PR 3) numbers
    bit-for-bit: one source row at the uniform-origin mean RTT.
    """
    i = num_players(env)
    return env._replace(origin=jnp.ones((1, i, 24), env.origin.dtype))


def project_feasible_routed(env: EnvParams, fractions: jnp.ndarray, tau) -> jnp.ndarray:
    """Map routing fractions (S, I, D) — simplex rows over D per (source,
    task) — to a feasible routed assignment AR3 (S, I, D), tasks/h.

    Feasibility is defined on the totals: Σ_s AR3 obeys eqs. (1)–(2) via the
    same water-filling as the unrouted ``project_feasible`` applied to the
    demand-aggregated fractions Σ_s origin[s, i] · fractions[s, i, :]. Each
    feasible (i, d) cell is then split across sources in proportion to the
    requested per-source mass (capacity shedding hits every source of a cell
    equally); mass water-filled into cells no source requested splits by the
    hour's origin mix. With S = 1 the routed projection *is*
    ``project_feasible`` (one source owns all demand, origin ≡ 1): the
    static shortcut keeps forward values and gradients bit-identical to the
    unrouted game — the ratio path below is 1.0 in value but its quotient
    rule would perturb gradients in the last ulp.
    """
    if fractions.shape[0] == 1:
        return project_feasible(env, fractions[0], tau)[None]
    origin = origin_at(env, tau)                                  # (S, I)
    agg = jnp.sum(origin[:, :, None] * fractions, axis=0)         # (I, D)
    ar = project_feasible(env, agg, tau)                          # (I, D)
    demand = env.car[:, tau][None, :] * origin                    # (S, I)
    req3 = demand[:, :, None] * fractions                         # (S, I, D)
    req = jnp.sum(req3, axis=0)                                   # (I, D)
    ratio = jnp.where(req[None] > 1e-9,
                      req3 / jnp.maximum(req[None], 1e-9),
                      origin[:, :, None])
    return ar[None] * ratio


# ---------------------------------------------------------------------------
# paper objective functions
# ---------------------------------------------------------------------------

def crac_cap_t(env: EnvParams, tau) -> jnp.ndarray:
    """(D,) CRAC cooling-power ceiling at hour tau, scaled by ``avail``: a
    curtailed/outaged DC has proportionally less cooling headroom too."""
    return CRAC_PER_DC * CRAC_MAX_W * env.avail[:, tau]


def dp_max_t(env: EnvParams, tau) -> jnp.ndarray:
    """DP_max[d] at hour tau (eq. 9)."""
    it = (env.it_idle + env.it_dyn) * env.avail[:, tau]
    crac = jnp.minimum(it / power_cop(env), crac_cap_t(env, tau))
    return (it + crac) * env.eff - env.rp[:, tau]


def power_cop(env: EnvParams) -> jnp.ndarray:
    t = env.tsupply
    # empirical CRAC COP fit: the coefficients absorb the degC units
    return 0.0068 * t * t + 0.0008 * t + 0.458  # lint: unit-ok(empirical COP quadratic in supply degC)


def load_share(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """(I, D) per-player share of each DC's load: frac_i / Σ_i frac_i.

    Columns sum to 1 wherever the DC carries load and to 0 where it is idle
    (an idle DC's residual idle/export power is unattributable to players —
    the estimator assigns it to no one).
    """
    frac = ar / jnp.maximum(capacity_at(env, tau), 1e-9)
    rho = jnp.sum(frac, axis=0)
    return frac / jnp.maximum(rho, 1e-9)[None, :]


def dp_est(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """DP_est[i, d] (eq. 10, reconciled): each player's share of the
    *detailed* DC power ``grid_power`` by load share, so
    Σ_i DP_est[i, d] == DP[d] exactly on every loaded DC.

    (The seed scaled DP_max by the raw rate fraction instead, which both
    over-attributed idle power at low utilization and broke
    estimator-vs-simulator agreement — eq. 18 could not match the detailed
    ``step_epoch`` costs it estimates.)
    """
    return grid_power(env, ar, tau)[None, :] * load_share(env, ar, tau)


def cet_est(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """CET[i] (eqs. 11–12): estimated cloud carbon per player, kg/h."""
    de = env.carbon[:, tau][None, :] * dp_est(env, ar, tau) / W_PER_KW
    return jnp.sum(de, axis=1)


def ce_est(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """CE (eq. 13): total estimated cloud carbon."""
    return jnp.sum(cet_est(env, ar, tau))


def nc_est(env: EnvParams, ar: jnp.ndarray) -> jnp.ndarray:
    """NC_est[i, d] (eqs. 14–15): NC_max · AR/ER with NC_max = nprice ·
    sizes · ER (the $/h network bill at full execution rate), which reduces
    to nprice · sizes · AR — identical to what ``step_epoch`` charges.

    (The seed's NC_max was scaled by node counts instead of ER, mis-unitted
    by node·h/task and inconsistent with the detailed simulator.)
    """
    return env.nprice * env.sizes[:, None] * ar


def grid_power(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """Detailed net DC power DP[d] (eq. 4) for a full assignment."""
    rho = jnp.sum(ar / jnp.maximum(capacity_at(env, tau), 1e-9), axis=0)  # (D,)
    a = env.avail[:, tau]
    it = (env.it_idle + env.it_dyn * jnp.clip(rho, 0.0, 1.0)) * a
    crac = jnp.minimum(it / power_cop(env), crac_cap_t(env, tau))
    return (it + crac) * env.eff - env.rp[:, tau]


def peak_increase(env: EnvParams, ar: jnp.ndarray, tau, peak_state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Δ_peak[d] (eq. 6) in $, plus the updated monthly peak state (W)."""
    draw = jnp.maximum(grid_power(env, ar, tau), 0.0)
    new_peak = jnp.maximum(peak_state, draw)
    delta = env.peak_price * (new_peak - peak_state) / W_PER_KW
    return delta, new_peak


def cct_est(env: EnvParams, ar: jnp.ndarray, tau, peak_state: jnp.ndarray) -> jnp.ndarray:
    """CCT[i] (eqs. 16–17): estimated cloud operating cost per player, $/h.

    Reconciled with the detailed simulator: energy is priced on the
    load-share attribution of the actual DC power, and the monthly-peak
    delta is split by the same shares. So Σ_i CCT == the ``step_epoch``
    energy + peak + network costs whenever every DC carries load. (The seed
    added the full fleet delta to *every* player — eq. 18 charged the
    monthly peak I times while the simulator charged it once.)
    """
    share = load_share(env, ar, tau)
    dpe = dp_est(env, ar, tau)
    a = jnp.where(dpe > 0, 1.0, env.alpha[None, :])
    energy = env.eprice[:, tau][None, :] * a * dpe / W_PER_KW
    delta, _ = peak_increase(env, ar, tau, peak_state)
    dc = energy + delta[None, :] * share + nc_est(env, ar)  # lint: unit-ok(peak delta is a one-off $ within the 1 h epoch, commensurable with $/h here)
    return jnp.sum(dc, axis=1)


def cc_est(env: EnvParams, ar: jnp.ndarray, tau, peak_state: jnp.ndarray) -> jnp.ndarray:
    """CC (eq. 18)."""
    return jnp.sum(cct_est(env, ar, tau, peak_state))


# ---------------------------------------------------------------------------
# SLA/latency model (dcsim.latency over EnvParams)
# ---------------------------------------------------------------------------

def latency_ms(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """(I, D) expected response time: mean access RTT + the M/M/c-style
    queued service sojourn at the hour's utilization (``dcsim.latency``).

    ``avail`` cancels out of the zero-load service share (nodes and rate
    curtail together) and enters through rho against effective capacity.

    A fully-dark DC (``avail == 0``, e.g. a realized crash hour) has zero
    effective capacity, so its naive rho is 0/eps — an idle-*fast* server
    that would under-price any allocation still pointing at it. It is
    pinned to saturation instead: the queue factor clamps (finite), the
    miss probability goes to ~1, and residual mass on a dead DC pays full
    SLA freight. Feasible allocations place nothing there, so their
    latency/SLA numbers are unchanged (both are allocation-weighted).
    """
    rho = jnp.sum(ar / jnp.maximum(capacity_at(env, tau), 1e-9), axis=0)
    rho = jnp.where(env.avail[:, tau] > 0.0, rho, 1.0)
    return latency.expected_latency_ms(env.er, env.nn_total, rho, env.rtt)


def sla_cost(env: EnvParams, ar: jnp.ndarray, tau,
             lat_ms: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(I, D) expected SLA-miss cost, $/h: sla_price · AR · p_miss.

    Exactly zero wherever ``sla_price`` is zero (the paper default).
    ``lat_ms`` reuses an already-computed ``latency_ms`` (the eager loop
    engine would otherwise evaluate the queueing model twice per epoch).
    """
    lat = latency_ms(env, ar, tau) if lat_ms is None else lat_ms
    p = latency.sla_miss_prob(lat, env.sla_ms[:, None])
    return env.sla_price[:, None] * ar * p


def sla_cost_est(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """(I,) per-player SLA-miss cost — the latency term of ``cost_sla``.

    Identical to the detailed simulator's charge by construction (both
    price the same expected miss probability), so the estimator/simulator
    consistency extends to the SLA term.
    """
    return jnp.sum(sla_cost(env, ar, tau), axis=1)


def latency_ms_routed(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """(S, I, D) per-path response time: rtt[s, d] + queued sojourn.

    ``ar`` is the assignment that sets utilization — either the (I, D)
    totals or a routed (S, I, D) tensor (summed over sources internally;
    queueing at a DC sees total load regardless of where it came from).
    """
    if ar.ndim == 3:
        ar = jnp.sum(ar, axis=0)
    rho = jnp.sum(ar / jnp.maximum(capacity_at(env, tau), 1e-9), axis=0)
    # dark DC == saturated, not idle-fast (see latency_ms)
    rho = jnp.where(env.avail[:, tau] > 0.0, rho, 1.0)
    return latency.expected_latency_ms_routed(env.er, env.nn_total, rho,
                                              source_rtt(env))


def sla_cost_routed(env: EnvParams, ar3: jnp.ndarray, tau,
                    lat_ms: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(S, I, D) expected SLA-miss cost, $/h, priced per (source, task) path:
    sla_price[i] · AR3[s, i, d] · p_miss(rtt[s, d] + sojourn[i, d]).

    The unrouted ``sla_cost`` prices every request against the fleet-mean
    access RTT; here a scheduler that keeps a region's requests nearby pays
    less than one that back-hauls them cross-country — locality is finally
    priced. ``lat_ms`` reuses an already-computed ``latency_ms_routed``.
    """
    lat3 = latency_ms_routed(env, ar3, tau) if lat_ms is None else lat_ms
    p = latency.sla_miss_prob(lat3, env.sla_ms[None, :, None])
    return env.sla_price[None, :, None] * ar3 * p


def sla_cost_est_routed(env: EnvParams, ar3: jnp.ndarray, tau) -> jnp.ndarray:
    """(I,) per-player SLA-miss cost of a routed assignment — the latency
    term of the routed ``cost_sla`` objective. Identical to the detailed
    simulator's charge by construction (same expected-miss pricing)."""
    return jnp.sum(sla_cost_routed(env, ar3, tau), axis=(0, 2))


OBJECTIVES = ("carbon", "cost", "cost_sla")


def player_reward(env, ar, tau, peak_state, objective: str) -> jnp.ndarray:
    """(I,) per-player objective value (lower is better).

    ``carbon``: CET (eq. 12). ``cost``: CCT (eq. 17). ``cost_sla``: CCT plus
    ``sla_weight`` × the expected SLA-miss cost — the beyond-paper objective
    that prices computational performance into the game.

    ``ar`` is the (I, D) allocation, or a routed (S, I, D) tensor — energy/
    peak/network/carbon terms depend only on the totals Σ_s AR3, while the
    SLA term prices each (source, task) path at its own RTT.
    """
    ar3 = ar if ar.ndim == 3 else None
    if ar3 is not None:
        ar = jnp.sum(ar3, axis=0)
    if objective == "carbon":
        return cet_est(env, ar, tau)
    if objective == "cost":
        return cct_est(env, ar, tau, peak_state)
    if objective == "cost_sla":
        sla = (sla_cost_est(env, ar, tau) if ar3 is None
               else sla_cost_est_routed(env, ar3, tau))
        return cct_est(env, ar, tau, peak_state) + env.sla_weight * sla
    raise ValueError(f"unknown objective {objective!r}; known: {OBJECTIVES}")


# ---------------------------------------------------------------------------
# constraints (eqs. 1–2)
# ---------------------------------------------------------------------------

def feasible_violation(env: EnvParams, ar: jnp.ndarray, tau) -> jnp.ndarray:
    """Aggregate constraint violation (0 when feasible)."""
    split = jnp.abs(jnp.sum(ar, axis=1) - env.car[:, tau])  # eq. (1)
    over = jnp.maximum(ar - capacity_at(env, tau), 0.0)     # eq. (2)
    return jnp.sum(split) + jnp.sum(over)


def project_feasible(env: EnvParams, fractions: jnp.ndarray, tau) -> jnp.ndarray:
    """Map simplex fractions (I, D) → feasible AR (both constraints).

    Rates beyond a DC's effective ER (ER·avail, so outage/curtailment
    windows shed correctly) are redistributed to DCs with headroom
    (iterative water-filling, 4 rounds is enough at <=60% utilization).
    If the whole fleet lacks headroom the residual is dropped — eq. (1)
    then reports the shed load as violation, which is physically right.
    """
    car = env.car[:, tau]
    er_t = capacity_at(env, tau)
    ar = fractions * car[:, None]

    def body(ar, _):
        over = jnp.maximum(ar - er_t, 0.0)
        ar = ar - over
        head = jnp.maximum(er_t - ar, 0.0)
        w = head / jnp.maximum(jnp.sum(head, axis=1, keepdims=True), 1e-9)
        ar = ar + jnp.sum(over, axis=1, keepdims=True) * w
        return ar, None

    ar, _ = jax.lax.scan(body, ar, None, length=4)
    return jnp.minimum(ar, er_t)


# ---------------------------------------------------------------------------
# detailed epoch simulation (ground-truth metrics, not the estimate)
# ---------------------------------------------------------------------------

def step_epoch(
    env: EnvParams, peak_state: jnp.ndarray, ar: jnp.ndarray, tau
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Simulate one epoch under assignment ``ar``; returns (new_peak, metrics).

    ``ar`` is the (I, D) allocation or a routed (S, I, D) tensor; physics
    (power, carbon, energy/peak/network bills) depends only on the totals,
    while the SLA charge and the ``latency_ms`` metric are priced per
    (source, task) path when routed. ``latency_ms`` is the request-weighted
    mean response time over all assignments; ``sla_miss_cost_usd`` rolls
    into ``cost_usd`` (exactly zero at the default ``sla_price = 0``).
    """
    ar3 = ar if ar.ndim == 3 else None
    if ar3 is not None:
        ar = jnp.sum(ar3, axis=0)
    dp = grid_power(env, ar, tau)  # (D,) W, can be negative
    de = env.carbon[:, tau] * dp / W_PER_KW  # kg/h (negative = displaced grid carbon)
    a = jnp.where(dp > 0, 1.0, env.alpha)
    energy_cost = env.eprice[:, tau] * a * dp / W_PER_KW
    delta, new_peak = peak_increase(env, ar, tau, peak_state)
    # $/GB × GB/task × tasks/h is already $/h (the seed divided by 1000 and
    # under-counted the detailed network bill 1000× vs the estimator)
    net_cost = jnp.sum(env.nprice * env.sizes[:, None] * ar, axis=0)
    if ar3 is None:
        lat = latency_ms(env, ar, tau)          # (I, D) ms
        sla = jnp.sum(sla_cost(env, ar, tau, lat_ms=lat), axis=0)  # (D,) $/h
        lat_mean = jnp.sum(ar * lat) / jnp.maximum(jnp.sum(ar), 1e-9)
    else:
        lat = latency_ms_routed(env, ar3, tau)  # (S, I, D) ms per path
        sla = jnp.sum(sla_cost_routed(env, ar3, tau, lat_ms=lat), axis=(0, 1))
        lat_mean = jnp.sum(ar3 * lat) / jnp.maximum(jnp.sum(ar3), 1e-9)
    total_cost = energy_cost + delta + net_cost + sla  # lint: unit-ok(peak delta is a one-off $ within the 1 h epoch, commensurable with $/h here)
    viol = feasible_violation(env, ar, tau)
    rho = jnp.sum(ar / jnp.maximum(capacity_at(env, tau), 1e-9), axis=0)
    metrics = {
        "carbon_kg": jnp.sum(de),
        "cost_usd": jnp.sum(total_cost),
        "energy_cost_usd": jnp.sum(energy_cost),
        "peak_cost_usd": jnp.sum(delta),
        "network_cost_usd": jnp.sum(net_cost),
        "sla_miss_cost_usd": jnp.sum(sla),
        "latency_ms": lat_mean,
        "grid_power_w": jnp.sum(jnp.maximum(dp, 0.0)),
        "violation": viol,
        "max_rho": jnp.max(rho),
    }
    return new_peak, metrics
