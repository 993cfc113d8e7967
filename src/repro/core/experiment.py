"""``ExperimentSpec``: the one declarative front door to every engine.

After three engine PRs the evaluation surface had five entry points
(``run_day``, ``run_day_scan``, ``run_days_batched``, ``run_month``,
``compare_techniques``) that each re-threaded the same ten kwargs and each
maintained their own ``functools.lru_cache`` compile path. This module
replaces that with:

- ``ExperimentSpec`` — a frozen, hashable description of one evaluation
  (technique, objective, engine, routed, hours/days, seeds, solver cfg,
  pretrain). Its *static* fields — the ones that change the compiled
  program — key a single module-level compile cache, so the scan, batched,
  sharded and month engines all share compiled artifacts no matter which
  call site (or legacy shim) asks for them.
- ``run(spec, envs)`` — the façade. ``spec.engine`` selects the day scan,
  the hour-loop reference, the vmapped fleet engine or the month scan;
  ``shard=True`` additionally shards the batched engine's env axis across
  devices via ``shard_map`` (single-device results are identical, and the
  default ``shard=False`` path is byte-for-byte the PR 2–4 program).
- ``sweep(spec, grid)`` — severity sweeps: a cartesian grid of scenario-
  transform parameters (``wan_degradation`` factors, ``origin_shift``
  weights, ``sla_tighten`` …) expands into one stacked env batch, every
  technique runs through ONE batched compile, and the result is structured
  per-grid-point curves — the routed-vs-source-blind degradation plots come
  out of a single call.

The legacy entry points in ``repro.core.schedulers`` are kept as thin shims
over the spec and remain pinned bit-for-bit against their PR 2–4 outputs;
new code should ``from repro.core import ExperimentSpec, run, sweep``.

Realized faults (PR 7, ``repro.faults``): ``run(spec, envs, faults=trace)``
threads a ``FaultTrace`` into the compiled engines as a *runtime* argument
— solvers plan on the unfaulted env, and each hour the scan body re-projects
the planned allocation against realized capacity (``spec.failover`` policy)
and simulates the epoch on the realized env view, emitting
``unserved_demand``/``failover_moved``/``degraded_sla_cost_usd`` (plus
``fallback_hours`` from the numerical finite-guard). Faultedness joins the
compile key, so ``faults=None`` keeps dispatching the exact pre-fault
artifacts. ``sweep(..., resume_dir=...)`` adds chunked, journaled,
retry-supervised grid execution (see ``repro.faults.resume``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults as FL
from .. import obs
from ..dcsim import env as E
from . import game
from . import schedulers as SCH
from .game import GameContext, fractions_to_ar

_TOTAL_KEYS = ("carbon_kg", "cost_usd", "sla_miss_cost_usd", "violation")

# degradation metrics: present (and summed into totals) only on engines
# compiled with faults/guard — the unfaulted metric dicts never carry them,
# which is what keeps the faults=None result dicts bit-identical
_FAULT_KEYS = ("unserved_demand", "failover_moved", "degraded_sla_cost_usd",
               "fallback_hours")

# per-hour physical signals streamed by the "engine/hour" tap
_TAP_HOUR_KEYS = ("carbon_kg", "cost_usd", "sla_miss_cost_usd", "latency_ms",
                  "grid_power_w")

ENGINES = ("scan", "loop", "batched", "month")


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One evaluation, declaratively. Frozen and hashable: the static fields
    (``technique``, ``objective``, ``hours``, ``cfg``, ``routed``) key the
    module compile cache; the rest (seeds, days, pretrain) only select
    runtime inputs.

    ``engine``: ``"scan"`` — one env, one jitted day; ``"loop"`` — the
    Python hour-loop parity reference; ``"batched"`` — a fleet of
    scenario-days in one vmapped compile (optionally device-sharded);
    ``"month"`` — a second-level scan threading the monthly peak across
    days. ``seeds`` (batched) / ``seed`` (everything else) reproduce the
    legacy entry points' RNG discipline exactly.

    ``taps`` opts the spec into telemetry streams (``repro.obs`` tap
    patterns, e.g. ``("engine/hour", "gt_drl/*")``): tapped engines compile
    as *separate* cache entries whose scan bodies stream diagnostics to the
    obs ring buffer; ``None`` defers to the ambient ``obs.taps(...)``
    context (default: everything off, and the taps-off artifacts are
    bit-for-bit the pre-obs programs).

    ``failover`` picks the realized-fault re-projection policy
    (``repro.faults.POLICIES``) — consulted only when ``run`` receives
    ``faults=``, and normalized out of the compile key otherwise, so it is
    free on unfaulted specs. ``guard=True`` compiles the numerical
    finite-guard (fallback to the capacity-proportional baseline +
    ``fallback_hours`` counter) into an *unfaulted* engine too; faulted
    engines always guard.
    """
    technique: str = "fd"
    objective: str = "carbon"
    engine: str = "scan"
    routed: bool = False
    hours: int = 24
    days: Optional[int] = None            # lint: runtime-only(month engine env repeat count: scan length is data, the per-day program is one artifact)
    seed: int = 0                         # lint: runtime-only(PRNG key material is a traced input, never part of the program)
    seeds: Optional[Tuple[int, ...]] = None  # lint: runtime-only(batched engine per-env keys: vmapped runtime input)
    pretrain: bool = True                 # lint: runtime-only(selects the initial solver state passed in at call time; the compiled epoch is identical)
    cfg: Any = None                       # solver config (frozen dataclass)
    taps: Optional[Tuple[str, ...]] = None   # obs tap patterns (None: ambient)
    failover: str = FL.DEFAULT_POLICY     # realized-fault failover policy
    guard: bool = False                   # finite-guard even when unfaulted
    workload: str = "aibench"             # capability layer the envs came from

    def __post_init__(self):
        if not isinstance(self.workload, str):
            raise ValueError(
                "spec.workload is a capability-layer *name* (the envs "
                "already embed the derived numbers; the name only keys the "
                f"compile cache), got {type(self.workload).__name__}")
        if self.engine not in ENGINES:
            raise ValueError(
                f"unknown engine {self.engine!r}; known: {ENGINES}")
        if self.objective not in E.OBJECTIVES:
            raise ValueError(f"unknown objective {self.objective!r}; "
                             f"known: {E.OBJECTIVES}")
        if self.failover not in FL.POLICIES:
            raise ValueError(f"unknown failover policy {self.failover!r}; "
                             f"known: {FL.POLICIES}")
        if self.seeds is not None and not isinstance(self.seeds, tuple):
            object.__setattr__(self, "seeds", tuple(self.seeds))
        if self.taps is not None and not isinstance(self.taps, tuple):
            object.__setattr__(self, "taps", tuple(self.taps))

    def replace(self, **changes) -> "ExperimentSpec":
        return dataclasses.replace(self, **changes)

    def static_key(self) -> Tuple[str, str, int, Any, bool, str, bool, str]:
        """The compile-relevant fields, in ``_day_core`` argument order.

        ``workload`` joins the key even though the engines only ever see
        ``EnvParams``: two workloads legitimately differ in the task-type
        count ``I`` (a shape, hence a retrace), and keeping their artifacts
        under distinct keys makes the cache accounting
        (``obs.engine_stat``) attribute compiles to the right workload.
        """
        return (self.technique, self.objective, self.hours, self.cfg,
                self.routed, self.failover, self.guard, self.workload)

    def effective_taps(self) -> frozenset:
        """The tap set this spec's engines compile under: the spec's own
        ``taps`` when given, else the ambient ``obs.taps(...)`` state. Part
        of the compile key, so tapped and untapped artifacts coexist."""
        return (obs.active_taps() if self.taps is None
                else frozenset(self.taps))


# ---------------------------------------------------------------------------
# engine cores (pure, jit/vmap/scan-friendly)
# ---------------------------------------------------------------------------

def _solver_step(technique: str, cfg) -> Callable:
    """step(key, state, ctx, peak) -> (state, SolveResult) from the registry;
    state threads the scan carry (per-player agents for gt-drl, () for
    stateless solvers)."""
    t = game.get_technique(technique)
    cfg = t.resolve_cfg(cfg)
    step = t.step

    def bound(key, state, ctx, peak):
        return step(key, state, ctx, peak, cfg)
    return bound


@functools.lru_cache(maxsize=None)
def _day_core(technique: str, objective: str, hours: int, cfg,
              routed: bool = False, failover: str = FL.DEFAULT_POLICY,
              guard: bool = False, workload: str = "aibench",
              faulted: bool = False,
              taps: frozenset = frozenset()) -> Callable:
    """day(env, key, peak0, state0[, trace]) -> (peak, state, metrics dict).

    Pure and jit/vmap-friendly; the RNG key is split exactly as the
    reference loop does, so both engines see the same per-epoch keys.
    ``routed`` plays the (S, I, D) routing game instead of the (I, D) one.

    ``faulted`` cores take a fifth argument — a ``faults.FaultTrace``
    pytree — and execute every hour through the plan/execute split: the
    solver steps on the unfaulted ``env`` (planning), then
    ``faults.execute_hour`` re-projects its allocation against realized
    capacity (``failover`` policy) and simulates the epoch on the realized
    env view. ``guard`` (implied by ``faulted``) compiles the finite-guard
    on the solver's joint strategy. All three are trace-time flags: the
    default core lowers to exactly the pre-fault program.

    ``taps`` only keys the cache: the ``obs.tap`` calls in the body check
    trace-time enablement themselves (the dispatch wrapper pins the active
    set to this key's ``taps``), so a taps-off core lowers to exactly the
    pre-obs program and a tapped core is a distinct artifact.

    ``workload`` likewise only keys the cache (see ``static_key``): the body
    is workload-agnostic — a derived llm env is just an ``EnvParams`` with a
    different ``I``.
    """
    del workload  # cache-key discriminator only
    step = _solver_step(technique, cfg)
    guard_on = guard or faulted

    # named scopes reach the compiled HLO's op_name metadata only, so a
    # device trace's ops can be put down to a layer of the hour
    def _body(env, trace, carry, tau):
        key, peak, state = carry
        key, ks = jax.random.split(key)
        ctx = GameContext(env=env, tau=tau, objective=objective,
                          routed=routed)
        with jax.named_scope("solver"):
            state, res = step(ks, state, ctx, peak)
        with jax.named_scope("nash_probe"):
            game.tap_nash_residual(ctx, res.fractions, peak)
        fr = res.fractions
        if guard_on:
            with jax.named_scope("guard"):
                fr, fell_back = FL.guard_fractions(env, tau, fr)
        with jax.named_scope("project"):
            ar = fractions_to_ar(ctx, fr)
        if faulted:
            with jax.named_scope("failover"):
                peak, m = FL.execute_hour(env, trace, peak, ar, tau, failover)
        else:
            with jax.named_scope("simulate"):
                peak, m = E.step_epoch(env, peak, ar, tau)
        if guard_on:
            m = {**m, "fallback_hours": fell_back}
        tap_keys = _TAP_HOUR_KEYS + tuple(k for k in _FAULT_KEYS if k in m)
        with jax.named_scope("tap"):
            obs.tap("engine/hour",
                    {"tau": tau, **{k: m[k] for k in tap_keys}})
        return (key, peak, state), m

    taus = functools.partial(jnp.arange, dtype=jnp.int32)
    if faulted:
        def day(env: E.EnvParams, key, peak0, state0, trace):
            (_, peak, state), ms = jax.lax.scan(
                functools.partial(_body, env, trace), (key, peak0, state0),
                taus(hours))
            return peak, state, ms
    else:
        def day(env: E.EnvParams, key, peak0, state0):
            (_, peak, state), ms = jax.lax.scan(
                functools.partial(_body, env, None), (key, peak0, state0),
                taus(hours))
            return peak, state, ms

    return day


def _sharded_batch(core: Callable, faulted: bool = False,
                   fault_axis: bool = False) -> Callable:
    """Shard the batched day engine's env axis across all local devices.

    ``shard_map`` over a 1-axis device mesh: env rows and their RNG keys
    split by shard, (peak0, state0) replicated — and the fault trace, when
    present, replicated (one shared day of trouble) or split with the env
    rows (``fault_axis=True``, a per-point stacked trace); each device runs
    the plain vmapped day core on its slice, so a 1-device mesh runs the
    EXACT unsharded program and N devices evaluate N env shards in parallel
    with zero cross-device collectives.
    """
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()), ("env",))
    axes = (0, 0, None, None) + (
        ((0 if fault_axis else None),) if faulted else ())
    specs = (P("env"), P("env"), P(), P()) + (
        ((P("env") if fault_axis else P()),) if faulted else ())
    batched = jax.vmap(core, in_axes=axes)
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=specs,
                       out_specs=(P("env"), P("env"), P("env")),
                       check_vma=False)
    return jax.jit(fn)


_KINDS = ("day", "batched", "sharded", "month")


@functools.lru_cache(maxsize=None)
def _compiled_raw(kind: str, technique: str, objective: str, hours: int, cfg,
                  routed: bool, failover: str, guard: bool, workload: str,
                  faulted: bool, fault_axis: bool,
                  taps: frozenset) -> Callable:
    """THE compile cache: one jitted artifact per (engine kind, spec static
    fields, failover/guard/faulted flags, workload, tap set), shared by
    ``run``/``sweep`` and every legacy shim — no engine compiles per call
    site anymore. Artifacts come back wrapped in the obs dispatch span
    (per-call timing + trace-time tap pinning).

    ``fault_axis`` (batched/sharded only): the FaultTrace carries a leading
    env-batch axis — one realized day of trouble per env row — instead of
    one trace shared by every row."""
    key = (kind, technique, objective, hours, cfg, routed, failover, guard,
           workload, faulted, fault_axis, taps)
    if fault_axis and kind not in ("batched", "sharded"):
        raise ValueError("a per-point (stacked) FaultTrace only makes sense "
                         "on the batched/sharded engines; the day and month "
                         f"engines take one trace (kind={kind!r})")
    core = _day_core(technique, objective, hours, cfg, routed, failover,
                     guard, workload, faulted, taps)
    if kind == "day":
        fn = jax.jit(core)
    elif kind == "batched":
        axes = (0, 0, None, None) + (
            ((0 if fault_axis else None),) if faulted else ())
        fn = jax.jit(jax.vmap(core, in_axes=axes))
    elif kind == "sharded":
        fn = _sharded_batch(core, faulted, fault_axis)
    elif kind == "month":
        if faulted:
            raise ValueError(
                "the month engine does not take realized faults yet: a "
                "FaultTrace describes one 24h day, and the month scan "
                "threads days through a second-level carry; run faulted "
                "days through the scan/batched engines")
        def month(env_days, keys, peak0, state0):
            def body(carry, x):
                peak, state = carry
                env, key = x
                peak, state, ms = core(env, key, peak, state)
                return (peak, state), (ms, peak)

            (peak, state), (ms, peaks) = jax.lax.scan(
                body, (peak0, state0), (env_days, keys))
            return peak, state, ms, peaks

        fn = jax.jit(month)
    else:
        raise ValueError(f"unknown engine kind {kind!r}; known: {_KINDS}")
    return obs.spans.instrument_dispatch(key, fn)


def _compiled(kind: str, technique: str, objective: str, hours: int, cfg,
              routed: bool, failover: str = FL.DEFAULT_POLICY,
              guard: bool = False, workload: str = "aibench",
              faulted: bool = False, fault_axis: bool = False,
              taps: frozenset = frozenset()) -> Callable:
    """Front door to the compile cache: same artifact as ``_compiled_raw``
    but every lookup/build is accounted in ``obs.cache_stats()``."""
    key = (kind, technique, objective, hours, cfg, routed, failover, guard,
           workload, faulted, fault_axis, taps)
    hit = obs.spans.engine_lookup(key)
    if hit:
        return _compiled_raw(*key)
    t0 = time.perf_counter()
    fn = _compiled_raw(*key)
    obs.spans.note_build(key, time.perf_counter() - t0)
    return fn


# the cache-introspection surface tests rely on (lru semantics preserved)
_compiled.cache_info = _compiled_raw.cache_info


def _engine_key(spec: ExperimentSpec, *, shard: bool = False,
                faulted: bool = False, fault_axis: bool = False) -> tuple:
    """The compile-cache key ``run`` uses for this spec (also the join key
    for ``obs.engine_stat`` / run records).

    ``failover`` is an execute-time policy: on unfaulted lookups it is
    normalized to the default so a spec's policy choice never forks the
    (identical) unfaulted artifact; ``fault_axis`` is likewise normalized
    out of unfaulted keys.
    """
    kind = {"scan": "day", "batched": "sharded" if shard else "batched",
            "month": "month"}.get(spec.engine)
    if kind is None:
        raise ValueError(f"engine {spec.engine!r} is not compiled")
    technique, objective, hours, cfg, routed, failover, guard, workload = \
        spec.static_key()
    if not faulted:
        failover = FL.DEFAULT_POLICY
        fault_axis = False
    return (kind, technique, objective, hours, cfg, routed, failover, guard,
            workload, faulted, fault_axis, spec.effective_taps())


def compiled_engine(spec: ExperimentSpec, *, shard: bool = False,
                    faulted: bool = False, fault_axis: bool = False) -> Callable:
    """The spec's compiled engine (public access to the cache)."""
    return _compiled(*_engine_key(spec, shard=shard, faulted=faulted,
                                  fault_axis=fault_axis))


def _clear_compile_caches() -> None:
    _day_core.cache_clear()
    _compiled_raw.cache_clear()
    obs.spans.note_eviction()


_compiled.cache_clear = _clear_compile_caches


# re-registering a technique name must not serve stale compiled engines
game.on_technique_change(_clear_compile_caches)


# ---------------------------------------------------------------------------
# runtime inputs + result formatting (the legacy entry points' exact shapes)
# ---------------------------------------------------------------------------

def _day_inputs(env, technique, objective, seed, pretrain, cfg,
                solver_state0=None, routed: bool = False):
    """Replicates the reference loop's key discipline + initial solver state.

    An injected ``solver_state0`` short-circuits state construction (no
    throwaway pretrain/init work) while keeping the key discipline intact.
    """
    key = jax.random.PRNGKey(seed)
    kp, key = jax.random.split(key)
    if solver_state0 is not None:
        return key, solver_state0
    t = game.get_technique(technique)
    return key, t.init_state(kp, env, objective, cfg, routed, pretrain)


def _totals_keys(present) -> Tuple[str, ...]:
    """The result's totals keys: the invariant ``_TOTAL_KEYS`` plus any
    degradation metrics the engine actually emitted (faulted/guarded
    engines only — unfaulted result dicts are unchanged)."""
    return _TOTAL_KEYS + tuple(k for k in _FAULT_KEYS if k in present)


def _fetch(ms: Mapping[str, Any], rows: Optional[int] = None
           ) -> Dict[str, np.ndarray]:
    """Copy each engine output to the host (its first ``rows`` rows)."""
    with obs.span("engine.fetch"):
        out = {k: np.asarray(v) for k, v in ms.items()}
        if rows is not None:
            out = {k: v[:rows] for k, v in out.items()}
        obs.count("fetches", len(out))
        return out


def _dispatched(rows: int, fleet_hours: int) -> None:
    """Count one engine call's env rows (padding included) and its real
    fleet-hours on the current request."""
    obs.count("rows", rows)
    obs.count("fleet_hours", fleet_hours)


def _format_day(ms, hours: int, technique: str, objective: str) -> Dict[str, Any]:
    """Stacked (hours,) metric arrays -> the run_day result dict."""
    host = _fetch(ms)
    with obs.span("engine.format"):
        host = {k: v.astype(float).tolist() for k, v in host.items()}
        per_epoch = [{**{k: host[k][t] for k in host}, "tau": t}
                     for t in range(hours)]
        totals = {k: 0.0 for k in _totals_keys(host)}
        for row in per_epoch:
            for k in totals:
                totals[k] += row[k]
        return {"per_epoch": per_epoch, "totals": totals,
                "technique": technique, "objective": objective}


# ---------------------------------------------------------------------------
# the façade
# ---------------------------------------------------------------------------

def _trace_stacked(faults) -> bool:
    """Does this FaultTrace carry a leading env-batch axis (one realized
    trace per env row)? Detected off ``avail_mult``: (n, D, 24) vs (D, 24)."""
    return faults is not None and np.ndim(faults.avail_mult) == 3


def run(
    spec: ExperimentSpec,
    envs,
    *,
    peak_state0: Optional[jnp.ndarray] = None,
    solver_state0: Any = None,
    solver: Optional[Callable] = None,
    shard: bool = False,
    record: Any = None,
    faults: Any = None,
) -> Dict[str, Any]:
    """Run one experiment. ``envs`` is a single EnvParams for the scan/loop
    engines, one-or-many (list or stacked) for batched, and one/list/stacked
    per-day rows for month.

    ``solver_state0`` injects an initial solver carry (deployed GT-DRL
    agents); ``solver`` injects a prebuilt stateful closure (loop engine
    only); ``shard=True`` (batched only) shards the env axis across devices
    via ``shard_map`` — identical results, the batch is padded to the device
    count and the padded rows' metrics dropped.

    ``faults`` (a ``repro.faults.FaultTrace``) switches the engine to the
    plan/execute split: solvers plan on the unfaulted ``envs`` while every
    hour executes against the trace's realized env view under
    ``spec.failover``, adding ``unserved_demand`` / ``failover_moved`` /
    ``degraded_sla_cost_usd`` / ``fallback_hours`` to the metrics. The
    batched engine takes either one trace shared across all env rows (the
    same day of trouble hits every scenario) or a stacked per-row trace
    (``faults.stack_traces`` — leading axis matches the env batch, so each
    grid point realizes its own day of trouble). ``faults=None`` (default)
    dispatches the exact unfaulted artifacts.

    ``record`` (True, or a JSONL path) appends a spec-keyed ``RunRecord``
    — totals, convergence curves, engine timing spans, git/jax provenance —
    under ``runs/`` (see ``repro.obs.records``).
    """
    with obs.request("api.run"):
        if shard and spec.engine != "batched":
            raise ValueError("shard=True needs engine='batched', "
                             f"got {spec.engine!r}")
        if shard and spec.effective_taps():
            raise ValueError("taps stream through jax.debug.callback, which the "
                             "shard_map engine does not support; run shard=False "
                             "when tapping")
        if solver is not None and spec.engine != "loop":
            raise ValueError("a prebuilt solver closure needs engine='loop', "
                             f"got {spec.engine!r}")
        if peak_state0 is not None and spec.engine == "batched":
            raise ValueError("the batched engine starts every scenario-day from "
                             "a zero peak; peak_state0 is not supported")
        if solver_state0 is not None and spec.engine == "loop":
            raise ValueError("the loop engine derives solver state from the "
                             "seed or a prebuilt solver=; solver_state0 is "
                             "scan/batched/month-only")
        if faults is not None and spec.engine == "month":
            raise ValueError("the month engine does not take realized faults "
                             "yet (a FaultTrace describes one day); run faulted "
                             "days through scan/loop/batched")
        if _trace_stacked(faults) and spec.engine != "batched":
            raise ValueError("a stacked (per-point) FaultTrace needs "
                             f"engine='batched', got {spec.engine!r}; the "
                             "scan/loop engines evaluate one env against one "
                             "trace")
        game.get_technique(spec.technique)  # fail fast with the known-names list
        if spec.engine == "scan":
            result = _run_scan(spec, envs, peak_state0, solver_state0, faults)
        elif spec.engine == "loop":
            result = _run_loop(spec, envs, peak_state0, solver, faults)
        elif spec.engine == "batched":
            result = _run_batched(spec, envs, solver_state0, shard, faults)
        else:
            result = _run_month(spec, envs, peak_state0, solver_state0)
        if record:
            with obs.span("run.record"):
                _record_run(spec, result, shard=shard, path=record,
                            faulted=faults is not None,
                            fault_axis=_trace_stacked(faults))
        return result


def _record_run(spec: ExperimentSpec, result: Dict[str, Any], *,
                shard: bool = False, path: Any = None,
                kind: str = "run", faulted: bool = False,
                fault_axis: bool = False) -> str:
    """Emit one JSONL RunRecord for a finished ``run`` result."""
    engine_spans = (None if spec.engine == "loop"
                    else obs.engine_stat(_engine_key(spec, shard=shard,
                                                     faulted=faulted,
                                                     fault_axis=fault_axis)))
    rec = obs.make_record(spec, result, kind=kind, engine_spans=engine_spans)
    return obs.write_record(rec, path if isinstance(path, str) else None)


def _run_scan(spec, env, peak_state0, solver_state0, faults=None):
    with obs.span("engine.inputs"):
        key, state0 = _day_inputs(env, spec.technique, spec.objective,
                                  spec.seed, spec.pretrain, spec.cfg,
                                  solver_state0, spec.routed)
        peak0 = (peak_state0 if peak_state0 is not None
                 else jnp.zeros((E.num_dcs(env),)))
    day = _compiled(*_engine_key(spec, faulted=faults is not None))
    _dispatched(1, spec.hours)
    if faults is None:
        _, _, ms = day(env, key, peak0, state0)
    else:
        _, _, ms = day(env, key, peak0, state0, faults)
    return _format_day(ms, spec.hours, spec.technique, spec.objective)


def _run_loop(spec, env, peak_state0, solver, faults=None):
    """The seed Python hour-loop, kept as the parity reference (including
    for the faulted plan/execute split — the same ``faults`` helpers run
    eagerly here). Metrics accumulate on-device and transfer with ONE
    ``jax.device_get``."""
    key = jax.random.PRNGKey(spec.seed)
    _, key = jax.random.split(key)
    if solver is None:
        if game.get_technique(spec.technique).stateful:
            # the scan engine's exact init discipline (same kp, same
            # pretrain flag), so loop-vs-scan parity holds for ANY
            # registered stateful technique, not just gt-drl
            _, state0 = _day_inputs(env, spec.technique, spec.objective,
                                    spec.seed, spec.pretrain, spec.cfg,
                                    None, spec.routed)
            solver = SCH.StatefulScheduler(spec.technique, state0,
                                           spec.cfg).solve_epoch
        else:
            solver = SCH.get_scheduler(
                spec.technique, env, spec.objective, routed=spec.routed,
                **({"cfg": spec.cfg} if spec.cfg is not None else {}),
            )
    d = E.num_dcs(env)
    guard_on = spec.guard or faults is not None
    peak = peak_state0 if peak_state0 is not None else jnp.zeros((d,))
    epoch_metrics: List[Dict[str, jnp.ndarray]] = []
    for tau in range(spec.hours):
        key, ks = jax.random.split(key)
        ctx = GameContext(env=env, tau=jnp.int32(tau), objective=spec.objective,
                          routed=spec.routed)
        res = solver(ks, ctx, peak)
        fr = res.fractions
        if guard_on:
            fr, fell_back = FL.guard_fractions(env, jnp.int32(tau), fr)
        ar = fractions_to_ar(ctx, fr)
        if faults is None:
            peak, m = E.step_epoch(env, peak, ar, jnp.int32(tau))
        else:
            peak, m = FL.execute_hour(env, faults, peak, ar, jnp.int32(tau),
                                      spec.failover)
        if guard_on:
            m = {**m, "fallback_hours": fell_back}
        epoch_metrics.append(m)  # stays on device; no per-epoch host sync
    _dispatched(1, spec.hours)
    with obs.span("engine.fetch"):
        host_metrics = jax.device_get(epoch_metrics)  # ONE transfer
        obs.count("fetches", sum(len(m) for m in epoch_metrics))
    with obs.span("engine.format"):
        per_epoch: List[Dict[str, float]] = []
        totals = {k: 0.0 for k in _totals_keys(
            epoch_metrics[0] if epoch_metrics else ())}
        for tau, m in enumerate(host_metrics):
            row = {k: float(v) for k, v in m.items()}
            row["tau"] = tau
            per_epoch.append(row)
            for k in totals:
                totals[k] += row[k]
        return {"per_epoch": per_epoch, "totals": totals,
                "technique": spec.technique, "objective": spec.objective}


def _run_batched(spec, envs, solver_state0, shard, faults=None):
    with obs.span("engine.inputs"):
        if isinstance(envs, E.EnvParams) and envs.er.ndim == 2:
            envs = [envs]  # single env == batch of one (compare_techniques parity)
        if isinstance(envs, E.EnvParams):
            env_b, n = envs, int(envs.er.shape[0])
            env0 = jax.tree_util.tree_map(lambda x: x[0], envs)
        else:
            envs = list(envs)
            env_b, n = E.stack_envs(envs), len(envs)
            env0 = envs[0]
        seeds = list(range(n)) if spec.seeds is None else list(spec.seeds)
        if len(seeds) != n:
            raise ValueError(f"{len(seeds)} seeds for {n} scenario-days")

        # per-day keys split exactly as run_day splits them; gt-drl
        # pretrains ONCE on the first seed's pretrain key (deploy-once
        # semantics)
        keys = jnp.stack([jax.random.split(jax.random.PRNGKey(s))[1]
                          for s in seeds])
        _, state0 = _day_inputs(env0, spec.technique, spec.objective,
                                seeds[0], spec.pretrain, spec.cfg,
                                solver_state0, spec.routed)
        peak0 = jnp.zeros((E.num_dcs(env0),))

        faulted = faults is not None
        stacked = _trace_stacked(faults)  # per-row traces vs one shared trace
        if stacked and int(faults.avail_mult.shape[0]) != n:
            raise ValueError(
                f"stacked FaultTrace has {int(faults.avail_mult.shape[0])} "
                f"rows for {n} scenario-days")
        trace = (faults,) if faulted else ()
        pad = (-n) % jax.device_count() if shard else 0
        if pad:
            env_b = E.pad_env_batch(env_b, n + pad)
            keys = jnp.concatenate(
                [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])])
            if stacked:  # pad the trace rows alongside their envs
                trace = (jax.tree_util.tree_map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(x[-1:], (pad,) + x.shape[1:])]),
                    faults),)
    batch = _compiled(*_engine_key(spec, shard=shard, faulted=faulted,
                                   fault_axis=stacked))
    _dispatched(n + pad, n * spec.hours)
    _, _, ms = batch(env_b, keys, peak0, state0, *trace)
    out = _fetch(ms, n if pad else None)  # (n, hours) each
    with obs.span("engine.format"):
        totals = {k: out[k].sum(axis=1) for k in _totals_keys(out)}
        return {"totals": totals, "per_epoch": out,
                "technique": spec.technique, "objective": spec.objective,
                "seeds": seeds}


def _run_month(spec, envs, peak_state0, solver_state0):
    days = spec.days
    with obs.span("engine.inputs"):
        if isinstance(envs, E.EnvParams) and envs.er.ndim == 2:
            n = 30 if days is None else int(days)
            env0, env_days = envs, E.tile_env(envs, n)
        elif isinstance(envs, E.EnvParams):
            n = int(envs.er.shape[0])
            env0 = jax.tree_util.tree_map(lambda x: x[0], envs)
            env_days = envs
        else:
            envs = [e if isinstance(e, E.EnvParams) else e[1] for e in envs]
            n, env0, env_days = len(envs), envs[0], E.stack_envs(envs)
        if days is not None and int(days) != n:
            raise ValueError(f"days={days} but {n} per-day envs were given")

        keys = jnp.stack(
            [jax.random.split(jax.random.PRNGKey(spec.seed + d))[1]
             for d in range(n)])
        _, state0 = _day_inputs(env0, spec.technique, spec.objective,
                                spec.seed, spec.pretrain, spec.cfg,
                                solver_state0, spec.routed)
        peak0 = (peak_state0 if peak_state0 is not None
                 else jnp.zeros((E.num_dcs(env0),)))

    month = _compiled(*_engine_key(spec))
    _dispatched(n, n * spec.hours)
    final_peak, _, ms, peaks = month(env_days, keys, peak0, state0)
    per_day = _fetch(ms)  # (n, hours) each
    host = _fetch({"peaks": peaks, "final_peak": final_peak})
    with obs.span("engine.format"):
        day_totals = {k: per_day[k].sum(axis=1) for k in _TOTAL_KEYS}
        return {"per_day": per_day, "day_totals": day_totals,
                "totals": {k: float(day_totals[k].sum())
                           for k in _TOTAL_KEYS},
                "peak_w": host["peaks"], "final_peak_w": host["final_peak"],
                "days": n, "technique": spec.technique,
                "objective": spec.objective}


# ---------------------------------------------------------------------------
# severity sweeps: parameter grids -> stacked envs -> per-point curves
# ---------------------------------------------------------------------------

def sweep(
    spec: ExperimentSpec,
    grid: Mapping[str, Sequence[Any]],
    *,
    base_env: Optional[E.EnvParams] = None,
    techniques: Optional[Sequence[str]] = None,
    base_scenarios: Sequence[Any] = (),
    cfg_overrides: Optional[Mapping[str, Any]] = None,
    shard: bool = False,
    record: Any = None,
    faults: Any = None,
    resume_dir: Optional[str] = None,
    chunk_points: Optional[int] = None,
    max_retries: int = 2,
    backoff_s: float = 0.25,
    point_timeout_s: Optional[float] = None,
) -> Dict[str, Any]:
    """Severity sweep: the cartesian ``grid`` of scenario-transform
    parameters expands into one stacked env batch, and every technique runs
    through ONE batched compile over all grid points.

    ``grid`` maps a registered transform name to a sequence of points — a
    params dict, or a bare scalar for the transform's declared severity knob
    (``{"wan_degradation": (1.0, 2.0, 4.0), "origin_shift": (0.0, 0.8)}`` is
    a 3 × 2 factor × weight grid). ``base_scenarios`` (Scenario specs or
    transforms) apply to ``base_env`` before every grid point — e.g. an
    ``sla_tighten`` row so misses are priced. Every point runs with
    ``spec.seed``'s RNG stream, so severity is the only variable along a
    curve. ``cfg_overrides`` maps technique -> solver config; ``spec.cfg``
    covers ``spec.technique`` itself, other techniques default. ``faults``
    executes every grid point through the realized plan/execute split under
    ``spec.failover`` — one ``repro.faults.FaultTrace`` shared by every
    point, a sequence of traces (one per grid point, stacked via
    ``faults.stack_traces``), or an already-stacked trace whose leading
    axis matches the grid.

    ``resume_dir`` switches to resumable execution: the grid runs in chunks
    of ``chunk_points`` grid points (default 1) per technique, each
    completed chunk journaled atomically under ``resume_dir`` (see
    ``repro.faults.SweepJournal``). A sweep killed mid-grid re-runs with
    the same arguments and recomputes ONLY the missing chunks; a chunk that
    raises is retried up to ``max_retries`` times with exponential backoff
    (``backoff_s * 2**k``); ``point_timeout_s`` bounds each chunk's wall
    time (a timed-out chunk fails into the retry path). The result gains a
    ``"resume"`` meta block (journal dir, chunks restored vs computed,
    retries, straggler chunks).

    Returns ``{"points": [{name: params}], "labels": [...], "results":
    {technique: {"totals": {k: (P,)}, "per_epoch": {k: (P, hours)}}}}`` —
    each metric row is one grid point's curve (the routed-vs-source-blind
    degradation plot is two techniques of one sweep).
    """
    from .. import scenarios as S

    with obs.request("api.sweep"):
        with obs.span("sweep.grid"):
            if base_env is None:
                base_env = E.build_env(4, seed=0)
            points, rows = S.build_grid(base_env, grid, base=base_scenarios)
            labels = [lbl for lbl, _ in rows]
            envs = [env for _, env in rows]
        n = len(rows)
        techniques = tuple(techniques) if techniques else (spec.technique,)
        overrides = dict(cfg_overrides or {})

        if faults is not None and not isinstance(faults, FL.FaultTrace):
            with obs.span("sweep.stack"):
                faults = FL.stack_traces(faults)  # one trace per point
        if _trace_stacked(faults) and int(faults.avail_mult.shape[0]) != n:
            raise ValueError(
                f"per-point faults: {int(faults.avail_mult.shape[0])} traces "
                f"for {n} grid points")

        def point_spec(t, n_pts):
            cfg = overrides.get(t, spec.cfg if t == spec.technique else None)
            return spec.replace(technique=t, cfg=cfg, engine="batched",
                                seeds=(spec.seed,) * n_pts)

        if resume_dir is not None:
            results, resume_meta = _sweep_resumable(
                point_spec, envs, techniques, labels, faults=faults,
                shard=shard, resume_dir=resume_dir,
                chunk_points=chunk_points or 1, max_retries=max_retries,
                backoff_s=backoff_s, point_timeout_s=point_timeout_s)
        else:
            resume_meta = None
            with obs.span("sweep.stack"):
                env_b = E.stack_envs(envs)
            results = {}
            for t in techniques:
                pspec = point_spec(t, n)
                res = _run_batched(pspec, env_b, None, shard, faults)
                results[t] = {"totals": res["totals"],
                              "per_epoch": res["per_epoch"]}
        if record:
            with obs.span("run.record"):
                _record_sweep(spec, grid, point_spec, techniques, results,
                              labels, shard=shard, faults=faults, path=record)
        out = {"grid": {name: list(pts) for name, pts in grid.items()},
               "points": points, "labels": labels, "results": results,
               "objective": spec.objective, "hours": spec.hours,
               "routed": spec.routed, "techniques": list(techniques)}
        if resume_meta is not None:
            out["resume"] = resume_meta
        return out


def _record_sweep(spec, grid, point_spec, techniques, results, labels, *,
                  shard, faults, path) -> None:
    """One JSONL RunRecord per technique of a finished sweep: each grid
    point's daily totals form the "curve" along the sweep's label axis."""
    n = len(labels)
    for t in techniques:
        pspec = point_spec(t, n)
        rec = obs.make_record(
            pspec, {**results[t], "technique": t,
                    "objective": spec.objective},
            kind="sweep",
            curves={k: np.asarray(v, dtype=float).tolist()
                    for k, v in results[t]["totals"].items()},
            engine_spans=obs.engine_stat(
                _engine_key(pspec, shard=shard, faulted=faults is not None,
                            fault_axis=_trace_stacked(faults))),
            extra={"labels": labels,
                   "grid": {name: list(pts) for name, pts in grid.items()}})
        obs.write_record(rec, path if isinstance(path, str) else None)


def _sweep_resumable(point_spec, envs, techniques, labels, *, faults, shard,
                     resume_dir, chunk_points, max_retries, backoff_s,
                     point_timeout_s):
    """The journaled chunk-at-a-time sweep path (see ``sweep``'s docstring).

    Execution plan: techniques in order, each technique's grid points in
    chunks of ``chunk_points``; the global chunk index is the journal step.
    Chunks run strictly in order, so the journal is always a prefix of the
    plan and ``SweepJournal.next_step()`` is the resume frontier. The
    supervisor is ``distributed.fault_tolerance.run_with_retries`` — a
    raising chunk is retried with exponential backoff from the frontier;
    ``HeartbeatMonitor`` turns per-chunk wall times into straggler reports.
    """
    import hashlib
    import time as _time

    from ..distributed import fault_tolerance as FT

    n = len(envs)
    chunks = [(start, min(start + chunk_points, n))
              for start in range(0, n, chunk_points)]
    plan = [(t, start, end) for t in techniques for start, end in chunks]
    sig_spec = point_spec(techniques[0], 1)
    sig = hashlib.sha256(repr((
        tuple(labels), tuple(techniques), chunk_points,
        sig_spec.objective, sig_spec.hours, sig_spec.routed,
        sig_spec.failover, sig_spec.guard, sig_spec.seed,
        sig_spec.workload, faults is not None, _trace_stacked(faults),
    )).encode()).hexdigest()[:16]
    journal = FL.SweepJournal(resume_dir, sig)
    monitor = FT.HeartbeatMonitor(num_workers=len(plan),
                                  window=max(len(plan), 1))

    restored_steps = [s for s in journal.completed_steps() if s < len(plan)]
    computed_steps: List[int] = []
    pending: Dict[int, Dict[str, Any]] = {}

    stacked = _trace_stacked(faults)

    def step_fn(step):
        FL.check_kill_switch()
        t, start, end = plan[step]
        with obs.span("sweep.chunk", step=step):
            pspec = point_spec(t, end - start)
            with obs.span("sweep.stack"):
                env_b = E.stack_envs(envs[start:end])
                chunk_faults = (jax.tree_util.tree_map(
                    lambda x: x[start:end], faults) if stacked else faults)
            t0 = _time.perf_counter()
            res = FL.call_with_timeout(
                lambda: _run_batched(pspec, env_b, None, shard, chunk_faults),
                point_timeout_s, label=f"chunk {step} ({t}[{start}:{end}])")
            monitor.record(step, _time.perf_counter() - t0)
            pending[step] = {
                "totals": {k: np.asarray(v)
                           for k, v in res["totals"].items()},
                "per_epoch": {k: np.asarray(v)
                              for k, v in res["per_epoch"].items()}}
        computed_steps.append(step)

    def save_fn(step_after):
        step = step_after - 1
        if step in pending:  # journal the chunk that just completed
            t, start, end = plan[step]
            journal.mark(step, pending.pop(step),
                         meta={"technique": t, "start": start, "end": end})

    events = FT.run_with_retries(
        step_fn, total_steps=len(plan), save_every=1, save_fn=save_fn,
        restore_fn=journal.next_step,
        policy=FT.FailurePolicy(max_restarts=max_retries, elastic=False),
        retry_on=(Exception,), backoff_s=backoff_s)

    results: Dict[str, Dict[str, Any]] = {}
    for step, (t, start, end) in enumerate(plan):
        part = journal.load(step)
        node = results.setdefault(t, {"totals": {}, "per_epoch": {}})
        for sect in ("totals", "per_epoch"):
            for k, v in part[sect].items():
                node[sect].setdefault(k, []).append(np.asarray(v))
    for t in results:
        for sect in ("totals", "per_epoch"):
            results[t][sect] = {k: np.concatenate(v)
                                for k, v in results[t][sect].items()}
    meta = {"journal": resume_dir, "signature": sig, "chunks": len(plan),
            "chunk_points": chunk_points, "restored": len(restored_steps),
            "computed": len(computed_steps), "retries": events["restarts"],
            "stragglers": [{"chunk": s.worker, "ratio": float(s.ratio)}
                           for s in monitor.stragglers()]}
    return results, meta
