#!/usr/bin/env python3
"""Drive the scheduler's main path once on a TPU, at the 16-DC fleet size.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded batched engine
                                     # against the one-device engine, only

One process, the public entry points (``repro.core.run`` / ``sweep``), the
default solver configs, a 16-DC fleet routed per source (S = 16), 24 hourly
epochs and the ``cost_sla`` objective:

- scan    all six techniques on one day of each workload (aibench, llm);
- sweep   a 4x4 wan_degradation x origin_shift grid, one batched compile per
          technique, for fd and gt-drl;
- faults  one day under a dc_crash, a wan_partition and a brownout, with the
          spill_nearest failover policy;
- month   a 30-day fd month on the month engine;
- taps    one tapped day whose per-hour series sums to the untapped totals.

The chip runs every phase twice: the first pass compiles, the second is the
warm dispatch, and both must give identical totals. fd and nash are
deterministic, so their phases run again on the host CPU backend, whose
totals the chip's must match within ``TOLERANCE``. A second CPU pass on
inputs moved by one ulp prints how far the CPU moves its own totals: the
tolerances are set from that spread. Every total must be finite, and every
``violation`` total at most ``VIOLATION_RTOL`` of the demand the run placed.

Timings printed are from this one run: set-up numbers, not a benchmark.
With no TPU the script exits non-zero before any phase and prints no result.
Its last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import compile_cache, faults, obs  # noqa: E402
from repro import scenarios as S  # noqa: E402
from repro.core import TECHNIQUES, ExperimentSpec, run, sweep  # noqa: E402
from repro.core import experiment as X  # noqa: E402
from repro.dcsim import env as E  # noqa: E402

SEED = 0
NUM_DCS = 16
HOURS = 24
DAYS = 30
OBJECTIVE = "cost_sla"
WORKLOADS = ("aibench", "llm")
DETERMINISTIC = ("fd", "nash")
SWEEP_TECHNIQUES = ("fd", "gt-drl")
GRID = {"wan_degradation": (1.0, 2.0, 3.0, 4.0),
        "origin_shift": tuple({"weight": w, "toward": (0,)}
                              for w in (0.0, 0.3, 0.6, 0.9))}
SWEEP_BASE = (S.Scenario("sla_tighten", {"tighten": 0.7}),)
FOUR_CHIP_ROWS = 18          # not a multiple of 4: the sharded engine pads
SHARD_TECHNIQUES = ("fd", "ga")  # deterministic, and one that draws per-row keys

# Chip vs host CPU. fd moves load by argmax over near-equal marginal costs,
# and nash stops in whichever local equilibrium its descent reaches, so one
# ulp of difference in any input or op can pick another plan of near-equal
# cost. On XLA:CPU alone, 1-ulp noise on the env inputs (four seeds) moves
# these totals by up to 4.5e-3 (USD, kg) and 9.4e-4 of demand (tasks/h moved
# by failover): the tolerances sit about 2x above that. Every run prints the
# same measurement for one seed beside the chip's differences.
PLAN_RTOL = 1e-2   # USD and kg totals, relative to the CPU total
RATE_RTOL = 2e-3   # tasks/h totals, relative to the demand the run placed
TOLERANCE = {
    "carbon_kg": PLAN_RTOL, "cost_usd": PLAN_RTOL,
    "sla_miss_cost_usd": PLAN_RTOL, "degraded_sla_cost_usd": PLAN_RTOL,
    "violation": RATE_RTOL, "unserved_demand": RATE_RTOL,
    "failover_moved": RATE_RTOL,
    "demand": 0.0, "fallback_hours": 0.0,   # inputs and counts: exact
}
RATE_KEYS = ("violation", "unserved_demand", "failover_moved", "demand")
# ``violation`` is |placed - demand| plus capacity excess, in tasks/h. At
# D=16 a day's demand is ~4e10 tasks, so float32 rounding alone leaves
# ~1e-7 of it; a shed or overloaded share is orders of magnitude larger.
VIOLATION_RTOL = 1e-5
# a 24-term float32 sum against its float64 re-sum
TAP_RTOL = 1e-5
# env inputs the 1-ulp noise touches: every float the solvers price, but
# not demand (``car``), the origin simplex or availability
NOISE_FIELDS = ("eprice", "er", "it_dyn", "it_idle", "rp", "tsupply", "eff",
                "peak_price", "alpha", "sizes")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _spec(technique: str, workload: str, **kw) -> ExperimentSpec:
    return ExperimentSpec(technique=technique, objective=OBJECTIVE,
                          routed=True, hours=HOURS, seed=SEED,
                          workload=workload, **kw)


# ---------------------------------------------------------------------------
# phases: each returns {label: totals}
# ---------------------------------------------------------------------------

def _demand(*envs) -> float:
    """Tasks the runs over ``envs`` had to place (the violation's scale)."""
    return float(sum(np.asarray(e.car[:, :HOURS], float).sum() for e in envs))


def scan_phase(envs, techniques):
    return {f"scan/{wl}/{t}": {**run(_spec(t, wl), env)["totals"],
                               "demand": _demand(env)}
            for wl, env in envs.items() for t in techniques}


def sweep_phase(env, workload, techniques):
    res = sweep(_spec(techniques[0], workload, engine="batched"), GRID,
                base_env=env, techniques=techniques,
                base_scenarios=SWEEP_BASE)
    n = len(GRID["wan_degradation"]) * len(GRID["origin_shift"])
    if len(res["labels"]) != n:
        raise SmokeFailure(f"sweep: {len(res['labels'])} points, want {n}")
    return {f"sweep/{workload}/{t}": {**res["results"][t]["totals"],
                                      "demand": _demand(env)}
            for t in techniques}


def faults_phase(env, workload, technique="fd"):
    trace = faults.compose(
        faults.dc_crash(env, dc=3, start=8, duration=6),
        faults.wan_partition(env, a=0, b=9),
        faults.brownout(env, dc=11, start=12, duration=8, severity=0.5))
    res = run(_spec(technique, workload, failover="spill_nearest"), env,
              faults=trace)
    tot = res["totals"]
    if not tot["failover_moved"] > 0.0:
        raise SmokeFailure("faults: the crash moved no demand off-plan")
    return {f"faults/{workload}/{technique}": {**tot, "demand": _demand(env)}}


def month_phase(env, workload):
    days = [e for _, e in S.build_month(env, days=DAYS, seed=SEED)]
    res = run(_spec("fd", workload, engine="month", days=DAYS), days)
    if res["days"] != DAYS:
        raise SmokeFailure(f"month: {res['days']} days, want {DAYS}")
    peak = np.asarray(res["final_peak_w"])
    if peak.shape != (NUM_DCS,) or not np.all(np.isfinite(peak) & (peak >= 0)):
        raise SmokeFailure(f"month: final peak state {peak!r}")
    return {f"month/{workload}/fd": {**res["totals"], "demand": _demand(*days)}}


def taps_phase(env, workload, technique="fd"):
    spec = _spec(technique, workload)
    plain = run(spec, env)["totals"]
    with obs.capture() as buf:
        tapped = run(spec.replace(taps=("engine/hour",)), env)["totals"]
    for k in ("carbon_kg", "cost_usd", "sla_miss_cost_usd"):
        series = buf.series("engine/hour", k)
        if series.shape != (HOURS,):
            raise SmokeFailure(f"taps: {k} series has shape {series.shape}")
        diff = _rel(series.astype(float).sum(), plain[k])
        if diff > TAP_RTOL:
            raise SmokeFailure(f"taps: hourly {k} sums to {series.sum()!r}, "
                               f"untapped total {plain[k]!r} (diff {diff:.3g})")
    return {f"taps/{workload}/{technique}": {**tapped,
                                            "demand": _demand(env)}}


def one_pass(envs, techniques, tag: str):
    """Every phase once, restricted to ``techniques``; prints phase times."""
    sweep_ts = tuple(t for t in SWEEP_TECHNIQUES if t in techniques)
    phases = [(f"scan {t}", lambda t=t: scan_phase(envs, (t,)))
              for t in techniques]
    phases += [
        ("sweep", lambda: sweep_phase(envs["llm"], "llm", sweep_ts)),
        ("faults", lambda: faults_phase(envs["aibench"], "aibench")),
        ("month", lambda: month_phase(envs["llm"], "llm")),
        ("taps", lambda: taps_phase(envs["aibench"], "aibench")),
    ]
    out = {}
    for name, phase in phases:
        t0 = time.perf_counter()
        out.update(phase())
        print(f"{tag} {name}: {time.perf_counter() - t0:.1f} s", flush=True)
    return out


def build_envs():
    return {wl: E.build_env(NUM_DCS, seed=SEED, workload=wl)
            for wl in WORKLOADS}


def ulp_noise(env, seed: int):
    """``env`` with each of ``NOISE_FIELDS`` scaled by 1 + u * 2**-23,
    u uniform in [-1, 1] per element."""
    rng = np.random.default_rng(seed)
    return env._replace(**{
        f: getattr(env, f) * jnp.asarray(
            1.0 + 2.0 ** -23 * rng.uniform(-1.0, 1.0, np.shape(getattr(env, f))),
            jnp.float32)
        for f in NOISE_FIELDS})


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _rel(a, b) -> float:
    a, b = np.asarray(a, float), np.asarray(b, float)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def _diff(key: str, a, b, demand: float) -> float:
    """|a - b| on the scale ``TOLERANCE[key]`` is stated in."""
    if key in RATE_KEYS:
        a, b = np.asarray(a, float), np.asarray(b, float)
        return float(np.max(np.abs(a - b))) / demand
    return _rel(a, b)


def check_finite(results):
    for label, tot in results.items():
        for k, v in tot.items():
            if not np.all(np.isfinite(np.asarray(v, float))):
                raise SmokeFailure(f"{label}: {k} is not finite: {v!r}")
        viol = float(np.max(np.abs(np.asarray(tot["violation"], float))))
        if viol > VIOLATION_RTOL * tot["demand"]:
            raise SmokeFailure(f"{label}: violation {viol!r} is more than "
                               f"{VIOLATION_RTOL} of demand {tot['demand']!r}")


def check_same(a, b, what: str):
    for label in a:
        for k in a[label]:
            if not np.array_equal(np.asarray(a[label][k]),
                                  np.asarray(b[label][k])):
                raise SmokeFailure(f"{what}: {label} {k} differs: "
                                   f"{a[label][k]!r} vs {b[label][k]!r}")


def worst_diffs(a, ref):
    """Largest ``_diff`` per total key over ``ref``'s labels: {key: (d, label)}."""
    worst = {}
    for label, tot in ref.items():
        for k, v in tot.items():
            d = _diff(k, a[label][k], v, tot["demand"])
            if d >= worst.get(k, (-1.0, ""))[0]:
                worst[k] = (d, label)
    return worst


def check_close(a, ref, what: str, spread=None):
    """Every total of ``a`` within ``TOLERANCE`` of ``ref``'s; prints the
    worst difference per key, beside ``spread``'s where given."""
    worst = worst_diffs(a, ref)
    print(f"{what}: worst difference per total (USD/kg relative; tasks/h "
          f"over demand){'; CPU under 1-ulp input noise' if spread else ''}")
    for k, (d, label) in sorted(worst.items()):
        side = f"  noise {spread[k][0]:.3e}" if spread else ""
        print(f"  {k:24s} {d:.3e}{side}  tol {TOLERANCE[k]:.0e}  ({label})")
    bad = {k: v for k, v in worst.items() if v[0] > TOLERANCE[k]}
    if bad:
        raise SmokeFailure(f"{what}: beyond tolerance: {bad}")


class CompileCounter:
    """Counts XLA compiles and persistent-cache hits through jax.monitoring."""

    def __init__(self):
        self.compile_s = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s.append(secs)

    def report(self, cache_dir: str):
        s = np.asarray(self.compile_s)
        small = s[s < 1.0]
        print(f"compile cache {cache_dir}: {self.hits} hits, "
              f"{self.misses} misses; {s.size} backend compiles, "
              f"{s.sum():.1f} s; {small.size} of them under 1 s, "
              f"{small.sum():.1f} s (one smoke run, not a benchmark)")


def print_engine_times():
    print("engine  first-dispatch s (compile + run)  warm-dispatch s  "
          "(one smoke run, not a benchmark)")
    for key, st in sorted(obs.cache_stats()["engines"].items()):
        warm = st["last_dispatch_s"] if st["dispatches"] > 1 else float("nan")
        print(f"  {key}  {st['first_dispatch_s']:.3f}  {warm:.3f}")


# ---------------------------------------------------------------------------
# the two entry paths
# ---------------------------------------------------------------------------

def one_chip():
    envs = build_envs()
    first = one_pass(envs, TECHNIQUES, "chip pass 1 (compile + run)")
    check_finite(first)
    second = one_pass(envs, TECHNIQUES, "chip pass 2 (warm)")
    check_same(first, second, "chip pass 1 vs pass 2")
    print_engine_times()

    with jax.default_device(jax.devices("cpu")[0]):
        cpu_envs = build_envs()
        ref = one_pass(cpu_envs, DETERMINISTIC, "host CPU")
        noisy = one_pass({wl: ulp_noise(e, i)
                          for i, (wl, e) in enumerate(cpu_envs.items())},
                         DETERMINISTIC, "host CPU, 1-ulp input noise")
    check_finite(ref)
    check_close(first, ref, "chip vs host CPU",
                spread=worst_diffs(noisy, ref))
    print(f"{len(first)} runs checked, {len(ref)} against the host CPU")


def four_chips():
    """The sharded batched engine over every chip vs the one-device engine."""
    if jax.device_count() != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, JAX found "
                           f"{jax.device_count()}")
    base = E.build_env(NUM_DCS, seed=SEED, workload="llm")
    envs = [env for _, env in S.build_grid(
        base, {"arrival_resample": tuple(
            {"seed": s, "std": 0.2} for s in range(FOUR_CHIP_ROWS))})[1]]
    demand = min(_demand(e) for e in envs)  # per row, the tolerances' scale
    for t in SHARD_TECHNIQUES:
        spec = _spec(t, "llm", engine="batched")
        plain = {f"batched/{t}": {**run(spec, envs)["totals"],
                                  "demand": demand}}
        sharded = {f"batched/{t}": {**run(spec, envs, shard=True)["totals"],
                                    "demand": demand}}
        check_finite(plain)
        check_finite(sharded)
        exact = all(np.array_equal(sharded[f"batched/{t}"][k], v)
                    for k, v in plain[f"batched/{t}"].items())
        print(f"sharded vs one device, {t}: totals "
              f"{'bit-identical' if exact else 'differ'}")
        check_close(sharded, plain, f"sharded vs one device, {t}")
    check_spread(_spec(SHARD_TECHNIQUES[0], "llm", engine="batched"), envs)
    print_engine_times()


def check_spread(spec, envs):
    """The sharded engine's outputs live on every chip, a quarter each."""
    n = len(envs)
    padded = -(-n // jax.device_count()) * jax.device_count()
    seeds = list(range(n)) + [n - 1] * (padded - n)
    keys = jnp.stack([jax.random.split(jax.random.PRNGKey(s))[1]
                      for s in seeds])
    _, state0 = X._day_inputs(envs[0], spec.technique, spec.objective, 0,
                              spec.pretrain, spec.cfg, None, spec.routed)
    env_b = E.pad_env_batch(E.stack_envs(envs), padded)
    _, _, ms = X.compiled_engine(spec, shard=True)(
        env_b, keys, jnp.zeros((NUM_DCS,)), state0)
    shards = ms["carbon_kg"].addressable_shards
    devices = {s.device for s in shards}
    rows = sorted(s.data.shape[0] for s in shards)
    if devices != set(jax.devices()) or rows != [padded // 4] * 4:
        raise SmokeFailure(f"sharded rows not spread over 4 chips: "
                           f"{len(devices)} devices, rows {rows}")
    print(f"sharded engine: {padded} rows ({n} + {padded - n} padding) "
          f"spread {rows} over {len(devices)} chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # the CPU reference needs JAX's CPU backend beside the TPU one
    plats = jax.config.jax_platforms
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", plats + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices()[0] is {dev.platform!r});"
              " nothing was run", file=sys.stderr)
        return 1

    cache_dir = compile_cache.enable()
    counter = CompileCounter()
    print(f"device: {dev.device_kind} x {jax.device_count()}, jax "
          f"{jax.__version__}, fleet D={NUM_DCS} routed, {HOURS} h, "
          f"{OBJECTIVE}", flush=True)
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)()
    counter.report(cache_dir)
    print(f"wall {time.perf_counter() - t0:.1f} s (one smoke run)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
