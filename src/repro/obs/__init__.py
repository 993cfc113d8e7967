"""``repro.obs`` — telemetry for the compiled evaluation engines.

Three pieces. Taps cost nothing when disabled; spans are always on and
change no output.

- **Taps** (``repro.obs.tap``): named emission points inside jitted scan
  bodies (``obs.tap(name, value)``). Disabled taps compile to nothing —
  the taps-off engines are bit-for-bit the pre-obs artifacts; enabled taps
  ship per-epoch solver diagnostics and per-hour physical signals to a
  host ring buffer via ``jax.debug.callback``. Built-in tap points:

  ========================  ===================================================
  name                      payload (per event)
  ========================  ===================================================
  ``engine/hour``           tau, carbon_kg, cost_usd, sla_miss_cost_usd,
                            latency_ms, grid_power_w — one event per epoch
  ``game/nash_residual``    tau, residual — the Nash-gap probe (computed
                            only when tapped)
  ``gt_drl/round``          value, best, delta — per best-response round
  ``gt_drl/ppo``            player, actor_loss, mean_reward — per PPO
                            improve call
  ``gt_drl/starts``         residual, replayed — per PPO start draw: the
                            gamma variates left after the first trial,
                            and those that reached the exact loop
  ========================  ===================================================

- **Spans** (``repro.obs.spans``): always on, about 2 µs each. Every
  public ``run``/``sweep`` call is one request (``api.run``,
  ``api.sweep``) whose child spans cover its host path (``sweep.grid``,
  ``scenario.<transform>``, ``sweep.stack``, ``engine.inputs``,
  ``engine.dispatch``, ``engine.fetch``, ``engine.format``) and whose
  counters say what it did (``rows``, ``fleet_hours``, ``dispatches``,
  ``fetches``, ``stacked_rows``): ``obs.requests(last=n)``. Each span is
  also a ``jax.profiler.TraceAnnotation``, so it shows on the host plane
  of an ``obs.profile(label)`` trace. Compile-cache accounting for the
  spec-keyed engine cache — hits/misses/evictions, build and
  first-dispatch (≈ compile) wall time, per-dispatch time — is queryable
  via ``obs.cache_stats()``; ``obs.span(name)`` times ad-hoc regions (the
  benchmark harness' timer).

- **Records** (``repro.obs.records`` / ``repro.obs.report``): ``run(spec,
  envs, record=True)`` (also ``sweep``/``compare_techniques``) appends a
  spec-keyed JSONL ``RunRecord`` (git SHA, jax/device info, totals,
  convergence curves, timing spans) under ``runs/``; ``python -m
  repro.obs`` renders the committed scoreboard from them.

Typical use::

    from repro import obs
    from repro.core import ExperimentSpec, run

    with obs.taps("engine/hour"), obs.capture() as buf:
        run(ExperimentSpec(technique="fd"), env, record=True)
    buf.series("engine/hour", "carbon_kg")   # (24,) convergence curve
    obs.cache_stats()                        # compile/dispatch accounting
"""
from . import records, report as report_mod, spans, tap as tap_mod
from .records import (load_records, make_record, run_info, spec_fields,
                      spec_key, write_record)
from .report import report, sparkline
from .spans import (Request, Span, cache_stats, count, engine_key_str,
                    engine_stat, profile, request, requests, reset_stats,
                    span)
from .spans import spans as all_spans
from .tap import (KNOWN_TAPS, TapBuffer, TapEvent, active_taps, capture,
                  clear_events, disable_taps, enable_taps, enabled, events,
                  ring, tap, taps, tracing)

__all__ = [
    "tap", "taps", "capture", "events", "ring", "clear_events",
    "enable_taps", "disable_taps", "enabled", "active_taps", "tracing",
    "KNOWN_TAPS", "TapBuffer", "TapEvent",
    "span", "all_spans", "Span", "request", "requests", "Request", "count",
    "cache_stats", "engine_stat", "engine_key_str",
    "reset_stats", "profile",
    "make_record", "write_record", "load_records", "run_info",
    "spec_fields", "spec_key",
    "report", "sparkline",
]
