"""Compile- and dispatch-span accounting for the compiled engines.

Before this module, compile time was silently folded into wall time and the
spec-keyed compile cache in ``repro.core.experiment`` was opaque — a perf
number could mean "fast engine" or "you hit the cache" and nothing could
tell them apart. Three pieces:

- ``span(name)`` — a ``with``-able wall-clock span (``.seconds`` after
  exit). The benchmark harness' ``Timer`` is this span under another name,
  so bench rows and engine telemetry share one timing code path
  (``note_bench`` records the emitted rows here too).
- engine-cache accounting — ``repro.core.experiment._compiled`` reports
  every lookup (``engine_lookup``), wraps every artifact's dispatch
  (``instrument_dispatch``: per-call wall time, first-dispatch time ≈
  trace+XLA-compile+run, and the trace-time tap pinning), and reports
  evictions (``note_eviction``, fired by ``register_technique(overwrite=
  True)`` / ``unregister_technique``). ``cache_stats()`` is the queryable
  view; a test asserts the taps-off path adds zero compiles.
- ``profile(label)`` — a ``jax.profiler`` trace dropped under
  ``runs/profiles/<label>`` for kernel-level work (the ROADMAP's Pallas
  item); raises where the profiler cannot start, so a run that asked for a
  trace never finishes without one.

Dispatch wrappers block on their outputs (``jax.block_until_ready``) so the
recorded span covers the actual computation and every live tap callback has
landed in its buffer before the engine returns — numerics are unaffected.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

from . import tap as _tap

SPAN_CAPACITY = 4096


@dataclasses.dataclass
class Span:
    """One timed region. ``seconds`` is set when the region exits."""
    name: str
    seconds: float = 0.0
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _t0: float = dataclasses.field(default=0.0, repr=False)

    def __enter__(self) -> "Span":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        _SPANS.append(self)


_SPANS: collections.deque = collections.deque(maxlen=SPAN_CAPACITY)


def span(name: str, **meta) -> Span:
    """``with obs.span("phase") as s: ...`` — then read ``s.seconds``."""
    return Span(name=name, meta=meta)


def spans(name: Optional[str] = None) -> List[Span]:
    out = list(_SPANS)
    return out if name is None else [s for s in out if s.name == name]


def note_bench(name: str, seconds: float, derived: str = "") -> None:
    """Record one benchmark row as a span (the bench harness' ``emit``
    routes through here, so ``BENCH_*.json`` rows and engine spans are the
    same measurements)."""
    _SPANS.append(Span(name=name, seconds=seconds,
                       meta={"kind": "bench", "derived": derived}))


# ---------------------------------------------------------------------------
# engine compile-cache accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineStat:
    """Per compile-key counters for one cached engine artifact."""
    hits: int = 0
    misses: int = 0
    build_s: float = 0.0           # python-side jit/vmap/shard_map wrap time
    first_dispatch_s: float = 0.0  # ≈ trace + XLA compile + first run
    dispatches: int = 0
    dispatch_s: float = 0.0        # total wall across all dispatches
    last_dispatch_s: float = 0.0
    evicted: bool = False

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["dispatch_s"] = round(d["dispatch_s"], 6)
        for k in ("build_s", "first_dispatch_s", "last_dispatch_s"):
            d[k] = round(d[k], 6)
        return d


_known: set = set()                      # keys with a live cached artifact
_engine: Dict[str, EngineStat] = {}      # resettable accounting, by key string
_evictions: int = 0


def engine_key_str(key: tuple) -> str:
    """Compact, human-scannable form of an engine compile key:
    ``kind:technique:objective:h<hours>:cfg=<...>:routed=<...>:
    wl=<workload>:faults=<policy|off[/point]>:guard=<on|off>:taps=<...>``."""
    (kind, technique, objective, hours, cfg, routed, failover, guard,
     workload, faulted, fault_axis, taps) = key
    cfg_s = "default" if cfg is None else type(cfg).__name__
    taps_s = ",".join(sorted(taps)) if taps else "off"
    faults_s = failover if faulted else "off"
    if faulted and fault_axis:
        faults_s += "/point"  # one trace per env row
    return (f"{kind}:{technique}:{objective}:h{hours}:cfg={cfg_s}:"
            f"routed={bool(routed)}:wl={workload}:faults={faults_s}:"
            f"guard={'on' if guard else 'off'}:taps={taps_s}")


def _stat(key: tuple) -> EngineStat:
    ks = engine_key_str(key)
    st = _engine.get(ks)
    if st is None:
        st = _engine[ks] = EngineStat()
    return st


def engine_lookup(key: tuple) -> bool:
    """Count one compile-cache lookup; returns True on a hit."""
    hit = key in _known
    st = _stat(key)
    if hit:
        st.hits += 1
    else:
        st.misses += 1
        _known.add(key)
    return hit


def note_build(key: tuple, seconds: float) -> None:
    _stat(key).build_s += seconds


def note_eviction() -> None:
    """The compile caches were cleared (technique re-registered/removed):
    every known artifact is gone; the next lookups are misses again."""
    global _evictions
    if _known:
        _evictions += len(_known)
        _known.clear()
    for st in _engine.values():
        st.evicted = True


def instrument_dispatch(key: tuple, fn: Callable) -> Callable:
    """Wrap a compiled engine so every call is a timed span, the first call
    is recorded as the compile span, and tracing happens under exactly the
    key's tap set (see ``tap.tracing``)."""
    import jax
    taps = key[-1]

    def dispatch(*args, **kwargs):
        st = _stat(key)
        t0 = time.perf_counter()
        with _tap.tracing(taps):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        st.dispatches += 1
        st.dispatch_s += dt
        st.last_dispatch_s = dt
        if st.dispatches == 1:
            st.first_dispatch_s = dt
        return out

    dispatch.__wrapped__ = fn
    return dispatch


def cache_stats() -> Dict[str, Any]:
    """The queryable compile-cache view: global hit/miss/eviction totals
    plus per-engine-key spans (``{"engines": {key: EngineStat dict}}``)."""
    return {
        "hits": sum(s.hits for s in _engine.values()),
        "misses": sum(s.misses for s in _engine.values()),
        "evictions": _evictions,
        "live_keys": len(_known),
        "engines": {k: s.as_dict() for k, s in _engine.items()},
    }


def engine_stat(key: tuple) -> Optional[Dict[str, Any]]:
    st = _engine.get(engine_key_str(key))
    return None if st is None else st.as_dict()


def reset_stats() -> None:
    """Zero the accounting (counters/spans). Does NOT touch the live
    compiled artifacts: keys still cached keep hitting, so post-reset
    numbers stay truthful about what actually compiled."""
    global _evictions
    _engine.clear()
    _SPANS.clear()
    _evictions = 0


# ---------------------------------------------------------------------------
# profiler traces
# ---------------------------------------------------------------------------

@contextmanager
def profile(label: str = "trace", logdir: str = "runs/profiles"):
    """Drop a ``jax.profiler`` trace for the block under
    ``<logdir>/<label>`` (viewable in TensorBoard/Perfetto; the tool for
    the queued Pallas-kernel work). Yields the trace directory; an error
    starting the profiler propagates."""
    import os

    import jax
    path = os.path.join(logdir, label)
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()
