"""Request-scoped spans and compile/dispatch accounting for the engines.

Before this module, compile time was silently folded into wall time and the
spec-keyed compile cache in ``repro.core.experiment`` was opaque — a perf
number could mean "fast engine" or "you hit the cache" and nothing could
tell them apart. Four pieces:

- ``span(name)`` — a ``with``-able wall-clock span (``start``/``end`` on
  ``time.perf_counter``, ``.seconds`` after exit). Spans nest into a tree:
  each carries its own ``id``, its ``parent``'s id and the ``request`` id
  of the public call it ran under. The current span lives in a
  ``contextvars.ContextVar``, so a worker thread started under
  ``contextvars.copy_context()`` keeps its spans under their request. Every
  span also enters ``jax.profiler.TraceAnnotation(name)``, so it lands on
  the host plane of any ``jax.profiler`` trace taken around it, beside the
  device ops. The benchmark harness' ``Timer`` is this span under another
  name.
- requests — ``request(name)`` opens the root span of one public call
  (``api.run``, ``api.sweep``); inside an open request it opens nothing, so
  a nested public call stays part of its caller's request. ``count(name,
  n)`` adds to the current request's counters (``rows``, ``fleet_hours``,
  ``dispatches``, ``fetches``, ``stacked_rows``). ``requests(last=n)``
  returns the last ``n`` finished requests with their counters and the
  self time of each child span name.
- engine-cache accounting — ``repro.core.experiment._compiled`` reports
  every lookup (``engine_lookup``) under an opaque key it labels with
  ``str(key)``, wraps every artifact's dispatch
  (``instrument_dispatch``: an ``engine.dispatch`` span per call, the
  first-dispatch time ≈ trace+XLA-compile+run, and the trace-time tap
  pinning), and reports evictions (``note_eviction``, fired by
  ``register_technique(overwrite=True)`` / ``unregister_technique``).
  ``cache_stats()`` is the queryable view; a test asserts the taps-off path
  adds zero compiles.
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
import contextlib
import contextvars
import dataclasses
import itertools
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Hashable, List, Optional

from jax.profiler import TraceAnnotation

from . import tap as _tap

SPAN_CAPACITY = 4096
REQUEST_CAPACITY = 1024

_ids = itertools.count(1)
# a request's spans may close on a worker thread (``call_with_timeout``)
_LOCK = threading.Lock()
_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_obs_span", default=None)


@dataclasses.dataclass
class Request:
    """One public call: its root span's name and interval, its counters,
    the self time of each child span name (``spans``) and of the root
    itself (``self_s``)."""
    id: int
    name: str
    start: float = 0.0
    end: float = 0.0
    self_s: float = 0.0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Span:
    """One timed region of the span tree. ``start``/``end`` are
    ``time.perf_counter`` readings; ``parent`` and ``request`` are ids
    (``None`` outside any span / request)."""
    __slots__ = ("name", "meta", "id", "parent", "request", "start", "end",
                 "_root", "_up", "_req", "_child_s", "_token", "_annotation")

    def __init__(self, name: str, meta: Optional[Dict[str, Any]] = None,
                 _root: bool = False):
        self.name = name
        self.meta = {} if meta is None else meta
        self.id = 0
        self.parent: Optional[int] = None
        self.request: Optional[int] = None
        self.start = self.end = 0.0
        self._root = _root
        self._up: Optional[Span] = None
        self._req: Optional[Request] = None
        self._child_s = 0.0

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, seconds={self.seconds:.6f})")

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        up = _CURRENT.get()
        self.id = next(_ids)
        if up is not None:
            self._up = up
            self.parent = up.id
            self._req = up._req
        if self._root:
            self._req = Request(id=self.id, name=self.name)
        if self._req is not None:
            self.request = self._req.id
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._token = _CURRENT.set(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        _CURRENT.reset(self._token)
        self._annotation.__exit__(*exc)
        self._token = self._annotation = None
        seconds = self.end - self.start
        req = self._req
        with _LOCK:
            if self._up is not None:
                self._up._child_s += seconds
            if req is not None:
                own = seconds - self._child_s
                if self._root:
                    req.start, req.end, req.self_s = self.start, self.end, own
                    _REQUESTS.append(req)
                else:
                    req.spans[self.name] = req.spans.get(self.name, 0.0) + own
        _SPANS.append(self)


_SPANS: collections.deque = collections.deque(maxlen=SPAN_CAPACITY)
_REQUESTS: collections.deque = collections.deque(maxlen=REQUEST_CAPACITY)


def span(name: str, **meta) -> Span:
    """``with obs.span("phase") as s: ...`` — then read ``s.seconds``."""
    return Span(name, meta)


def request(name: str, **meta):
    """The root span of one public call (``with obs.request("api.run")``);
    inside an open request it opens nothing and yields the current span."""
    cur = _CURRENT.get()
    if cur is not None and cur._req is not None:
        return contextlib.nullcontext(cur)
    return Span(name, meta, _root=True)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the current request (no-op outside
    any request)."""
    cur = _CURRENT.get()
    if cur is not None and cur._req is not None:
        c = cur._req.counters
        with _LOCK:
            c[name] = c.get(name, 0) + n


def spans(name: Optional[str] = None) -> List[Span]:
    out = list(_SPANS)
    return out if name is None else [s for s in out if s.name == name]


def requests(last: Optional[int] = None) -> List[Request]:
    """The finished requests, oldest first; ``last=n`` keeps the last n."""
    out = list(_REQUESTS)
    if last is None:
        return out
    return out[len(out) - last:] if last > 0 else []


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


def _stat(key: Hashable) -> EngineStat:
    ks = str(key)
    st = _engine.get(ks)
    if st is None:
        st = _engine[ks] = EngineStat()
    return st


def engine_lookup(key: Hashable) -> bool:
    """Count one compile-cache lookup; returns True on a hit."""
    hit = key in _known
    st = _stat(key)
    if hit:
        st.hits += 1
    else:
        st.misses += 1
        _known.add(key)
    return hit


def note_build(key: Hashable, seconds: float) -> None:
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


def instrument_dispatch(key: Hashable, taps: frozenset,
                        fn: Callable) -> Callable:
    """Wrap a compiled engine so every call is a timed span, the first call
    is recorded as the compile span, and tracing happens under exactly the
    tap set ``taps`` (see ``tap.tracing``)."""
    import jax

    def dispatch(*args, **kwargs):
        st = _stat(key)
        with span("engine.dispatch") as s, _tap.tracing(taps):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        count("dispatches")
        dt = s.seconds
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


def engine_stat(key: Hashable) -> Optional[Dict[str, Any]]:
    st = _engine.get(str(key))
    return None if st is None else st.as_dict()


def reset_stats() -> None:
    """Zero the accounting (counters/spans/requests). Does NOT touch the
    live compiled artifacts: keys still cached keep hitting, so post-reset
    numbers stay truthful about what actually compiled."""
    global _evictions
    _engine.clear()
    _SPANS.clear()
    _REQUESTS.clear()
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
