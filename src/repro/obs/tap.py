"""In-scan metric streams: ``tap(name, value)`` out of jitted code.

A *tap* is a named emission point inside traced/compiled code (engine scan
bodies, GT-DRL best-response rounds). Whether a tap is live is decided at
**trace time** against the active tap set, so

- a disabled tap compiles to *nothing* — ``tap`` returns before touching
  jax, the lowered program is byte-identical to one with no tap call, and
  the taps-off engines stay pinned bit-for-bit against their pre-obs
  artifacts;
- an enabled tap lowers to a ``jax.debug.callback`` that ships the value
  (any pytree of arrays) to a host-side ring buffer at run time. Callbacks
  do not change the math: XLA treats them as opaque effects, and the
  engine parity tests assert taps-on == taps-off exactly.

Because liveness is a compile-time property, every compiled-engine cache in
``repro.core.experiment`` keys on the active tap set: enabling taps
compiles a *second* artifact instead of mutating the first, and disabling
them again is a cache hit on the original.

Expensive diagnostics (the Nash-residual probe) use the ``thunk=`` form so
the value is only *computed* when the tap is live::

    obs.tap("game/nash_residual", thunk=lambda: nash_residual(...))

Enablement is either ambient (``with obs.taps("engine/*"): ...``) or
per-spec (``ExperimentSpec(taps=("engine/hour",))``); patterns are exact
names, ``prefix/*`` wildcards, or ``"*"`` for everything.
"""
from __future__ import annotations

import collections
import functools
import threading
from contextlib import contextmanager
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import numpy as np

DEFAULT_CAPACITY = 65536

#: The tap registry: every name ever passed to ``tap(...)`` must be
#: declared here, and ``repro.lint``'s taps checker enforces it statically
#: (a typo'd name would otherwise compile to a tap that never fires).
#: Keep sorted; keep literal — the lint pass reads this tuple from the AST.
KNOWN_TAPS = (
    "engine/hour",          # experiment engines: per-hour scan-body metrics
    "game/nash_residual",   # game loop: best-reply residual probe
    "gt_drl/ppo",           # GT-DRL: per-player PPO actor/critic losses
    "gt_drl/round",         # GT-DRL: per-round best-response telemetry
    "gt_drl/starts",        # GT-DRL: PPO start draws left after one trial
)


class TapEvent(NamedTuple):
    """One host-side record: the tap's name and its value (numpy pytree)."""
    name: str
    value: Any


class TapBuffer:
    """Bounded ring buffer of ``TapEvent``s (oldest events drop first)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._dq: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def append(self, event: TapEvent) -> None:
        with self._lock:
            self._dq.append(event)

    def __len__(self) -> int:
        return len(self._dq)

    def clear(self) -> None:
        with self._lock:
            self._dq.clear()

    @property
    def events(self) -> List[TapEvent]:
        return list(self._dq)

    def names(self) -> Tuple[str, ...]:
        return tuple(sorted({e.name for e in self._dq}))

    def by_name(self, name: str) -> List[Any]:
        """All values emitted under ``name``, in arrival order."""
        return [e.value for e in self._dq if e.name == name]

    def series(self, name: str, field: Optional[str] = None) -> np.ndarray:
        """Stack a tap's values (or one ``field`` of dict-valued taps) into
        one array — the convergence-curve accessor."""
        vals = self.by_name(name)
        if field is not None:
            vals = [v[field] for v in vals]
        return np.stack([np.asarray(v) for v in vals]) if vals else np.empty((0,))

    def counts(self) -> dict:
        out: dict = {}
        for e in self._dq:
            out[e.name] = out.get(e.name, 0) + 1
        return out


# ---------------------------------------------------------------------------
# module state: the active tap set (trace time) + the sink stack (run time)
# ---------------------------------------------------------------------------

_ACTIVE: frozenset = frozenset()   # patterns live at trace time
_RING = TapBuffer()                # default sink
_SINKS: List[TapBuffer] = [_RING]  # capture() pushes/pops


def active_taps() -> frozenset:
    """The ambient tap patterns — part of every compiled-engine cache key."""
    return _ACTIVE


def normalize(patterns) -> frozenset:
    """None/str/iterable -> the frozenset compile-key form."""
    if patterns is None:
        return frozenset()
    if isinstance(patterns, str):
        patterns = (patterns,)
    return frozenset(patterns)


@functools.lru_cache(maxsize=1024)
def _matches(name: str, patterns: frozenset) -> bool:
    for p in patterns:
        if p == name or p == "*" or (p.endswith("/*") and
                                     name.startswith(p[:-1])):
            return True
    return False


def enabled(name: str) -> bool:
    """Trace-time liveness check for one tap name."""
    return bool(_ACTIVE) and _matches(name, _ACTIVE)  # lint: host-ok(liveness is decided over the host-side active-pattern set at trace time, never over traced values)


def enable_taps(*patterns: str) -> None:
    global _ACTIVE
    _ACTIVE = _ACTIVE | normalize(patterns)


def disable_taps() -> None:
    global _ACTIVE
    _ACTIVE = frozenset()


@contextmanager
def taps(*patterns: str):
    """Ambient enablement: every tap matching ``patterns`` is live for runs
    dispatched inside the block (a different compiled artifact — the
    taps-off one is untouched and stays cached)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = prev | normalize(patterns)
    try:
        yield _RING
    finally:
        _ACTIVE = prev


@contextmanager
def tracing(patterns: frozenset):
    """Pin the active set to exactly ``patterns`` for the duration.

    The compiled engines wrap every dispatch in this so the program traced
    under a cache key always matches that key's tap set — no matter when
    jit decides to trace or what the ambient state is by then.
    """
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = frozenset(patterns)
    try:
        yield
    finally:
        _ACTIVE = prev


@contextmanager
def capture(*patterns: str, capacity: int = DEFAULT_CAPACITY):
    """Collect events into a fresh buffer (and enable ``patterns``, if any).

    ``with obs.capture("engine/hour") as buf: run(...)`` leaves the global
    ring untouched and hands back exactly this block's events.
    """
    buf = TapBuffer(capacity)
    _SINKS.append(buf)
    try:
        if patterns:
            with taps(*patterns):
                yield buf
        else:
            yield buf
    finally:
        _SINKS.remove(buf)


def _record(name: str, value) -> None:
    """The host-side callback target: numpy-ify and append to the live sink."""
    import jax
    host = jax.tree_util.tree_map(np.asarray, value)
    _SINKS[-1].append(TapEvent(name, host))


def tap(name: str, value: Any = None, *, thunk: Optional[Callable] = None):
    """Emit ``value`` (any pytree of arrays) under ``name`` — from inside or
    outside jitted code.

    When ``name`` is not in the active tap set this is a pure no-op: nothing
    is traced, nothing is lowered, the compiled program is unchanged. When
    live, ``thunk`` (if given) is called to *build* the value — use it for
    diagnostics that are expensive to compute — and the value travels to the
    current host sink via ``jax.debug.callback``.
    """
    if not enabled(name):
        return
    import jax
    if thunk is not None:
        value = thunk()
    jax.debug.callback(functools.partial(_record, name), value)  # lint: host-ok(the sanctioned obs escape hatch: an opaque effect that ships values to the host ring; parity tests pin taps-on == taps-off)


def events(name: Optional[str] = None) -> List[TapEvent]:
    """The default ring's events (optionally filtered by exact name)."""
    evs = _RING.events
    return evs if name is None else [e for e in evs if e.name == name]


def ring() -> TapBuffer:
    return _RING


def clear_events() -> None:
    _RING.clear()
