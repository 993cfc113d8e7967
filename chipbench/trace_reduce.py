"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy time.

The window is the host's span from the start of the first annotated call
(``CALL_SPAN``, written by the harness around each public call) to the end
of the last; the profiler runs only around those calls. On each device
plane, busy time is the union of the intervals in which an operation ran;
the result averages it over the devices. Idle gaps are the holes in that
union, each named by what the host was doing at its midpoint: inside a call
(the program's host path, dispatch) or between calls (the harness). Host
and device clocks in a TPU v5e trace differ by about a millisecond, so a
label is only sure for gaps longer than that.

Ops nest on the ``XLA Ops`` line (a ``while`` spans its body's ops), so an
op is charged its self time: its duration less that of the ops inside it.
An op is named by its HLO instruction name (``%fusion.161``).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

CALL_SPAN = "chipbench.call"
OPS_LINE = "XLA Ops"
TOP = 10
MIN_GAP_NS = 1000     # holes between back-to-back ops are not idle time


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _self_time(events) -> Dict[str, float]:
    """Nanoseconds of each op, less its nested ops'."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float]] = []      # (name, end) of enclosing ops
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        d = e - s
        short = name.split(" = ", 1)[0]
        out[short] += d
        if stack:
            out[stack[-1][0]] -= d
        stack.append((short, e))
    return out


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str, device_prefix: str = "/device:TPU:") -> Dict:
    """``busy_s`` (mean over devices), ``window_s``, ``calls``,
    ``device_ops`` and ``idle_gaps`` (top entries, seconds)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    calls: List[Tuple[float, float]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices.append([(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ln in ops for ev in ln.events])
        if plane.name.startswith("/host:"):
            calls += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ln in plane.lines for ev in ln.events
                      if ev.name == CALL_SPAN]
    if not calls:
        raise ValueError(f"{path}: no {CALL_SPAN!r} span on a host plane")
    if not devices:
        raise ValueError(f"{path}: no plane named {device_prefix}*")
    lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    n_calls, calls = len(calls), _union(calls)
    busy = []
    op_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for events in devices:
        merged = _union([(s, e) for _, s, e in events])
        busy.append(sum(e - s for s, e in merged))
        for name, ns in _self_time(events).items():
            op_ns[name] += ns / len(devices)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s > MIN_GAP_NS:
                mid = (s + e) / 2
                inside = any(a <= mid <= b for a, b in calls)
                gaps.append(("inside call" if inside else "between calls",
                             (e - s) * 1e-9))
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "calls": n_calls,
            "devices": len(devices),
            "device_ops": [[n, ns * 1e-9] for n, ns in ops],
            "idle_gaps": [[n, s] for n, s in gaps[:TOP]]}
