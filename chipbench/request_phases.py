"""The program's own request spans, read for the per-layer host metrics.

Every public ``run``/``sweep`` call is one request in ``repro.obs``
(``obs.requests``): its counters and the self time of each child span name.
A reader takes the traced window's requests, the last ``ctx["calls"]``
finished, and trusts them only where their ``fleet_hours`` add up to the
window's. A program that keeps no requests gives ``None`` and no error.
The harness reports a reader's metric once ``BENCHMARK.json`` lists it under
``per_layer``.
"""
from __future__ import annotations

from typing import Any, List, Mapping, Optional, Sequence


def window_requests(ctx: Mapping[str, Any]) -> Optional[List[Any]]:
    """The traced window's requests, or None."""
    calls = ctx.get("calls")
    if not calls or not ctx.get("fleet_hours"):
        return None
    try:
        from repro import obs

        reqs = obs.requests(last=calls)
    except (ImportError, AttributeError):
        return None
    if len(reqs) != calls:
        return None
    if sum(r.counters.get("fleet_hours", 0) for r in reqs) != \
            ctx["fleet_hours"]:
        return None
    return reqs


def ms_per_fleet_hour(ctx: Mapping[str, Any], names: Sequence[str] = (),
                      prefixes: Sequence[str] = ()) -> Optional[float]:
    """Self time of the spans named ``names`` or starting with ``prefixes``,
    in milliseconds per fleet-hour; None where no request has such a span."""
    reqs = window_requests(ctx)
    if reqs is None:
        return None
    hits = [s for r in reqs for n, s in r.spans.items()
            if n in names or n.startswith(tuple(prefixes))]
    if not hits:
        return None
    return 1000.0 * sum(hits) / ctx["fleet_hours"]


def unspanned_share(ctx: Mapping[str, Any]) -> Optional[float]:
    """100 * (window - time inside any child span of the requests) / window:
    the requests' own self time plus the harness between calls."""
    reqs = window_requests(ctx)
    if reqs is None or not ctx.get("window_s"):
        return None
    covered = sum(s for r in reqs for s in r.spans.values())
    return 100.0 * (ctx["window_s"] - covered) / ctx["window_s"]
