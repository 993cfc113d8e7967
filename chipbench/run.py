"""Run one benchmark cell once on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Refuse anything but a TPU with at least the cell's chips: exit 3, no
   result.
2. Keep JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``,
   else at ``<checkout>/.jax_cache``.
3. Build the cell's inputs from ``--seed`` (``chipbench.traffic``): its
   configuration from ``chipbench/configs``, its mix from
   ``chipbench/traffic``.
4. Set-up: deploy the stateful solver where the mix asks, and make two
   calls, which compile every shape the window uses.
5. Measure for ``--seconds`` in a closed loop of public API calls, each
   returning its results on the host. The window ends when the last call
   that started inside it completes.
6. Hold every answer to the plain reference (``chipbench.correct``), print
   each number beside its limit as the last lines on stderr, and print one
   JSON object as the last line on stdout.

``--trace 1`` runs a window of at most ``TRACE_S`` seconds (whole calls)
under ``jax.profiler`` and reports the cell's per-layer metrics instead of
its end-to-end ones.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import correct as C  # noqa: E402
from chipbench.manifest import Manifest  # noqa: E402
from chipbench.traffic import Caller, Inputs  # noqa: E402

WARMUP_CALLS = 2
# a traced window's length: the profiler's output grows with every op, and
# two traced gt-drl days added ~190 s to a run on a TPU v5e
TRACE_S = 1.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def fleet_rate(calls: int, fleet_hours_per_call: int,
               window_s: float) -> float:
    """Fleet-hours per second over the whole window."""
    return calls * fleet_hours_per_call / window_s


def with_host_cpu() -> None:
    """Keep JAX's CPU backend beside the chip's: the reference runs there."""
    import jax

    plats = jax.config.jax_platforms
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", plats + ",cpu")


def _check_chip(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (devices()[0] is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")


def _cache(root: str) -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _dispatch_s() -> float:
    from repro import obs

    return sum(st["dispatch_s"] for st in obs.cache_stats()["engines"].values())


def _device(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             t0: float = T0) -> Dict[str, Any]:
    """One run of one cell; returns the result object (see module doc)."""
    m = Manifest(root)
    cell = m.cell(workload)
    config, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    limits = m.limits(workload)
    # the system under test: a checkout without it fails here
    import repro.core  # noqa: F401
    with_host_cpu()
    if require_chip:
        _check_chip(int(cell["chips"]))
    _cache(root)
    import jax

    from chipbench.compile_counter import CompileCounter

    counter = CompileCounter()
    inputs = Inputs(config, mix, seed)
    caller = Caller(inputs)
    caller.deploy()
    for k in range(WARMUP_CALLS):
        caller.call(k)
    setup_compile_s = math.fsum(counter.compile_s)
    compiles0 = len(counter.compile_s)
    setup_s = time.perf_counter() - t0

    if trace:   # the traced run's window is the profiler's: whole calls
        seconds = min(seconds, TRACE_S)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    results: List[Any] = []
    call_s: List[float] = []
    dispatch0 = _dispatch_s()
    start = time.perf_counter()
    end = start
    try:
        while end - start < seconds:
            k = len(results)
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.call"):
                results.append(caller.call(k))
            end = time.perf_counter()
            call_s.append(end - c0)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = end - start
    dispatch_s = _dispatch_s() - dispatch0
    window_compiles = len(counter.compile_s) - compiles0
    device = _device(int(cell["chips"]))

    answers = [(k, r, ans) for k, res in enumerate(results)
               for r, ans in enumerate(caller.answers(res))]
    caller.close()
    del caller, results
    checks, failed = C.check(inputs, answers, limits, seed)
    fh_call = inputs.fleet_hours_per_call
    out: Dict[str, Any] = {
        "correct": all(v <= lim for _, v, lim in checks) and failed == 0,
        "attempted": len(answers), "failed": failed}
    if not trace:
        values = {"fleet_hours_per_s": fleet_rate(len(call_s), fh_call,
                                                  window_s),
                  "setup_s": setup_s}
        out["metrics"] = {
            mt["name"]: {"value": values[mt["name"]], "unit": mt["unit"]}
            for mt in m.data["end_to_end"]}
    else:
        from chipbench import trace_reduce

        try:
            red = trace_reduce.reduce(trace_reduce.find(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"window_s": window_s, "dispatch_s": dispatch_s,
               "fleet_hours": len(call_s) * fh_call,
               "fleet_hours_per_call": fh_call, "calls": len(call_s),
               "window_compiles": window_compiles,
               "setup_compile_s": setup_compile_s, "trace": red}
        out["metrics"] = {}
        for mt in m.data["per_layer"]:
            v = m.reader(mt["name"])(ctx)
            if v is not None:
                out["metrics"][mt["name"]] = {"value": v, "unit": mt["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["device"] = device
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
