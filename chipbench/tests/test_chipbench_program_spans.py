"""The readers of the program's request spans: each phase per fleet-hour,
and the unspanned share, on synthetic requests and in a traced CPU run,
where the phases, the dispatch and the unspanned time make up the
window. The run lists the readers under ``per_layer`` in its own manifest,
as the entries of ``PER_LAYER`` would read in ``BENCHMARK.json``."""
import json
import os

import pytest

from chipbench import run
from chipbench.manifest import Manifest

SEED = 2 ** 31 + 91
PHASES = ("grid_ms_per_fleet_hour", "inputs_ms_per_fleet_hour",
          "fetch_ms_per_fleet_hour", "format_ms_per_fleet_hour")
NEW = PHASES + ("unspanned_share",)
PER_LAYER = [
    {"name": name, "unit": "%" if name == "unspanned_share" else "ms",
     "better": "lower", "source": "program_span",
     "layer": "run/sweep host path", "moves": "fleet_hours_per_s"}
    for name in NEW]
PER_LAYER[0]["workloads"] = ["aibench16-fd-sweep64",
                             "aibench16-fd-faults-sweep"]


def _request(i, fleet_hours, spans, self_s=0.001):
    from repro.obs import Request

    return Request(id=i, name="api.sweep", start=0.0,
                   end=self_s + sum(spans.values()), self_s=self_s,
                   counters={"rows": 4, "fleet_hours": fleet_hours,
                             "dispatches": 1, "fetches": 10},
                   spans=dict(spans))


@pytest.fixture
def seeded(monkeypatch):
    """Three finished requests; the window's two are the last."""
    from repro import obs

    sweep_spans = {"sweep.grid": 0.002, "scenario.wan_degradation": 0.010,
                   "scenario.sla_tighten": 0.004, "sweep.stack": 0.004,
                   "engine.inputs": 0.006, "engine.dispatch": 0.050,
                   "engine.fetch": 0.003, "engine.format": 0.001}
    reqs = [_request(1, 999, {"engine.inputs": 5.0}),
            _request(2, 96, sweep_spans), _request(3, 96, sweep_spans)]
    monkeypatch.setattr(obs, "requests",
                        lambda last=None: reqs[len(reqs) - last:])
    return {"calls": 2, "fleet_hours": 192, "window_s": 0.2,
            "dispatch_s": 0.1}


def _read(name, ctx):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return Manifest(root).reader(name)(ctx)


@pytest.mark.parametrize("name,want", [
    ("grid_ms_per_fleet_hour", 1000 * 2 * 0.020 / 192),
    ("inputs_ms_per_fleet_hour", 1000 * 2 * 0.006 / 192),
    ("fetch_ms_per_fleet_hour", 1000 * 2 * 0.003 / 192),
    ("format_ms_per_fleet_hour", 1000 * 2 * 0.001 / 192),
    ("unspanned_share", 100 * (0.2 - 2 * 0.080) / 0.2)])
def test_reader_on_seeded_requests(seeded, name, want):
    assert _read(name, seeded) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_where_fleet_hours_disagree(seeded, name):
    assert _read(name, {**seeded, "fleet_hours": 191}) is None
    # the window's calls reach back to a request of another size
    assert _read(name, {**seeded, "calls": 3, "fleet_hours": 288}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_none_without_program_requests(monkeypatch, name):
    from repro import obs

    monkeypatch.delattr(obs, "requests")
    assert _read(name, {"calls": 1, "fleet_hours": 96, "window_s": 1.0,
                        "dispatch_s": 0.5}) is None


def test_grid_reader_is_none_for_requests_without_a_grid(monkeypatch):
    from repro import obs

    reqs = [_request(1, 24, {"engine.inputs": 0.001,
                             "engine.dispatch": 0.060})]
    monkeypatch.setattr(obs, "requests", lambda last=None: reqs[-last:])
    ctx = {"calls": 1, "fleet_hours": 24, "window_s": 0.07}
    assert _read("grid_ms_per_fleet_hour", ctx) is None
    assert _read("inputs_ms_per_fleet_hour", ctx) == pytest.approx(
        1000 * 0.001 / 24)


def _with_fd_day(root):
    """Add a small fd day (one scan-engine run per call) and the readers'
    ``per_layer`` entries to the bench."""
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(bench, "traffic", "tiny-fd-day.json"), "w") as f:
        json.dump({"call": "run", "spec": {"technique": "fd",
                                           "engine": "scan"},
                   "pool": 2, "arrival_std": 0.2}, f)
    with open(os.path.join(bench, "limits", "small-fd-sweep.json")) as f:
        limits = json.load(f)
    with open(os.path.join(bench, "limits", "small-fd-day.json"), "w") as f:
        json.dump(limits, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": "small-fd-day",
                                  "config": "small-aibench",
                                  "traffic": "tiny-fd-day", "chips": 1,
                                  "why": "test"})
    manifest["per_layer"] += PER_LAYER
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("cell", ["small-fd-day", "small-fd-sweep"])
def test_traced_phases_add_up_to_the_window(small_bench, monkeypatch, cell):
    from chipbench import trace_reduce
    from repro.core import experiment as X

    X._compiled.cache_clear()
    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda path: reduce(path, device_prefix="/host:CPU"))
    out = run.run_cell(_with_fd_day(small_bench), cell, SEED, 0.2, True,
                       require_chip=False)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert ("grid_ms_per_fleet_hour" in got) == (cell == "small-fd-sweep")
    assert set(NEW) - {"grid_ms_per_fleet_hour"} <= set(got)
    # window ms per fleet-hour, from the dispatch and its share
    dispatch = got["dispatch_ms_per_fleet_hour"]
    window = dispatch / (1 - got["host_share"] / 100)
    parts = (sum(got.get(k, 0.0) for k in PHASES) + dispatch
             + got["unspanned_share"] / 100 * window)
    assert parts == pytest.approx(window, rel=0.01)
    assert 0 <= got["unspanned_share"] < 100
    assert all(got[k] >= 0 for k in PHASES if k in got)
