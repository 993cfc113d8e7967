"""``correct`` on the small fd cells: true for the sound program, false with
each fault that the cell can have planted underneath the timed path."""
import pytest

from chipbench import run
from faults import plant

SEED = 2 ** 31 + 77
CELLS = ["small-fd-sweep", "small-fd-faults", "small-fd-batch-shard",
         "small-fd-sweep-resume"]


@pytest.fixture(autouse=True)
def fresh_engines():
    from repro.core import experiment as X

    X._compiled.cache_clear()
    yield
    X._compiled.cache_clear()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_bench, cell):
    out = run.run_cell(small_bench, cell, SEED, 0.2, False,
                       require_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 4
    assert set(out["checks"]) == {"demand_gap", "sum_gap", "violation_share",
                                  "physical_bounds", "plan_gap"}
    assert set(out["metrics"]) == {"fleet_hours_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_batch"])
@pytest.mark.parametrize("cell", CELLS[:3])
def test_fault_is_not_correct(small_bench, monkeypatch, cell, fault):
    plant(monkeypatch, fault)
    out = run.run_cell(small_bench, cell, SEED, 0.2, False,
                       require_chip=False)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_traced_run_reports_the_per_layer_metrics(small_bench, monkeypatch):
    # on the CPU the ops run on host threads: read the host plane as the
    # "device" so that the traced path runs end to end
    from chipbench import trace_reduce

    reduce = trace_reduce.reduce
    monkeypatch.setattr(trace_reduce, "reduce",
                        lambda path: reduce(path, device_prefix="/host:CPU"))
    out = run.run_cell(small_bench, CELLS[0], SEED, 0.2, True,
                       require_chip=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {
        "host_share", "dispatch_ms_per_fleet_hour", "device_idle_share",
        "device_ms_per_fleet_hour", "window_compiles", "setup_compile_s"}
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert 0 <= out["metrics"]["host_share"]["value"] <= 100
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
