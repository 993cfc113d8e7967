"""``correct`` on a small gt-drl day cell: true for the sound program, false
with each fault that the cell can have planted underneath it. (One day per
call: no batch to halve.) No committed cell runs gt-drl: the public API
returns no plan, so only the plan-free numbers can hold it; this keeps the
harness's deploy path working."""
import pytest

from chipbench import run
from benchlib import tiny_gtdrl
from faults import plant

SEED = 2 ** 31 + 91
CELL = "small-gtdrl-day"


@pytest.fixture(autouse=True)
def fresh_engines():
    from repro.core import experiment as X

    X._compiled.cache_clear()
    yield
    X._compiled.cache_clear()


@pytest.mark.parametrize("fault", [None, "state_unchanged", "answer_altered"])
def test_correct_only_without_a_fault(small_bench, monkeypatch, fault):
    tiny_gtdrl(monkeypatch)
    if fault:
        plant(monkeypatch, fault)
    out = run.run_cell(small_bench, CELL, SEED, 0.2, False,
                       require_chip=False)
    if fault is None:
        assert out["correct"], out["checks"]
        assert "plan_gap" not in out["checks"]
    else:
        assert not out["correct"], out["checks"]
        assert out["failed"] > 0
