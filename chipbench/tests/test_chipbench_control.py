"""The control (the reference in bfloat16, in the program's place) makes
``correct`` false in each small fd cell, on three seeds."""
import pytest

from chipbench.control import control_run
from chipbench.manifest import Manifest
from chipbench.traffic import Inputs


@pytest.mark.parametrize("cell", ["small-fd-sweep", "small-fd-faults",
                                  "small-fd-batch-shard"])
def test_control_fails_a_limit(small_bench, cell):
    m = Manifest(small_bench)
    w = m.cell(cell)
    cfg, mix = m.config(w["config"]), m.traffic(w["traffic"])
    limits = m.limits(cell)
    for seed in (1, 2 ** 31 + 2, 3):
        out = control_run(Inputs(cfg, mix, seed), limits, seed)
        assert not out["correct"], out["checks"]
        assert out["failed"] > 0
