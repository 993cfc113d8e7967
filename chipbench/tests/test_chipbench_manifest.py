"""The manifest and the files it names, found by name."""
import json
import os

import pytest

from chipbench.manifest import Manifest, problems
from chipbench.traffic import Inputs

from benchlib import ROOT


def test_committed_manifest_resolves_every_name():
    m = Manifest(ROOT)
    assert problems(m) == []
    for w in m.data["workloads"]:
        cfg = m.config(w["config"])
        assert cfg["hours"] >= 1 and cfg["env"]["er"]
        assert m.traffic(w["traffic"])["call"] in ("run", "sweep")
    e2e = {x["name"] for x in m.data["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert {x["moves"] for x in m.data["per_layer"]} <= e2e


def test_committed_metric_readers_load():
    m = Manifest(ROOT)
    for metric in m.data["per_layer"]:
        assert m.reader(metric["name"])({}) is None or metric["name"] in (
            "window_compiles", "setup_compile_s")


@pytest.mark.parametrize("field,value", [
    ("name", "bad name"), ("name", "a/b"), ("unit", "tokens per second"),
    ("unit", "x" * 17), ("better", "up")])
def test_bad_names_and_units_are_found(tmp_path, field, value):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["end_to_end"][0][field] = value
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(data, f)
    os.symlink(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    assert problems(Manifest(str(tmp_path)))


def test_a_new_config_and_mix_need_only_new_files(small_bench):
    m = Manifest(small_bench)
    assert problems(m) == []
    cell = m.cell("small-fd-sweep")
    inputs = Inputs(m.config(cell["config"]), m.traffic(cell["traffic"]),
                    seed=2 ** 31 + 5)
    assert inputs.rows == 4 and inputs.hours == 4
    assert inputs.fleet_hours_per_call == 16
    env = inputs.env_of(3, 2)
    assert env["er"].shape == (10, 4)


def test_a_sharded_batched_run_mix_needs_only_new_files(small_bench):
    # the mix exists only in the temporary directory; the harness finds it
    # by name, and each row of a call plans on the next day of the pool
    m = Manifest(small_bench)
    cell = m.cell("small-fd-batch-shard")
    mix = m.traffic(cell["traffic"])
    assert mix["kwargs"] == {"shard": True}
    inputs = Inputs(m.config(cell["config"]), mix, seed=2 ** 31 + 6)
    assert inputs.rows == 3 and inputs.fleet_hours_per_call == 12
    assert [inputs.pool_of(1, r) for r in range(3)] == [1, 0, 1]
    assert len({inputs.seed_of(k, r) for k in range(2)
                for r in range(3)}) == 6
    assert inputs.trace_of(1, 0) is inputs.traces[1][0]


def test_same_seed_same_inputs_other_seed_same_sizes(small_bench):
    m = Manifest(small_bench)
    cell = m.cell("small-gtdrl-day")
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    a, b, c = (Inputs(cfg, mix, s) for s in (2 ** 32 + 1, 2 ** 32 + 1, 9))
    assert all((x["car"] == y["car"]).all() for x, y in zip(a.pool, b.pool))
    assert a.seed_of(5) == b.seed_of(5)
    assert [p["car"].shape for p in a.pool] == [p["car"].shape for p in c.pool]
    assert not (a.pool[0]["car"] == c.pool[0]["car"]).all()
