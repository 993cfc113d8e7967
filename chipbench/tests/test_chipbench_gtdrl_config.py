"""The GT-DRL deployment's configuration file, ``us16-aibench-gtdrl``: it is
what ``make_config_gtdrl`` writes, its fleet is ``us16-aibench``'s, and its
``scheduler`` group is the learner the program runs in the cell."""
import json
import os

import jax
import pytest

from benchlib import BENCH, ROOT
from chipbench import make_config_gtdrl
from chipbench.manifest import Manifest, problems

NAME = "us16-aibench-gtdrl"
OWN = ("name", "source", "deployment", "assumed", "scheduler")


@pytest.fixture(scope="module")
def committed():
    with open(os.path.join(BENCH, "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_file_is_what_the_generator_writes(committed):
    assert json.loads(json.dumps(make_config_gtdrl.make(NAME))) == committed


def test_fleet_is_us16_aibench(committed):
    with open(os.path.join(BENCH, "configs", "us16-aibench.json")) as f:
        fleet = json.load(f)
    assert set(committed) - set(fleet) == {"scheduler"}
    for k in fleet:
        if k not in OWN:
            assert committed[k] == fleet[k], k
    assert committed["source"] != fleet["source"]


def test_manifest_runs_the_config_in_the_gtdrl_cell(committed):
    m = Manifest(ROOT)
    assert problems(m) == []
    cell = m.cell("aibench16-gtdrl-day")
    assert cell["config"] == NAME
    entry = [c for c in m.data["configs"] if c["name"] == NAME][0]
    assert entry["source"] == committed["source"]
    assert entry["reduced"] == committed["reduced"] == []
    assert m.traffic(cell["traffic"])["spec"]["technique"] == \
        committed["scheduler"]["technique"]


def test_scheduler_widths_are_the_agents(committed):
    """The actor's and critic's layer widths are those of the agents the
    program builds on this fleet."""
    import jax.numpy as jnp

    from repro.core import game, gt_drl
    from repro.dcsim.env import EnvParams

    s = committed["scheduler"]
    env = EnvParams(**{k: jnp.asarray(v, jnp.float32)
                       for k, v in committed["env"].items()})
    cfg = game.get_technique("gt-drl").default_cfg
    agents = jax.eval_shape(lambda k: gt_drl.init_agents(k, env, cfg, True),
                            jax.random.PRNGKey(0))
    for net, widths in ((agents.actor["mlp"], s["actor_widths"]),
                        (agents.critic, s["critic_widths"])):
        got = [net["w0"].shape[1]] + [net[f"w{i}"].shape[2]
                                      for i in range(len(widths) - 1)]
        assert got == widths
        assert net["w0"].shape[0] == s["players"]
