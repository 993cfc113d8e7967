"""The batched engine's inputs are assembled in a constant number of
compiled calls (``E.stack_envs``, ``FL.stack_traces``, ``E.first_row``,
the per-row keys) and are bit for bit what the eager expressions give:
one ``jnp.stack`` per leaf, one ``jax.random.split`` per row."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults as FL
from repro import obs
from repro import scenarios as S
from repro.core import ExperimentSpec, run, sweep
from repro.core import experiment as X
from repro.core.force_directed import FDConfig
from repro.dcsim import env as E

SPEC = ExperimentSpec(technique="fd", objective="cost_sla", routed=True,
                      hours=3, cfg=FDConfig(iters=40))
GRID64 = {"wan_degradation": (1.0, 2.0, 3.0, 4.0),
          "origin_shift": (0.0, 0.3, 0.6, 0.9),
          "sla_tighten": (1.0, 0.85, 0.7, 0.55)}


def _eager_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _eager_keys(seeds):
    return jnp.stack([jax.random.split(jax.random.PRNGKey(s))[1]
                      for s in seeds])


def _assert_same_leaves(got, want):
    assert type(got) is type(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype, g.weak_type) == (w.shape, w.dtype, w.weak_type)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture(scope="module", params=[4, 16], ids=["D4", "D16"])
def grid_envs(request):
    """The sweep64 grid's 64 envs: rows that differ, with the weak types
    the eager transforms leave."""
    _, rows = S.build_grid(E.build_env(request.param, seed=0), GRID64)
    return [env for _, env in rows]


@pytest.mark.parametrize("rows", [1, 3, 64])
def test_stack_envs_matches_eager_stack(grid_envs, rows):
    envs = grid_envs[:rows]
    _assert_same_leaves(E.stack_envs(envs), _eager_stack(envs))


@pytest.mark.parametrize("rows", [1, 3, 64])
@pytest.mark.parametrize("d", [4, 16])
def test_stack_traces_matches_eager_stack(d, rows):
    traces = [FL.random_trace(d, seed=k) for k in range(rows)]
    _assert_same_leaves(FL.stack_traces(traces), FL.FaultTrace(
        *(jnp.stack([getattr(t, f) for t in traces])
          for f in FL.FaultTrace._fields)))


def test_first_row_matches_eager_slice(grid_envs):
    env_b = E.stack_envs(grid_envs[:3])
    _assert_same_leaves(E.first_row(env_b),
                        jax.tree_util.tree_map(lambda x: x[0], env_b))


@pytest.mark.parametrize("seeds", [
    [7] * 5,
    [0, 1, 2, 3, 4],
    [0],
    [2 ** 31 - 1],
    [2 ** 32],
    [3, 2 ** 33 + 3, 3],
    [-1, 5],
    list(np.arange(6, dtype=np.int64) * 1_000_003),
], ids=["repeated", "distinct", "zero", "int32_max", "2^32",
        "above_2^33", "negative", "numpy"])
def test_row_keys_match_eager_split(seeds):
    got, want = X._row_keys(seeds), _eager_keys(seeds)
    assert (got.shape, got.dtype) == (want.shape, want.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("faulted", [False, True], ids=["plain", "faults"])
def test_sweep_matches_eager_assembly(monkeypatch, faulted):
    """A sweep gives the totals and per-epoch arrays that the eager
    stacking, row-0 slice and per-row key split gave."""
    env = E.build_env(4, seed=0)
    grid = {"wan_degradation": (1.0, 3.0), "origin_shift": (0.0, 0.6),
            "sla_tighten": (1.0, 0.7)}
    traces = ([FL.random_trace(env, seed=k) for k in range(8)]
              if faulted else None)
    spec = SPEC.replace(seed=2 ** 30 + 11)
    got = sweep(spec, grid, base_env=env, faults=traces)

    monkeypatch.setattr(E, "stack_envs", lambda envs: _eager_stack(list(envs)))
    monkeypatch.setattr(FL, "stack_traces", _eager_stack)
    monkeypatch.setattr(E, "first_row", lambda t: jax.tree_util.tree_map(
        lambda x: x[0], t))
    monkeypatch.setattr(X, "_row_keys", _eager_keys)
    want = sweep(spec, grid, base_env=env, faults=traces)

    for sect in ("totals", "per_epoch"):
        g, w = got["results"]["fd"][sect], want["results"]["fd"][sect]
        assert set(g) == set(w)
        for k in w:
            np.testing.assert_array_equal(g[k], w[k])


def test_repeat_sweep_builds_no_new_program_and_counts_rows():
    env = E.build_env(4, seed=0)
    grid = {"wan_degradation": (1.0, 2.0), "origin_shift": (0.0, 0.5)}
    traces = [FL.random_trace(env, seed=k) for k in range(4)]
    stacked_traces = FL.stack_traces(traces)
    programs = (E.stack_trees, E.first_row, X._split_seeds)
    spec = SPEC.replace(engine="batched", seeds=(SPEC.seed,) * 4)

    def calls():  # the batched calls share the sweep's engine
        sweep(SPEC, grid, base_env=env, faults=traces)
        run(spec, [env] * 4, faults=stacked_traces)
        run(spec, E.stack_envs([env] * 4), faults=stacked_traces)
        run(SPEC, env)

    calls()
    sizes = [p._cache_size() for p in programs]
    misses = obs.cache_stats()["misses"]
    calls()
    assert [p._cache_size() for p in programs] == sizes
    assert obs.cache_stats()["misses"] == misses
    swept, listed, stacked, scan = obs.requests(last=4)
    assert swept.counters["stacked_rows"] == 4       # the env rows, not traces
    assert listed.counters["stacked_rows"] == 4
    assert "stacked_rows" not in stacked.counters    # stacked by the caller
    assert "stacked_rows" not in scan.counters
