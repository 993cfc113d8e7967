"""``repro.core.sampling.dirichlet`` against ``jax.random.dirichlet``: the
same samples, bit for bit, at the GT-DRL starts' shapes and at the edges
of the gamma sampler (the boost boundary alpha = 1, the compaction's
extra passes and the exact replay)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import gt_drl, sampling
from repro.core.game import GameContext, uniform_fractions
from repro.dcsim import env as E

EPISODES, S, D = 32, 16, 16     # the gt-drl cell's starts: episodes x (S, D)
KEY = jax.random.PRNGKey(20240401)


def _uniform_joint():
    return jnp.full((S, D), 1.0 / D)


def _peaked_joint():
    w = jax.random.dirichlet(jax.random.PRNGKey(7), jnp.full((S, D), 0.3))
    return w ** 4 / jnp.sum(w ** 4, axis=-1, keepdims=True)


def _alpha(joint):
    return jnp.broadcast_to(joint * 20.0 + 0.5, (EPISODES, S, D))


def _assert_same(key, alpha):
    want = jax.jit(jax.random.dirichlet)(key, alpha)
    # a fresh function each time: no trace cached under other constants
    got, counts = jax.jit(lambda k, a: sampling.dirichlet_counted(k, a))(
        key, alpha)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert jnp.array_equal(got, want)
    return {k: int(v) for k, v in counts.items()}


@pytest.mark.parametrize("joint", [_uniform_joint, _peaked_joint],
                         ids=["uniform", "peaked"])
def test_dirichlet_matches_jax_at_the_starts_shape(joint):
    counts = _assert_same(KEY, _alpha(joint()))
    assert counts["residual"] > 0


@pytest.mark.parametrize("value", [0.5, 1.0, 20.5])
def test_dirichlet_matches_jax_at_constant_alpha(value):
    alpha = jnp.full((EPISODES, S, D), value, jnp.float32)
    for seed in (0, 1, 2):
        _assert_same(jax.random.PRNGKey(seed), alpha)


def test_dirichlet_matches_jax_under_vmap_over_players():
    keys = jax.random.split(KEY, 5)
    joints = jnp.stack([_uniform_joint(), _peaked_joint()] * 2
                       + [_uniform_joint()])
    alphas = jax.vmap(_alpha)(joints)
    want = jax.jit(jax.vmap(jax.random.dirichlet))(keys, alphas)
    got = jax.jit(jax.vmap(sampling.dirichlet))(keys, alphas)
    assert jnp.array_equal(got, want)


def test_dirichlet_matches_jax_with_typed_keys():
    key = jax.random.key(11)
    alpha = _alpha(_peaked_joint())
    want = jax.random.dirichlet(key, alpha)
    assert jnp.array_equal(jax.jit(sampling.dirichlet)(key, alpha), want)


@pytest.mark.parametrize("trials", [0, 3])
def test_dirichlet_matches_jax_through_many_passes_and_the_replay(
        trials, monkeypatch):
    """A one-entry buffer takes one pass per variate left; with no
    straight-line trials on it, every such variate goes through the exact
    loop, including its inner ``v <= 0`` redraws (some 0.7 % of the first
    normals at alpha = 1 give ``v <= 0``)."""
    monkeypatch.setattr(sampling, "_buffer", lambda n: 1)
    monkeypatch.setattr(sampling, "_TRIALS", trials)
    alpha = jnp.concatenate([jnp.full((8, S, D), 1.0),
                             _alpha(_peaked_joint())[:8]])
    counts = _assert_same(KEY, alpha)
    assert counts["residual"] > 1
    if trials == 0:
        assert counts["replayed"] == counts["residual"]


def test_starts_tap_reports_the_residual_at_the_cells_shapes():
    env = E.build_env(num_dcs=D, seed=0)
    ctx = GameContext(env, tau=6, objective="cost_sla", routed=True)
    joint = uniform_fractions(ctx)
    assert joint.shape == (S, E.num_players(env), D)
    *_, state0_fn = gt_drl._player_game(
        env, ctx.tau, ctx.objective, jnp.zeros(D), joint, 0, "strategy",
        EPISODES)
    with obs.capture("gt_drl/starts") as buf:
        starts = jax.jit(state0_fn)(KEY)
        jax.block_until_ready(starts)
    assert starts.shape == (EPISODES, S * D)
    residual = buf.series("gt_drl/starts", "residual")
    replayed = buf.series("gt_drl/starts", "replayed")
    assert residual.shape == (1,) and residual[0] > 0
    assert 0 <= replayed[0] <= residual[0]
    rows = np.asarray(starts).reshape(EPISODES, S, D)
    np.testing.assert_allclose(rows.sum(-1), 1.0, rtol=1e-5)
