"""GT-DRL's best-response rounds against their plain reference
(``chipbench/reference_gtdrl.py``) on the CPU: 4 DCs, the tiny learner of
``benchlib.tiny_gtdrl``, seeded random agents, a routed ``cost_sla`` game
and an unrouted ``carbon`` one. Each half of a round, one epoch and a
small scan day match the float32 reference within ``compare_gtdrl.TOL``;
the reference computed in bfloat16, and the program with a fault planted
in its PPO, each miss at least one of them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import HOURS, small_config, tiny_gtdrl
from chipbench import compare_gtdrl as C
from chipbench import reference_gtdrl as G

SEED = 2 ** 31 + 15
CASES = {"routed": (True, "cost_sla"), "unrouted": (False, "carbon")}
TAU = 2
# a small day's totals, relative: each hour runs free from the agents the
# hour before left, so a near-tie choice can move a later hour's plan
DAY_GAP = 1e-4


@pytest.fixture(scope="module")
def tiny_cfg():
    from repro.core import game

    with pytest.MonkeyPatch.context() as mp:
        tiny_gtdrl(mp)
        return game.get_technique("gt-drl").default_cfg


@pytest.fixture(scope="module")
def env():
    from repro.dcsim.env import EnvParams

    return EnvParams(**{k: jnp.asarray(np.asarray(v, np.float32))
                        for k, v in small_config()["env"].items()})


def _agents(env, cfg, routed):
    """Seeded agents whose policy is not near zero: the init's weights
    plus seeded noise."""
    from repro.core import gt_drl

    agents = gt_drl.init_agents(jax.random.PRNGKey(SEED % 997), env, cfg,
                                routed)
    leaves, tree = jax.tree_util.tree_flatten((agents.actor, agents.critic))
    keys = jax.random.split(jax.random.PRNGKey(SEED % 991), len(leaves))
    noisy = [x + 0.3 * jax.random.normal(k, x.shape)
             for x, k in zip(leaves, keys)]
    actor, critic = jax.tree_util.tree_unflatten(tree, noisy)
    return agents._replace(actor=actor, critic=critic)


def _setup(env, cfg, case):
    from repro.core.game import GameContext

    routed, objective = CASES[case]
    ctx = GameContext(env=env, tau=jnp.int32(TAU), objective=objective,
                      routed=routed)
    agents = _agents(env, cfg, routed)
    joint = jax.random.dirichlet(jax.random.PRNGKey(SEED % 983),
                                 jnp.ones(ctx.joint_shape()))
    e = G.as_dtype({k: jnp.asarray(v) for k, v in G.env_view(
        C.env_dict(env), ctx.is_routed()).items()}, jnp.float32)
    plain = [C.plain_agent(agents, i) for i in range(env.er.shape[0])]
    return ctx, agents, joint, e, plain


@pytest.mark.parametrize("parity", [0, 1])
@pytest.mark.parametrize("case", list(CASES))
def test_half_update_matches_reference(env, tiny_cfg, case, parity):
    ctx, agents, joint, e, plain = _setup(env, tiny_cfg, case)
    peak = jnp.zeros((env.er.shape[1],))
    key = jax.random.PRNGKey(SEED % 977 + parity)
    prog, new_agents, new_joint = C.program_players(
        agents, joint, key, parity, ctx, peak, tiny_cfg)
    i_n = env.er.shape[0]
    assert sorted(prog) == list(range(parity, i_n, 2))
    j3 = joint if ctx.is_routed() else joint[None]
    gaps = [C.all_gaps(p, (e, j3, i, TAU, peak, C.config_of(tiny_cfg),
                           ctx.is_routed(), ctx.objective, jnp.float32),
                       ctx, joint, peak, tiny_cfg)
            for i, p in prog.items()]
    for k in C.GAPS:
        assert max(g[k] for g in gaps) <= C.TOL[k], (k, gaps)
    # half_update puts each active player's row and agent in place and
    # leaves the other half's as they were
    for i in range(i_n):
        got_row = np.asarray(new_joint[..., i, :])
        got_std = np.asarray(new_agents.actor["log_std"][i])
        if i in prog:
            want_row = np.asarray(prog[i]["row"]).reshape(got_row.shape)
            want_std = np.asarray(prog[i]["agent"]["actor"]["log_std"])
        else:
            want_row = np.asarray(joint[..., i, :])
            want_std = np.asarray(agents.actor["log_std"][i])
        np.testing.assert_array_equal(got_row, want_row)
        np.testing.assert_array_equal(got_std, want_std)


@pytest.mark.parametrize("case", list(CASES))
def test_solve_epoch_matches_reference(env, tiny_cfg, case):
    ctx, agents, _, e, plain = _setup(env, tiny_cfg, case)
    peak = jnp.zeros((env.er.shape[1],))
    key = jax.random.PRNGKey(SEED % 971)
    _, res = C._program()["epoch"](key, agents, ctx, peak, tiny_cfg)
    ref = G.solve_epoch(key, plain, e, TAU, peak, C.config_of(tiny_cfg),
                        ctx.is_routed(), ctx.objective, jnp.float32)
    assert int(res.info["diverged_rounds"]) == ref["diverged_rounds"] == 0
    np.testing.assert_allclose(np.asarray(res.info["round_values"]),
                               ref["round_values"], rtol=C.TOL["best"])
    assert abs(float(res.info["best"]) - ref["best"]) <= (
        C.TOL["best"] * abs(ref["best"]))


@pytest.mark.parametrize("case", list(CASES))
def test_scan_day_totals_match_reference(env, tiny_cfg, case):
    from repro.core import ExperimentSpec, run

    routed, objective = CASES[case]
    _, agents, _, e, plain = _setup(env, tiny_cfg, case)
    spec = ExperimentSpec(technique="gt-drl", engine="scan", routed=routed,
                          objective=objective, hours=HOURS, cfg=tiny_cfg,
                          seed=SEED % 2 ** 30)
    got = run(spec, env, solver_state0=agents)
    ref = G.play_day(spec.seed, plain, e, HOURS, C.config_of(tiny_cfg),
                     routed, objective, jnp.float32)
    for k in ("carbon_kg", "cost_usd", "energy_cost_usd", "peak_cost_usd"):
        want = float(np.sum(ref[k]))
        assert abs(got["totals"].get(k, sum(
            h[k] for h in got["per_epoch"])) - want) <= DAY_GAP * abs(want), k


@pytest.mark.parametrize("case", list(CASES))
def test_bfloat16_reference_fails_a_tolerance(env, tiny_cfg, case):
    routed, objective = CASES[case]
    _, agents, _, _, _ = _setup(env, tiny_cfg, case)
    rep = C.compare_hour(env, agents, jax.random.PRNGKey(SEED % 967), TAU,
                         tiny_cfg, objective, routed,
                         dtypes=("float32", "bfloat16"))["default"]
    assert rep["float32"]["passes"], rep["float32"]
    assert not rep["bfloat16"]["passes"], rep["bfloat16"]


def _even_half(env, cfg, case):
    """The program's even players of round 1 from the uniform joint, each
    with the reference's game for ``all_gaps``."""
    ctx, agents, _, e, _ = _setup(env, cfg, case)
    d = env.er.shape[1]
    joint = jnp.full(ctx.joint_shape(), 1.0 / d, jnp.float32)
    peak = jnp.zeros((d,))
    prog, _, _ = C.program_players(agents, joint, jax.random.PRNGKey(
        SEED % 967), 0, ctx, peak, cfg, half=False)
    j3 = joint if ctx.is_routed() else joint[None]
    games = {i: (e, j3, i, TAU, peak, C.config_of(cfg), ctx.is_routed(),
                 ctx.objective, jnp.float32) for i in prog}
    return ctx, joint, peak, prog, games


@pytest.mark.parametrize("fault,caught", [("update_dropped", "update"),
                                          ("advantage_sign", "advantage")])
def test_planted_ppo_fault_fails_a_tolerance(env, tiny_cfg, fault, caught):
    from repro.core import ppo

    saved = ppo._update, ppo._gae
    with C.planted(fault):
        ctx, joint, peak, prog, games = _even_half(env, tiny_cfg, "routed")
        gaps = [C.all_gaps(p, games[i], ctx, joint, peak, tiny_cfg)
                for i, p in prog.items()]
    assert (ppo._update, ppo._gae) == saved     # gone after the block
    worst = {k: max(g[k] for g in gaps) for k in C.GAPS}
    assert worst[caught] > C.TOL[caught], worst
    if fault == "update_dropped":   # PPO left each agent as it was
        assert worst["agent"] == pytest.approx(1.0)
        assert worst["update"] == pytest.approx(1.0)


def test_float64_look_reads_float32_rounding(env, tiny_cfg):
    # at 4 DCs and one update the game is well conditioned: the float32
    # reference sits within the tolerances of the float64 one
    ctx, joint, peak, prog, games = _even_half(env, tiny_cfg, "routed")
    e, j3, _, tau, _, rcfg, routed, objective, _ = games[0]
    look = C.look_f64(prog, G.env_view(C.env_dict(env), routed), j3, tau,
                      rcfg, routed, objective, ctx, joint, peak, tiny_cfg)
    assert 0.0 < look["agent"] <= C.TOL["agent"], look
    assert look["update"] <= C.TOL["update"], look
    assert look["finals"] <= C.TOL["finals"], look
    assert 0.0 < look["std_min"] <= look["std_median"], look
