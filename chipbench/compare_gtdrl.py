"""Hold the program's GT-DRL learner to its plain reference.

``chipbench/reference_gtdrl.py`` plays the same best-response round in
plain ``jax.numpy``. For every player of round 1 of an hour (the even
half, then the odd half from the program's result of the even one), each
step of the reference is fed what the program fed its own step, and the
outputs are compared:

- ``agent``: the agent after PPO, from the agent before it, as the
  distance to the reference's agent over the reference's own step (1
  where PPO left the agent unchanged);
- ``rollout``, ``advantage`` and ``update``: each of PPO's iterations
  from the program's agent: the start states and rollouts, the
  advantages and returns of the program's rollouts, and the agent after
  an update fed the program's rollouts and advantages;
- ``logits`` and ``reward``: the 18 proposals of the program's improved
  agent and their rewards; and the polish's start, where the reference's
  two best proposals differ by more than ``TOL["reward"]`` (an argmax
  flips on one ulp where rewards tie; otherwise counted in ``skipped``);
- ``finals`` and ``reward``: the two polished rows, from the program's
  start and current row, and the four finals' rewards (``reward`` also
  holds the rollouts' rewards);
- ``row``: the chosen row, against the row of any reference final within
  ``TOL["reward"]`` of the best final reward.

Over the whole hour (``rounds`` rounds, free-running from the same agents
and key) it compares the best game value (``best``); and every agent's
actor means and critic values on a batch of states (``policy``), the
learner's batched matmuls as the rollouts run them.

Each gap is the worst over the players; ``passes`` says whether every gap
is within its tolerance. Two controls have to miss at least one: the
bfloat16 reference fed the same inputs, and the program with a fault
planted in its PPO (``PLANTS``). ``look_f64`` holds the float32 reference
to a float64 one on the host CPU, to show which gaps are float32's own.

    python chipbench/compare_gtdrl.py --seeds N[,N...] [--taus T[,T...]]
        [--plants update_dropped,advantage_sign] [--looks K] [--out PATH]

builds ``aibench16-gtdrl-day``'s inputs from each seed, deploys the agents
as the cell does, and compares each hour ``T`` of its first pool day,
solved with the key the scan engine gives that hour, from a zero peak.
It exits 0 where every float32 comparison passes and every control fails.
This comparison is not part of the cell's ``correct``, which holds the
answers to the plan-free numbers only (``chipbench/correct.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import reference as R  # noqa: E402
from chipbench import reference_gtdrl as G  # noqa: E402

# Tolerances. Each is about 3 times the float32 program's largest gap over
# 4 seeds x 3 hours on a TPU v5e, rounded up to 1, 2 or 5 times a power of
# ten, and has to stay below half the smallest reading of the controls it
# is meant to catch: the bfloat16 reference and the planted faults
# (PLANTS). ``agent`` and ``update`` miss that rule (see each); PERF.md,
# section 4, gives every reading.
TOL = {
    # the agent after PPO, from the same agent: |program - reference| over
    # |reference - agent before|, in the L2 norm over each network (actor,
    # critic); an agent PPO left unchanged reads 1. Float32 reads 0.14-0.31
    # on every seed, bfloat16 >= 1.2, the update dropped 1: the rule's 3x
    # room does not fit under half of 1, so the tolerance sits between.
    # PPO runs free here: each update's rounding moves the next rollouts.
    # The float32 reference against a float64 one reads 0.005-0.12: the
    # actor's updates are that ill-conditioned in float32 itself
    "agent": 0.5,
    # one PPO iteration fed the program's inputs to each of its steps:
    # - the start states and rollouts (states, actions, log-probabilities,
    #   values) from the same agent and start states, each over its largest
    #   magnitude (float32 <= 5.4e-6, bfloat16 >= 1.0e-2; their rewards
    #   count under "reward");
    "rollout": 2e-5,
    # - the normalised advantages and returns of the same rollouts, over
    #   their largest magnitude (<= 2.8e-7; bfloat16 >= 2.7, the sign
    #   turned 2);
    "advantage": 1e-6,
    # - the agent after the update fed the same rollouts and advantages, as
    #   "agent" measures it (float32 1.9e-3 to 9.7e-2, the actor's; the
    #   float32 reference against a float64 one up to 9.4e-2; bfloat16 >=
    #   1.1, the update dropped 1; 0.5 is half of that, not below it)
    "update": 0.5,
    # proposals (logits) of the same agent, over their largest magnitude:
    # one forward pass of the actor (<= 9.6e-8 on the chip, <= 6.3e-7 in
    # the CPU tests' noisier agents; bfloat16 >= 3.6e-3)
    "logits": 2e-6,
    # rewards of the same proposals and rollouts (an objective over its
    # value at the current joint, ~ -1), absolute (<= 6.0e-7; bfloat16 >=
    # 7.9e-3); also the margin that makes a choice between two proposals
    # unambiguous
    "reward": 2e-6,
    # polished logits from the same starts, over their largest magnitude:
    # 40 gradient steps of fixed length 0.4 on a flat objective
    # (<= 9.0e-4; bfloat16 >= 2.4)
    "finals": 5e-3,
    # the chosen row's fractions, absolute (<= 9.3e-4; bfloat16 >= 0.57)
    "row": 5e-3,
    # the hour's best game value, relative (<= 8.9e-6 over every run of
    # the script since it fed the polish the program's start; bfloat16
    # >= 1.4e-3)
    "best": 5e-5,
    # actor means and critic values of every agent on a batch of states,
    # vmapped as the rollouts run them, over their largest magnitude: the
    # learner's batched matmuls (HIGH <= 3.1e-5; one bfloat16 pass 2.6e-3,
    # the bfloat16 reference 5.8e-3)
    "policy": 1e-4,
}
GAPS = ("agent", "rollout", "advantage", "update", "logits", "reward",
        "finals", "row")
# Faults planted in the program's PPO, each of which the comparison has to
# catch: the update leaves the agent as it was (a state left unchanged),
# and the advantages enter the update with their sign turned.
PLANTS = ("update_dropped", "advantage_sign")


def config_of(cfg) -> G.Config:
    """The reference's ``Config`` from the program's ``GTDRLConfig``."""
    fields = {f: getattr(cfg.ppo, f) for f in
              ("horizon", "episodes", "iters", "update_epochs", "clip",
               "gamma", "lam", "lr", "vf_coef", "ent_coef")}
    fields.update(rounds=cfg.rounds, polish_steps=cfg.polish_steps,
                  polish_lr=cfg.polish_lr)
    return G.Config(**fields)


def plain_agent(agents, i: Optional[int] = None) -> Dict[str, Any]:
    """Player ``i`` of the program's stacked ``AgentState`` (or a single
    player's, ``i`` None), as plain dicts."""
    import jax

    take = ((lambda t: t) if i is None else
            (lambda t: jax.tree_util.tree_map(lambda x: x[i], t)))

    def opt(o):
        return {"step": take(o.step), "mu": take(o.mu), "nu": take(o.nu)}

    return {"actor": take(agents.actor), "critic": take(agents.critic),
            "actor_opt": opt(agents.actor_opt),
            "critic_opt": opt(agents.critic_opt)}


def env_dict(env) -> Dict[str, np.ndarray]:
    return {k: np.asarray(getattr(env, k)) for k in R.FIELDS}


def _f64(x) -> np.ndarray:
    return np.asarray(x, np.float64)


def _rel_gap(a, b) -> float:
    """max |a - b| over the largest magnitude of ``b``."""
    a, b = _f64(a), _f64(b)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-12))


def _update_gaps(before, after_p, after_r) -> Dict[str, float]:
    """Per network (actor, critic), |after_p - after_r| over |after_r -
    before| in the L2 norm over its leaves: 1 where the program left the
    agent as it was."""
    import jax

    flat = lambda t: np.concatenate([_f64(x).reshape(-1)
                                     for x in jax.tree_util.tree_leaves(t)])
    gaps = {}
    for net in ("actor", "critic"):
        b, p, r = (flat(t[net]) for t in (before, after_p, after_r))
        gaps[net] = float(np.linalg.norm(p - r)
                          / max(np.linalg.norm(r - b), 1e-30))
    return gaps


def _update_gap(before, after_p, after_r) -> float:
    """The worse network's ``_update_gaps``."""
    return max(_update_gaps(before, after_p, after_r).values())


def _top2_gap(rewards) -> float:
    r = np.sort(_f64(rewards))
    return float(r[-1] - r[-2])


@functools.lru_cache(maxsize=None)
def _program():
    """The program's learner steps, jitted once per process (fresh wrappers,
    so that a planted fault is traced anew)."""
    import jax

    from repro.core import gt_drl
    from repro.core import networks as nets
    from repro.core import ppo as P

    def policy(actor, critic, states):
        per = lambda f, p: jax.vmap(lambda x: f(p, x))(states)
        return (jax.vmap(lambda a: per(nets.actor_mean, a))(actor),
                jax.vmap(lambda c: per(nets.critic_value, c))(critic))

    def ppo_iter(k1, k2, agent, env, tau, objective, peak, joint, i, cfg):
        # one iteration of ``ppo.ppo_improve`` for player i of ``joint``:
        # its start states, rollouts, advantages and returns, new agent
        reward_of, state_of, state0_fn = gt_drl._player_game(
            env, tau, objective, peak, joint, i, cfg.state_mode,
            cfg.ppo.episodes)
        s0 = state0_fn(k2)
        ro = P._rollout(k1, agent, s0, state_of, reward_of, cfg.ppo)
        adv, ret = P._gae(ro, cfg.ppo)
        return s0, ro, adv, ret, P._update(agent, ro, adv, ret, cfg.ppo)[0]

    return {"players": jax.jit(lambda *a: gt_drl._run_players(*a),
                               static_argnums=(5, 8)),
            "half": jax.jit(lambda *a: gt_drl.half_update(*a),
                            static_argnums=(3, 6)),
            "epoch": jax.jit(lambda *a: gt_drl.solve_epoch(*a),
                             static_argnums=(4,)),
            "ppo_iter": jax.jit(ppo_iter, static_argnums=(5, 9)),
            "policy": jax.jit(policy)}


@contextlib.contextmanager
def planted(fault: str):
    """The program's PPO broken as ``fault`` (one of ``PLANTS``) says,
    inside the block."""
    import jax.numpy as jnp

    from repro.core import ppo as P

    saved = P._update, P._gae
    if fault == "update_dropped":
        zero = jnp.zeros((), jnp.float32)
        P._update = lambda agent, ro, adv, ret, cfg: (agent, {
            "actor_loss": zero, "critic_loss": zero})
    elif fault == "advantage_sign":
        def flipped(ro, cfg):
            adv, ret = saved[1](ro, cfg)
            return -adv, ret
        P._gae = flipped
    else:
        raise ValueError(f"no planted fault {fault!r}")
    _program.cache_clear()
    try:
        yield
    finally:
        P._update, P._gae = saved
        _program.cache_clear()


def policy_gap(agents, states, dt) -> float:
    """Worst gap of every agent's actor means and critic values on
    ``states`` (episodes, state dim): the program's, vmapped over players
    and episodes as its rollouts run them, against the reference's plain
    batched matmuls in ``dt``."""
    import jax

    mu, v = _program()["policy"](agents.actor, agents.critic, states)
    gaps = []
    for i in range(mu.shape[0]):
        a = G.as_dtype(plain_agent(agents, i), dt)
        s = G.as_dtype(states, dt)
        with jax.default_matmul_precision("highest"):
            gaps.append(_rel_gap(mu[i], G.mlp(a["actor"]["mlp"], s)))
            gaps.append(_rel_gap(v[i], G.mlp(a["critic"], s)[:, 0]))
    return max(gaps)


def program_players(agents, joint, key_r, parity: int, ctx, peak, cfg,
                    half: bool = True):
    """The program's players of one parity, as its ``half_update`` runs
    them: per player its key, its agent before (``state_in``, and as
    plain dicts ``agent_in``) and after PPO (``agent``: actor and critic),
    its proposals, finals and ``row``; and, unless ``half`` is false, the
    agents and joint ``half_update`` leaves."""
    import jax
    import jax.numpy as jnp

    i_n = ctx.num_players()
    keys = jax.random.split(key_r, i_n)
    idx = jnp.arange(parity, i_n, 2)
    sub = jax.tree_util.tree_map(lambda x: x[idx], agents)
    prog = _program()
    new, rows, info = prog["players"](keys[idx], sub, idx, ctx.env, ctx.tau,
                                      ctx.objective, peak, joint, cfg)
    new_agents, new_joint = (prog["half"](agents, joint, key_r, parity, ctx,
                                          peak, cfg) if half else (None, None))
    take_i = lambda t, i: jax.tree_util.tree_map(lambda x: x[i], t)
    out = {}
    for n, i in enumerate(range(parity, i_n, 2)):
        take = lambda t: jax.tree_util.tree_map(lambda x: x[n], t)
        row = rows[n]
        out[i] = {"key": keys[i], "state_in": take_i(agents, i),
                  "agent_in": plain_agent(agents, i),
                  "agent": {"actor": take(new.actor),
                            "critic": take(new.critic)},
                  "row": row if row.ndim == 2 else row[None],
                  **{k: v[n] for k, v in info.items()}}
    return out, new_agents, new_joint


def ppo_gaps(prog: Dict[str, Any], game: tuple, ctx, joint, peak, cfg
             ) -> Dict[str, float]:
    """One player's PPO, iteration by iteration from the program's agent:
    the program's iteration (``ppo_iter``), and each reference step fed
    what the program fed its own (start states, rollouts, advantages).
    ``joint`` and ``peak`` are the program's; ``game`` is as in
    ``player_gaps``."""
    import jax

    i, dt, rcfg = game[2], game[-1], game[5]
    k_ppo, _ = jax.random.split(prog["key"])
    agent = prog["state_in"]
    it = _program()["ppo_iter"]
    out = {"rollout": 0.0, "advantage": 0.0, "update": 0.0, "reward": 0.0,
           "update_actor": 0.0, "update_critic": 0.0}
    for key_i in jax.random.split(k_ppo, cfg.ppo.iters):
        k1, k2 = jax.random.split(key_i)
        s0, ro, adv, ret, new = it(k1, k2, agent, ctx.env, ctx.tau,
                                   ctx.objective, peak, joint, i, cfg)
        plain = plain_agent(agent)
        ref = G.rollout_stage(k1, G.as_dtype(plain, dt), G.as_dtype(s0, dt),
                              *game)
        out["rollout"] = max(
            out["rollout"], _rel_gap(s0, G.starts(k2, *game)),
            *(_rel_gap(p, r) for p, r in zip(
                (ro.states, ro.actions, ro.logps, ro.values),
                (ref[0], ref[1], ref[2], ref[4]))))
        out["reward"] = max(out["reward"], float(np.max(np.abs(
            _f64(ro.rewards) - _f64(ref[3])))))
        adv_r, ret_r = G.gae_stage(G.as_dtype(ro.rewards, dt),
                                   G.as_dtype(ro.values, dt), rcfg)
        out["advantage"] = max(out["advantage"], _rel_gap(adv, adv_r),
                               _rel_gap(ret, ret_r))
        upd = G.update_stage(G.as_dtype(plain, dt), *G.as_dtype(
            (ro.states, ro.actions, ro.logps, adv, ret), dt), rcfg)
        for net, v in _update_gaps(plain, plain_agent(new), upd).items():
            out[f"update_{net}"] = max(out[f"update_{net}"], v)
        out["update"] = max(out["update_actor"], out["update_critic"])
        agent = new
    return out


def player_gaps(prog: Dict[str, Any], game: tuple) -> Dict[str, Any]:
    """One player's gaps: each reference step fed the program's inputs to
    that step. ``game`` is ``(e, joint, i, tau, peak, cfg, routed,
    objective, dt)`` as the reference takes them."""
    import jax

    dt = game[-1]
    k_ppo, k_cand = jax.random.split(prog["key"])
    ref = G.ppo_stage(k_ppo, G.as_dtype(prog["agent_in"], dt), *game)
    agent = _update_gap(prog["agent_in"], prog["agent"], ref)
    improved = {"actor": G.as_dtype(prog["agent"]["actor"], dt)}
    cand, cand_rewards = G.propose(k_cand, improved, *game)
    out = {"agent": agent, "skipped": 0,
           "logits": _rel_gap(prog["cand_logits"], cand),
           "reward": float(np.max(np.abs(_f64(prog["cand_rewards"])
                                         - _f64(cand_rewards))))}
    if _top2_gap(cand_rewards) > TOL["reward"]:
        best = _f64(cand)[int(np.argmax(_f64(cand_rewards)))]
        out["logits"] = max(out["logits"],
                            _rel_gap(_f64(prog["finals"])[2], best))
    else:
        out["skipped"] += 1
    finals, final_rewards, _ = G.polish_select(
        G.as_dtype(prog["finals"][2], dt), *game)
    out["finals"] = _rel_gap(_f64(prog["finals"])[:2], _f64(finals)[:2])
    rr = _f64(final_rewards)
    out["reward"] = max(out["reward"], float(np.max(np.abs(
        _f64(prog["final_rewards"]) - rr))))
    near = [k for k in range(len(rr)) if rr.max() - rr[k] <= TOL["reward"]]
    s_n = game[1].shape[0]
    row_p = _f64(prog["row"]).reshape(-1)
    out["row"] = min(float(np.max(np.abs(row_p - _f64(
        G._softmax_rows(finals[k], s_n)).reshape(-1)))) for k in near)
    return out


def all_gaps(prog: Dict[str, Any], game: tuple, ctx, joint, peak, cfg
             ) -> Dict[str, Any]:
    """``player_gaps`` and ``ppo_gaps`` of one player, in one dict."""
    g = player_gaps(prog, game)
    g_ppo = ppo_gaps(prog, game, ctx, joint, peak, cfg)
    g["reward"] = max(g["reward"], g_ppo.pop("reward"))
    return {**g, **g_ppo}


def look_f64(half: Dict[int, Dict[str, Any]], env_np, joint3, tau: int,
             rcfg, routed: bool, objective: str, ctx, joint, peak, cfg
             ) -> Dict[str, float]:
    """The reference against itself: float32 against float64, both on the
    host CPU from the program's inputs to each player of ``half``: the
    agent after PPO (``agent``, as ``_update_gap`` measures it), the agent
    after PPO's first update fed the program's first rollouts and
    advantages (``update``), and the polished logits from the program's
    start (``finals``); with the policy's smallest and median standard
    deviation (``std_min``, ``std_median``). Where these read as the
    program does against the float32 reference, the gap is float32's own
    conditioning, not the program's. ``ctx``, ``joint``, ``peak`` and
    ``cfg`` are the program's."""
    import jax
    import jax.numpy as jnp

    host = lambda t: jax.tree_util.tree_map(np.asarray, t)
    first = {}
    for i, p in half.items():
        k_ppo, _ = jax.random.split(p["key"])
        k1, k2 = jax.random.split(jax.random.split(k_ppo, cfg.ppo.iters)[0])
        _, ro, adv, ret, _ = _program()["ppo_iter"](
            k1, k2, p["state_in"], ctx.env, ctx.tau, ctx.objective, peak,
            joint, i, cfg)
        first[i] = host((ro.states, ro.actions, ro.logps, adv, ret,
                         ro.values[:, :-1]))
    res = {}
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        for name in ("float32", "float64"):
            dt = jnp.dtype(name)
            e = G.as_dtype({k: jnp.asarray(v) for k, v in env_np.items()}, dt)
            j3 = jnp.asarray(np.asarray(joint3), dt)
            peak_dt = jnp.zeros((j3.shape[-1],), dt)
            for i, p in half.items():
                game = (e, j3, i, tau, peak_dt, rcfg, routed, objective, dt)
                k_ppo, _ = jax.random.split(jnp.asarray(np.asarray(p["key"])))
                before = G.as_dtype(host(p["agent_in"]), dt)
                agent = G.ppo_stage(k_ppo, before, *game)
                upd = G.update_stage(before, *G.as_dtype(first[i][:5], dt),
                                     rcfg)
                finals = G.polish_select(
                    jnp.asarray(np.asarray(p["finals"][2]), dt), *game)[0]
                res[name, i] = (host(agent), host(upd), np.asarray(finals))
    out = {f"{g}_{net}": 0.0 for g in ("agent", "update")
           for net in ("actor", "critic")}
    out["finals"] = 0.0
    stds, resid = [], []
    for i, p in half.items():
        (a32, u32, f32), (a64, u64, f64) = res["float32", i], res["float64", i]
        before = host(p["agent_in"])
        for g, (x32, x64) in (("agent", (a32, a64)), ("update", (u32, u64))):
            for net, v in _update_gaps(before, x32, x64).items():
                out[f"{g}_{net}"] = max(out[f"{g}_{net}"], v)
        out["finals"] = max(out["finals"], _rel_gap(f32[:2], f64[:2]))
        stds.append(np.exp(np.clip(_f64(before["actor"]["log_std"]),
                                   *G.LOG_STD)))
        # the critic's error on its first rollouts, over the returns
        values, ret = _f64(first[i][5]), _f64(first[i][4])
        resid.append(np.abs(values - ret) / np.maximum(np.abs(ret), 1e-30))
    out["agent"] = max(out["agent_actor"], out["agent_critic"])
    out["update"] = max(out["update_actor"], out["update_critic"])
    stds, resid = np.concatenate(stds), np.concatenate(
        [r.reshape(-1) for r in resid])
    out.update(std_min=float(stds.min()), std_median=float(np.median(stds)),
               critic_residual_median=float(np.median(resid)))
    return out


def _precision(name: str):
    """The matmul precision the program's side runs under: ``default`` is
    the program as it stands."""
    import jax

    if name == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(name)


def compare_hour(env, agents, key, tau: int, cfg, objective: str,
                 routed: bool, dtypes=("float32", "bfloat16"),
                 precisions=("default",), epoch_dtypes=None,
                 look: bool = False) -> Dict[str, Any]:
    """Round 1 of hour ``tau``, player by player, and the hour's best game
    value: the program, at each matmul precision of ``precisions``,
    against the reference in each of ``dtypes`` (the best value in each of
    ``epoch_dtypes``, by default ``dtypes``; none, and the program's epoch
    is not run). ``key`` is the hour's solver key; the peak starts at 0.
    Returns ``report[precision][dtype]``, and with ``look`` the float32
    reference against the float64 one on the round's even half
    (``report["look"]``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.game import GameContext

    ctx = GameContext(env=env, tau=jnp.int32(tau), objective=objective,
                      routed=routed)
    i_n, d = env.er.shape
    peak = jnp.zeros((d,), jnp.float32)
    routed_game = ctx.is_routed()
    joint0 = jnp.full(ctx.joint_shape(), 1.0 / d, jnp.float32)
    rcfg = config_of(cfg)
    env_np = G.env_view(env_dict(env), routed_game)
    k1, k2 = jax.random.split(jax.random.split(key, cfg.rounds)[0])
    as3 = (lambda j: j) if routed_game else (lambda j: j[None])
    envs = {name: G.as_dtype({k: jnp.asarray(v) for k, v in env_np.items()},
                             jnp.dtype(name)) for name in dtypes}
    epoch_dtypes = dtypes if epoch_dtypes is None else epoch_dtypes

    best_ref = {}
    for name in epoch_dtypes:
        dt = jnp.dtype(name)
        plain = [G.as_dtype(plain_agent(agents, i), dt) for i in range(i_n)]
        best_ref[name] = G.solve_epoch(key, plain, envs[name], tau,
                                       peak.astype(dt), rcfg, routed_game,
                                       objective, dt)["best"]

    # a batch of states like the rollouts' starts: Dirichlet rows
    states = jax.random.dirichlet(
        jax.random.PRNGKey(tau), jnp.ones((cfg.ppo.episodes,)
                                          + ctx.joint_shape()[:-2] + (d,)))
    states = states.reshape(cfg.ppo.episodes, -1)
    report: Dict[str, Any] = {"tau": tau, "tolerances": dict(TOL)}
    for prec in precisions:
        with _precision(prec):
            policy = {name: policy_gap(agents, states, jnp.dtype(name))
                      for name in dtypes}
            red, agents_mid, joint_mid = program_players(
                agents, joint0, k1, 0, ctx, peak, cfg)
            black, _, _ = program_players(agents_mid, joint_mid, k2, 1, ctx,
                                          peak, cfg)
            best_prog = (float(_program()["epoch"](key, agents, ctx, peak,
                                                   cfg)[1].info["best"])
                         if best_ref else None)
            report[prec] = {}
            for name in dtypes:
                dt = jnp.dtype(name)
                gaps = []
                for joint, half in ((joint0, red), (joint_mid, black)):
                    j3 = as3(joint).astype(dt)
                    gaps += [all_gaps(p, (envs[name], j3, i, tau,
                                          peak.astype(dt), rcfg, routed_game,
                                          objective, dt), ctx, joint, peak,
                                      cfg) for i, p in half.items()]
                out = {k: max(g[k] for g in gaps)
                       for k in GAPS + ("update_actor", "update_critic")}
                out["policy"] = policy[name]
                out["skipped"] = sum(g["skipped"] for g in gaps)
                if name in best_ref:
                    out["best"] = abs(best_prog - best_ref[name]) / max(
                        abs(best_ref[name]), 1e-30)
                    out["best_values"] = [best_prog, best_ref[name]]
                out["passes"] = all(out[k] <= TOL[k] for k in TOL if k in out)
                report[prec][name] = out
    if look:
        report["look"] = look_f64(red, env_np, as3(joint0), tau, rcfg,
                                  routed_game, objective, ctx, joint0, peak,
                                  cfg)
    return report


def hour_key(seed: int, tau: int):
    """The solver key the scan engine gives hour ``tau`` of a day run with
    ``seed``."""
    import jax

    key = jax.random.split(jax.random.PRNGKey(seed))[1]
    for _ in range(tau + 1):
        key, ks = jax.random.split(key)
    return ks


def _summary(runs, plants) -> Dict[str, Any]:
    """Per gap, the largest float32 reading and the smallest bfloat16 one
    over ``runs``; what each planted fault broke; the looks."""
    out: Dict[str, Any] = {"runs": len(runs), "tolerances": dict(TOL)}
    for prec in runs[0]["precisions"] if runs else ():
        for name, pick in (("float32", max), ("bfloat16", min)):
            rows = [r[prec][name] for r in runs if name in r.get(prec, {})]
            if rows:
                out[f"{prec}.{name}"] = {
                    k: pick(r[k] for r in rows) for k in TOL
                    if all(k in r for r in rows)}
                out[f"{prec}.{name}"]["passes"] = [r["passes"] for r in rows]
    out["plants"] = {f: [k for k in TOL if k in rep and rep[k] > TOL[k]]
                     for f, rep in plants.items()}
    out["looks"] = [dict(r["look"], seed=r["seed"], tau=r["tau"])
                    for r in runs if "look" in r]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="aibench16-gtdrl-day")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--taus", default="12", help="comma-separated hours")
    ap.add_argument("--dtypes", default="float32,bfloat16")
    ap.add_argument("--epoch-dtypes", default="float32",
                    help="the reference dtypes whose hour runs free (best)")
    ap.add_argument("--precisions", default="default",
                    help="the program's matmul precisions, comma-separated")
    ap.add_argument("--plants", default="",
                    help="planted faults run at the first seed and hour")
    ap.add_argument("--looks", type=int, default=0,
                    help="seeds whose first hour also runs the float64 look")
    ap.add_argument("--out", default=None,
                    help="a JSON-lines file: one line a comparison, then "
                         "the summary")
    args = ap.parse_args(argv)

    import jax

    from chipbench.manifest import Manifest
    from chipbench.traffic import Caller, Inputs

    m = Manifest(ROOT)
    cell = m.cell(args.workload)
    precisions = tuple(args.precisions.split(","))
    dtypes = tuple(args.dtypes.split(","))
    taus = [int(t) for t in args.taus.split(",")]
    plants = [f for f in args.plants.split(",") if f]
    epoch_dtypes = tuple(d for d in args.epoch_dtypes.split(",") if d)
    sink = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        sink = open(args.out, "a")

    def emit(obj):
        text = json.dumps(obj)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()

    runs, planted_reps = [], {}
    for n, seed in enumerate(int(x) for x in args.seeds.split(",")):
        inputs = Inputs(m.config(cell["config"]), m.traffic(cell["traffic"]),
                        seed)
        caller = Caller(inputs)
        caller.deploy()
        spec = caller.spec
        for t, tau in enumerate(taus):
            t0 = time.perf_counter()
            rep = compare_hour(caller.envs[0], caller.state0,
                               hour_key(inputs.seed_of(0), tau), tau,
                               spec.cfg, spec.objective, spec.routed, dtypes,
                               precisions, epoch_dtypes,
                               look=t == 0 and n < args.looks)
            rep.update(seed=seed, precisions=list(precisions),
                       device=jax.devices()[0].device_kind,
                       seconds=time.perf_counter() - t0)
            runs.append(rep)
            emit(rep)
            if n == 0 and t == 0:
                for fault in plants:
                    with planted(fault):
                        got = compare_hour(
                            caller.envs[0], caller.state0,
                            hour_key(inputs.seed_of(0), tau), tau, spec.cfg,
                            spec.objective, spec.routed, ("float32",),
                            ("default",), epoch_dtypes=())
                    planted_reps[fault] = got["default"]["float32"]
                    emit({"plant": fault, "seed": seed, "tau": tau,
                          **planted_reps[fault]})
        caller.close()
    summary = _summary(runs, planted_reps)
    emit({"summary": summary})
    ok = (all(r[p]["float32"]["passes"] for r in runs for p in precisions)
          and not any(r[p][d]["passes"] for r in runs for p in precisions
                      for d in dtypes if d != "float32")
          and all(not rep["passes"] for rep in planted_reps.values()))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
