"""Plain reference of GT-DRL's best-response rounds (arXiv:2404.01459 §5.3).

It imports nothing of the program; the game objective comes from
``chipbench.reference``. Arrays are plain dicts (the env's field names, and
per player ``{"actor", "critic", "actor_opt", "critic_opt"}`` with the
optimizer state as ``{"step", "mu", "nu"}``), and every function takes a
dtype: float32 is the reference, computed under
``jax.default_matmul_precision("highest")``, and bfloat16 its control.
Players run one after another in a Python loop; episodes and candidates
are explicit batch axes or ``lax.map`` loops, never ``vmap``, and no
gather: a player's row is read and written through a mask.

One player's round (``player_round``), in three steps that can each be
fed the program's own inputs (``ppo_stage``, ``propose``,
``polish_select``; and within PPO, one iteration's ``starts``,
``rollout_stage``, ``gae_stage`` and ``update_stage``):

1. PPO improve, ``iters`` times: ``episodes`` rollouts of ``horizon``
   steps from Dirichlet-jittered copies of the player's row (state: the
   row's fractions; action: Gaussian logits; reward: minus the player's
   objective with its row replaced, over the objective at the current
   joint), GAE, then ``update_epochs`` steps of the clipped surrogate and
   the value loss, each through AdamW with its global-norm clip.
2. Candidates: the policy mean, the current row, and 16 samples around
   the mean; the best by reward starts the polish.
3. Polish: ``polish_steps`` normalised gradient steps on the reward, from
   the best candidate and from the current row.
4. Select: the best of the two polished rows and their two starts.

A round (two calls of ``half``) plays the even players against the
current joint, then the odd players against the joint the even half
left, each player with its own key ``split(key_r, I)[i]``. ``solve_epoch`` plays ``rounds``
rounds from the uniform joint and keeps the best game value; a round
whose joint or value is not finite is rewound, as the program does.
``play_day`` plays a day from the scan engine's key discipline, carrying
the agents and the monthly peak from hour to hour, and scores each hour's
best joint with the detailed epoch model (``reference.simulate``).

Random numbers come from the same key splits as the program and are drawn
in float32 whatever the dtype (the bfloat16 control casts them, and so
does a float64 copy computed with x64 on).

Departures from the paper's text and from the program's configuration:

- ``state_mode="strategy"`` only (the paper's, and the cell's); the
  program's ``"env"`` state is not covered.
- ``GTDRLConfig.damping`` is read by no code of the program: a round
  blends nothing of the old joint, so neither does this one.
- The paper gives no network or PPO sizes; they are the program's
  defaults, passed in as a ``Config``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R

N_CAND = 16                 # sampled proposals around the policy mean
LOG_STD = (-4.0, 1.0)       # clip of the policy's log standard deviation
ADAM = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "grad_clip": 1.0}
ROW_EPS = 1e-9              # log of a row: log(f + 1e-9)
BASE_EPS = 1e-6             # reward scale: |objective_i| + 1e-6
JITTER = (20.0, 0.5)        # Dirichlet alpha of an episode's start: 20 f + 0.5


@dataclasses.dataclass(frozen=True)
class Config:
    """The learner's sizes and rates (the program's ``GTDRLConfig`` and
    ``PPOConfig`` fields of the same names)."""
    horizon: int = 6
    episodes: int = 32
    iters: int = 4
    update_epochs: int = 4
    clip: float = 0.2
    gamma: float = 0.9
    lam: float = 0.95
    lr: float = 3e-3
    vf_coef: float = 0.5
    ent_coef: float = 1e-3
    rounds: int = 8
    polish_steps: int = 40
    polish_lr: float = 0.4


# ---------------------------------------------------------------------------
# the game: one player's objective
# ---------------------------------------------------------------------------

def env_view(env: Mapping[str, np.ndarray], routed: bool
             ) -> Dict[str, np.ndarray]:
    """The env as the game sees it: an unrouted game is one source whose
    requests pay the DCs' mean access RTT."""
    env = {k: np.asarray(env[k], np.float64) for k in R.FIELDS}
    if routed:
        return env
    i_n = env["er"].shape[0]
    return {**env, "origin": np.ones((1, i_n, env["origin"].shape[2])),
            "rtt": env["rtt"].mean(axis=0, keepdims=True)}


def player_costs(e, fr3, tau, peak, objective: str):
    """(I,) each player's objective of the joint (S, I, D): its load share
    of carbon (eq. 12), or of energy and the peak charge plus its network
    bill (eq. 17), plus its SLA-miss cost for ``cost_sla``."""
    ar3 = R.place(e, fr3, tau)
    ar = jnp.sum(ar3, axis=0)
    frac = ar / jnp.maximum(R._cap(e, tau), R.EPS)
    share = frac / jnp.maximum(jnp.sum(frac, axis=0), R.EPS)[None, :]
    dp = R._power(e, ar, tau)
    dpe = dp[None, :] * share
    if objective == "carbon":
        return jnp.sum(e["carbon"][:, tau][None, :] * dpe, axis=1) / R.W_PER_KW
    a = jnp.where(dpe > 0, 1.0, e["alpha"][None, :])
    energy = e["eprice"][:, tau][None, :] * a * dpe / R.W_PER_KW
    new_peak = jnp.maximum(peak, jnp.maximum(dp, 0.0))
    delta = e["peak_price"] * (new_peak - peak) / R.W_PER_KW
    net = e["nprice"] * e["sizes"][:, None] * ar
    cct = jnp.sum(energy + delta[None, :] * share + net, axis=1)
    if objective == "cost":
        return cct
    if objective == "cost_sla":
        return cct + e["sla_weight"] * jnp.sum(R._sla3(e, ar3, tau),
                                               axis=(0, 2))
    raise ValueError(f"the reference has no objective {objective!r}")


def _mask(n: int, i):
    return jnp.arange(n) == i


def own_row(joint, i):
    """Player ``i``'s (S, D) rows of the joint."""
    m = _mask(joint.shape[1], i)[None, :, None]
    return jnp.sum(jnp.where(m, joint, 0.0), axis=1)


def with_row(joint, i, row):
    """The joint with player ``i``'s (S, D) rows replaced."""
    m = _mask(joint.shape[1], i)[None, :, None]
    return jnp.where(m, row[:, None, :], joint)


def _pick(xs, idx):
    """``xs[idx]`` through a mask."""
    m = _mask(xs.shape[0], idx).reshape((-1,) + (1,) * (xs.ndim - 1))
    return jnp.sum(jnp.where(m, xs, 0.0), axis=0)


def _softmax_rows(logits, s: int):
    """Flat logits -> (S, D) rows of fractions."""
    return jax.nn.softmax(logits.reshape(s, -1), axis=-1)


# ---------------------------------------------------------------------------
# actor, critic and AdamW
# ---------------------------------------------------------------------------

def mlp(p, x):
    """``tanh`` hidden layers, linear output; ``x`` is (..., in)."""
    n = len(p) // 2
    for li in range(n):
        x = x @ p[f"w{li}"] + p[f"b{li}"]
        if li < n - 1:
            x = jnp.tanh(x)
    return x


def policy_std(actor):
    return jnp.exp(jnp.clip(actor["log_std"], *LOG_STD))


def gaussian_logp(x, mu, std):
    z = (x - mu) / std
    return jnp.sum(-0.5 * z * z - jnp.log(std) - 0.5 * math.log(2 * math.pi),
                   axis=-1)


def adamw(params, grads, opt, lr):
    """One AdamW step (no weight decay) after clipping the gradient's
    global norm to ``ADAM["grad_clip"]``."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, ADAM["grad_clip"] / (gnorm + 1e-9))
    step = opt["step"] + 1
    b1, b2 = ADAM["b1"], ADAM["b2"]
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def leaf(p, g, m, v):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / bc1.astype(p.dtype)) / (jnp.sqrt(v / bc2.astype(p.dtype))
                                           + ADAM["eps"])
        return p - lr * upd, m, v

    out = jax.tree_util.tree_map(leaf, params, grads, opt["mu"], opt["nu"])
    is_out = lambda x: isinstance(x, tuple)
    pick = lambda k: jax.tree_util.tree_map(lambda o: o[k], out,
                                            is_leaf=is_out)
    return pick(0), {"step": step, "mu": pick(1), "nu": pick(2)}


# ---------------------------------------------------------------------------
# PPO
# ---------------------------------------------------------------------------

def _normal(keys, n: int, dt):
    """One (n,) standard normal draw per key, in float32, cast to ``dt``."""
    return jax.lax.map(lambda k: jax.random.normal(k, (n,), jnp.float32),
                       keys).astype(dt)


def rollout(key, agent, s0, reward_of, state_of, cfg: Config, dt):
    """``episodes`` x ``horizon`` steps of the Gaussian-logit policy."""
    actor, critic = agent["actor"], agent["critic"]
    std = policy_std(actor)
    b = s0.shape[0]
    keys = jax.random.split(key, cfg.horizon)
    s = s0
    states, actions, logps, rewards, values = [], [], [], [], []
    for t in range(cfg.horizon):
        mu = mlp(actor["mlp"], s)
        logits = mu + std * _normal(jax.random.split(keys[t], b),
                                    mu.shape[1], dt)
        states.append(s)
        actions.append(logits)
        logps.append(gaussian_logp(logits, mu, std))
        rewards.append(jax.lax.map(reward_of, logits))
        values.append(mlp(critic, s)[:, 0])
        s = jax.lax.map(state_of, logits)
    values.append(mlp(critic, s)[:, 0])
    st = lambda xs: jnp.stack(xs, axis=1)      # (B, T, ...)
    return st(states), st(actions), st(logps), st(rewards), st(values)


def gae(rewards, values, cfg: Config):
    """Normalised advantages and returns, (B, T) each."""
    t_n = rewards.shape[1]
    deltas = rewards + cfg.gamma * values[:, 1:] - values[:, :-1]
    adv = [None] * t_n
    run = jnp.zeros_like(deltas[:, 0])
    for t in reversed(range(t_n)):
        run = deltas[:, t] + cfg.gamma * cfg.lam * run
        adv[t] = run
    adv = jnp.stack(adv, axis=1)
    returns = adv + values[:, :-1]
    adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
    return adv, returns


def update(agent, states, actions, logps, adv, returns, cfg: Config):
    """``update_epochs`` full-batch steps of the clipped surrogate (actor)
    and the value loss (critic)."""
    s = states.reshape(-1, states.shape[-1])
    a = actions.reshape(-1, actions.shape[-1])
    lp_old, adv, ret = logps.reshape(-1), adv.reshape(-1), returns.reshape(-1)

    def actor_loss(actor):
        mu = mlp(actor["mlp"], s)
        lp = gaussian_logp(a, mu, policy_std(actor))
        ratio = jnp.exp(lp - lp_old)
        clipped = jnp.clip(ratio, 1 - cfg.clip, 1 + cfg.clip) * adv
        ent = jnp.sum(jnp.clip(actor["log_std"], *LOG_STD))
        return (-jnp.mean(jnp.minimum(ratio * adv, clipped))
                - cfg.ent_coef * ent)

    def critic_loss(critic):
        return cfg.vf_coef * jnp.mean((mlp(critic, s)[:, 0] - ret) ** 2)

    agent = dict(agent)
    for _ in range(cfg.update_epochs):
        ga = jax.grad(actor_loss)(agent["actor"])
        agent["actor"], agent["actor_opt"] = adamw(
            agent["actor"], ga, agent["actor_opt"], cfg.lr)
        gc = jax.grad(critic_loss)(agent["critic"])
        agent["critic"], agent["critic_opt"] = adamw(
            agent["critic"], gc, agent["critic_opt"], cfg.lr)
    return agent


def ppo_improve(key, agent, state0, reward_of, state_of, cfg: Config, dt):
    """``iters`` x (rollout, GAE, update)."""
    for key_i in jax.random.split(key, cfg.iters):
        k1, k2 = jax.random.split(key_i)
        states, actions, logps, rewards, values = rollout(
            k1, agent, state0(k2), reward_of, state_of, cfg, dt)
        adv, ret = gae(rewards, values, cfg)
        agent = update(agent, states, actions, logps, adv, ret, cfg)
    return agent


# ---------------------------------------------------------------------------
# one player's round, a round, an epoch
# ---------------------------------------------------------------------------

class _Game:
    """Player ``i``'s view of the joint: its reward of a flat logit row
    (minus its objective with the row replaced, over the objective at the
    joint), its state of a logit row (the row's fractions), and its
    current row as logits."""

    def __init__(self, e, joint, i, tau, peak, objective: str):
        self.s_n = joint.shape[0]
        self.own = own_row(joint, i)
        self.own_logits = jnp.log(self.own + ROW_EPS).reshape(-1)
        base = jnp.abs(_pick(player_costs(e, joint, tau, peak, objective),
                             i)) + BASE_EPS

        def reward_of(logits):
            fr = with_row(joint, i, _softmax_rows(logits, self.s_n))
            return -_pick(player_costs(e, fr, tau, peak, objective), i) / base

        self.reward_of = reward_of

    def state_of(self, logits):
        return _softmax_rows(logits, self.s_n).reshape(-1)


def _starts(key, e, joint, i, tau, peak, cfg: Config, routed: bool,
            objective: str, dt):
    """The ``episodes`` start states of one PPO iteration: Dirichlet-
    jittered copies of the player's row (``key`` is the iteration key's
    second split)."""
    alpha = own_row(joint, i) * JITTER[0] + JITTER[1]
    if not routed:
        alpha = alpha[0]
    fr = jax.random.dirichlet(key, jnp.broadcast_to(
        alpha.astype(jnp.float32), (cfg.episodes,) + alpha.shape),
        dtype=jnp.float32)
    return fr.reshape(cfg.episodes, -1).astype(dt)


def _ppo_stage(key, agent, e, joint, i, tau, peak, cfg: Config,
               routed: bool, objective: str, dt):
    """Step 1: the agent after PPO (``key`` is the player key's first
    split)."""
    g = _Game(e, joint, i, tau, peak, objective)
    state0 = lambda k: _starts(k, e, joint, i, tau, peak, cfg, routed,
                               objective, dt)
    return ppo_improve(key, agent, state0, g.reward_of, g.state_of, cfg, dt)


def _rollout_stage(key, agent, s0, e, joint, i, tau, peak, cfg: Config,
                   routed: bool, objective: str, dt):
    """One PPO iteration's rollouts from the start states ``s0`` (``key``
    is the iteration key's first split)."""
    g = _Game(e, joint, i, tau, peak, objective)
    return rollout(key, agent, s0, g.reward_of, g.state_of, cfg, dt)


def _propose(key, agent, e, joint, i, tau, peak, cfg: Config,
             routed: bool, objective: str, dt):
    """Step 2: the 18 proposals (policy mean, current row, 16 samples)
    and their rewards (``key`` is the player key's second split)."""
    g = _Game(e, joint, i, tau, peak, objective)
    mu = mlp(agent["actor"]["mlp"], g.state_of(g.own_logits))
    eps = jax.random.normal(key, (N_CAND, mu.shape[0]),
                            jnp.float32).astype(dt)
    cand = jnp.concatenate([mu[None], g.own_logits[None],
                            mu[None] + policy_std(agent["actor"]) * eps])
    return cand, jax.lax.map(g.reward_of, cand)


def _polish_select(start, e, joint, i, tau, peak, cfg: Config,
                   routed: bool, objective: str, dt):
    """Steps 3 and 4: polish ``start`` and the current row, and choose the
    best of the two and their starts. Returns the four finals, their
    rewards and the chosen (S, D) row."""
    g = _Game(e, joint, i, tau, peak, objective)

    def polish(logits, _):
        gr = jax.grad(lambda lg: -g.reward_of(lg))(logits)
        return logits - cfg.polish_lr * gr / (jnp.sqrt(jnp.sum(gr * gr))
                                              + 1e-9), None

    starts = [start, g.own_logits]
    polished = [jax.lax.scan(polish, x, None, length=cfg.polish_steps)[0]
                for x in starts]
    finals = jnp.stack(polished + starts)
    final_rewards = jax.lax.map(g.reward_of, finals)
    row = _softmax_rows(_pick(finals, jnp.argmax(final_rewards)), g.s_n)
    return finals, final_rewards, row


_ppo_stage_jit = jax.jit(_ppo_stage, static_argnums=(7, 8, 9, 10))
_starts_jit = jax.jit(_starts, static_argnums=(6, 7, 8, 9))
_rollout_stage_jit = jax.jit(_rollout_stage, static_argnums=(8, 9, 10, 11))
_gae_jit = jax.jit(gae, static_argnums=(2,))
_update_jit = jax.jit(update, static_argnums=(6,))
_propose_jit = jax.jit(_propose, static_argnums=(7, 8, 9, 10))
_polish_select_jit = jax.jit(_polish_select, static_argnums=(6, 7, 8, 9))


def as_dtype(tree, dt):
    """Float leaves to ``dt``; the optimizer's step stays an integer."""
    return jax.tree_util.tree_map(
        lambda x: (jnp.asarray(x, dt) if jnp.issubdtype(jnp.asarray(x).dtype,
                                                        jnp.floating)
                   else jnp.asarray(x)), tree)


def ppo_stage(key, agent, e, joint, i: int, tau: int, peak, cfg: Config,
              routed: bool, objective: str, dt):
    """Step 1 of player ``i``'s round: its agent after PPO. ``key`` is the
    first split of the player's key; ``e`` holds the ``env_view`` arrays
    in ``dt`` and ``joint`` is (S, I, D), S = 1 unrouted."""
    with jax.default_matmul_precision("highest"):
        return _ppo_stage_jit(key, agent, e, joint, i, tau, peak, cfg,
                              routed, objective, dt)


def starts(key, e, joint, i: int, tau: int, peak, cfg: Config,
           routed: bool, objective: str, dt):
    """The start states of one PPO iteration of player ``i``; ``key`` is
    the second split of the iteration's key."""
    return _starts_jit(key, e, joint, i, tau, peak, cfg, routed, objective,
                       dt)


def rollout_stage(key, agent, s0, e, joint, i: int, tau: int, peak,
                  cfg: Config, routed: bool, objective: str, dt):
    """The rollouts of one PPO iteration of player ``i`` from ``s0``:
    ``(states, actions, logps, rewards, values)``; ``key`` is the first
    split of the iteration's key."""
    with jax.default_matmul_precision("highest"):
        return _rollout_stage_jit(key, agent, s0, e, joint, i, tau, peak,
                                  cfg, routed, objective, dt)


def gae_stage(rewards, values, cfg: Config):
    """Normalised advantages and returns of one iteration's rollouts."""
    return _gae_jit(rewards, values, cfg)


def update_stage(agent, states, actions, logps, adv, returns, cfg: Config):
    """The agent after one iteration's update, from its rollouts and
    advantages."""
    with jax.default_matmul_precision("highest"):
        return _update_jit(agent, states, actions, logps, adv, returns, cfg)


def propose(key, agent, e, joint, i: int, tau: int, peak, cfg: Config,
            routed: bool, objective: str, dt):
    """Step 2: the proposals of the improved ``agent`` and their rewards;
    ``key`` is the second split of the player's key."""
    with jax.default_matmul_precision("highest"):
        return _propose_jit(key, agent, e, joint, i, tau, peak, cfg, routed,
                            objective, dt)


def polish_select(start, e, joint, i: int, tau: int, peak, cfg: Config,
                  routed: bool, objective: str, dt):
    """Steps 3 and 4 from the polish's ``start``: the four finals, their
    rewards and the chosen row."""
    with jax.default_matmul_precision("highest"):
        return _polish_select_jit(start, e, joint, i, tau, peak, cfg, routed,
                                  objective, dt)


def player_round(key, agent, e, joint, i: int, tau: int, peak,
                 cfg: Config, routed: bool, objective: str, dt
                 ) -> Dict[str, Any]:
    """One player's PPO improve, proposals, polish and choice.

    ``e`` holds the ``env_view`` arrays in ``dt``; ``joint`` is (S, I, D)
    (S = 1 unrouted). Returns the improved agent, the 18 proposals
    (``cand``: mean, current row, samples) and their rewards, the four
    finals (two polished, then their starts) with their rewards, and the
    chosen (S, D) row."""
    k_ppo, k_cand = jax.random.split(key)
    game = (e, joint, i, tau, peak, cfg, routed, objective, dt)
    agent = ppo_stage(k_ppo, agent, *game)
    cand, cand_rewards = propose(k_cand, agent, *game)
    finals, final_rewards, row = polish_select(
        _pick(cand, jnp.argmax(cand_rewards)), *game)
    return {"agent": agent, "cand": cand, "cand_rewards": cand_rewards,
            "finals": finals, "final_rewards": final_rewards, "row": row}


def half(key_r, agents: List[Dict[str, Any]], e, joint, parity: int,
         tau: int, peak, cfg: Config, routed: bool, objective: str, dt
         ) -> Tuple[List[Dict[str, Any]], Any, Dict[int, Dict[str, Any]]]:
    """The players of one parity best-respond to ``joint``, one after
    another; returns the agents, the new joint and each player's round."""
    i_n = joint.shape[1]
    keys = jax.random.split(key_r, i_n)
    agents = list(agents)
    outs = {}
    for i in range(parity, i_n, 2):
        outs[i] = player_round(keys[i], agents[i], e, joint, i, tau, peak,
                               cfg, routed, objective, dt)
    for i, out in outs.items():
        agents[i] = out["agent"]
        joint = with_row(joint, i, out["row"])
    return agents, joint, outs


def game_value(e, joint, tau, peak, objective: str):
    with jax.default_matmul_precision("highest"):
        return float(jnp.sum(player_costs(e, joint, tau, peak, objective)))


def solve_epoch(key, agents: List[Dict[str, Any]], e, tau: int, peak,
                cfg: Config, routed: bool, objective: str, dt
                ) -> Dict[str, Any]:
    """``rounds`` red-black rounds from the uniform joint; the best joint
    and value, each round's value and the rounds rewound."""
    s_n = e["origin"].shape[0]
    i_n, d = e["er"].shape
    joint = jnp.full((s_n, i_n, d), 1.0 / d, dt)
    best_joint, best_val = joint, game_value(e, joint, tau, peak, objective)
    values, diverged = [], 0
    for key_r in jax.random.split(key, cfg.rounds):
        k1, k2 = jax.random.split(key_r)
        new_agents, new_joint, _ = half(k1, agents, e, joint, 0, tau, peak,
                                        cfg, routed, objective, dt)
        new_agents, new_joint, _ = half(k2, new_agents, e, new_joint, 1, tau,
                                        peak, cfg, routed, objective, dt)
        val = game_value(e, new_joint, tau, peak, objective)
        values.append(val)
        if not (np.isfinite(val) and bool(jnp.all(jnp.isfinite(new_joint)))):
            diverged += 1
            continue
        agents, joint = new_agents, new_joint
        if val < best_val:
            best_joint, best_val = joint, val
    return {"agents": agents, "fractions": best_joint, "best": best_val,
            "round_values": values, "diverged_rounds": diverged}


def play_day(seed: int, agents: List[Dict[str, Any]], e, hours: int,
             cfg: Config, routed: bool, objective: str, dt
             ) -> Dict[str, np.ndarray]:
    """Per-hour metrics (hours,) of one day: hour ``tau`` solves with the
    ``tau``-th split of ``split(PRNGKey(seed))[1]``, as the scan engine
    does, from the agents the hour before left."""
    key = jax.random.split(jax.random.PRNGKey(seed))[1]
    peak = jnp.zeros((e["er"].shape[1],), dt)
    rows = []
    for tau in range(hours):
        key, ks = jax.random.split(key)
        out = solve_epoch(ks, agents, e, tau, peak, cfg, routed, objective,
                          dt)
        agents = out["agents"]
        with jax.default_matmul_precision("highest"):
            peak, m = R.simulate(e, R.place(e, out["fractions"], tau), tau,
                                 peak)
        rows.append({k: float(v) for k, v in m.items()})
    return {k: np.asarray([r[k] for r in rows]) for k in rows[0]}
