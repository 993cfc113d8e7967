"""GT-DRL: the paper's contribution (§5.3).

Per-player PPO agents embedded in the non-cooperative game: each round,
every player best-responds with a few PPO iterations against the others'
current strategies (Jacobi-style simultaneous best response — fully
vmappable across players, which is how all |I| agents train on one
accelerator at once), then strategies are re-combined. The game-theoretic
decomposition shrinks each agent's state/action space from |I|·|D| to |D|
(paper §5.3, the central scalability argument).

State faithful to the paper: the player's own strategy (its fractions).
``state_mode="env"`` (beyond-paper, flag-gated) appends normalized per-DC
context features so the pretrained policy can condition on prices/carbon.

Routed games (``GameContext.routed``) grow each player's strategy from a
(D,) simplex row to an (S, D) routing matrix — the decomposition argument
carries over: |S|·|D| per agent instead of |S|·|I|·|D| joint — and
``state_mode="env"`` gains the player's origin-weighted access RTT feature.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..dcsim import env as E
from . import networks as nets
from .game import GameContext, SolveResult, player_rewards, uniform_fractions
from .ppo import AgentState, PPOConfig, agent_init, average_agents, ppo_improve


@dataclasses.dataclass(frozen=True)
class GTDRLConfig:
    ppo: PPOConfig = PPOConfig(horizon=6, episodes=32, iters=4, update_epochs=4)
    rounds: int = 8                 # best-response (game) rounds per epoch
    polish_steps: int = 40          # best-reply refinement of adopted proposals
    polish_lr: float = 0.4
    damping: float = 0.5            # Jacobi damping: blend of new vs old joint
    state_mode: str = "strategy"    # strategy | env
    pretrain_iters: int = 60        # total (tau, joint) contexts seen offline
    pretrain_batch: int = 4         # contexts trained in parallel per step
    half_update: str = "gather"     # gather (I/2 dispatch) | masked (reference)


def _norm(x: jnp.ndarray) -> jnp.ndarray:
    """Max-normalize; safe when the whole vector is zero (e.g. a zero-carbon
    grid or a renewable_drought scale=0 scenario) — returns zeros, not NaN."""
    return x / jnp.maximum(jnp.max(jnp.abs(x)), 1e-9)


def _ctx_features(env: E.EnvParams, tau, i, routed: bool = False) -> jnp.ndarray:
    """Per-DC context for state_mode='env' (beyond-paper).

    Routed games append the player's origin-weighted access RTT per DC —
    the locality signal the (S, D) routing strategy is meant to exploit.
    """
    feats = [
        _norm(env.er[i]),
        _norm(E.dp_max_t(env, tau)),
        _norm(env.carbon[:, tau]),
        _norm(env.eprice[:, tau]),
        _norm(env.rp[:, tau]),
    ]
    if routed:
        w = E.origin_at(env, tau)[:, i]                       # (S,)
        feats.append(_norm(jnp.sum(w[:, None] * E.source_rtt(env), axis=0)))
    return jnp.concatenate(feats)


def _row_shape(env: E.EnvParams, routed: bool):
    """One player's strategy shape: (D,), or (S, D) in a routed game.

    The degenerate S = 1 origin is normalized to the unrouted (D,) shape —
    one source has nothing to route, and running the identical program is
    what keeps the S = 1 parity guarantee bit-for-bit (see
    ``GameContext.is_routed``).
    """
    d = E.num_dcs(env)
    s = E.num_sources(env)
    return (s, d) if (routed and s > 1) else (d,)


def state_dim(env: E.EnvParams, mode: str, routed: bool = False) -> int:
    d = E.num_dcs(env)
    shape = _row_shape(env, routed)
    own = int(np.prod(shape))
    if mode == "strategy":
        return own
    return own + (6 if len(shape) == 2 else 5) * d


def _state_of(env, tau, i, mode, routed):
    shape = _row_shape(env, routed)

    def fn(logits):
        frac = jax.nn.softmax(logits.reshape(shape), axis=-1).reshape(-1)
        if mode == "strategy":
            return frac
        return jnp.concatenate([frac, _ctx_features(env, tau, i, routed)])
    return fn


def init_agents(key, env: E.EnvParams, cfg: GTDRLConfig,
                routed: bool = False) -> AgentState:
    """Stacked per-player agents: leading axis |I| on every leaf.

    In a routed game each agent's action space is the flattened (S, D)
    routing matrix instead of a single (D,) simplex row.
    """
    i_n = E.num_players(env)
    sd = state_dim(env, cfg.state_mode, routed)
    ad = int(np.prod(_row_shape(env, routed)))
    keys = jax.random.split(key, i_n)
    return jax.vmap(lambda k: agent_init(k, sd, ad, cfg.ppo))(keys)


def _player_reward_closure(env, tau, objective, peak_state, joint_fracs, i, scale):
    """reward(logits) = -objective_i(joint with row i replaced) / scale."""
    routed = joint_fracs.ndim == 3
    shape = _row_shape(env, routed)

    def fn(logits):
        row = jax.nn.softmax(logits.reshape(shape), axis=-1)
        fr = joint_fracs.at[..., i, :].set(row)
        ar = (E.project_feasible_routed(env, fr, tau) if routed
              else E.project_feasible(env, fr, tau))
        r = E.player_reward(env, ar, tau, peak_state, objective)[i]
        return -r / scale

    return fn


def _one_player_round(key, agent, env, tau, objective, peak_state, joint, i, mode, ppo_cfg,
                      polish_steps=30, polish_lr=0.4):
    """PPO-improve player i against fixed others; return (agent, greedy row).

    The player's strategy row is (D,) — or its (S, D) routing matrix in a
    routed game (``joint`` is then the (S, I, D) tensor); the agent always
    works in the flattened logit space and rows reshape at the boundary.
    """
    routed = joint.ndim == 3
    shape = _row_shape(env, routed)
    proj = E.project_feasible_routed if routed else E.project_feasible
    base = jnp.abs(E.player_reward(
        env, proj(env, joint, tau), tau, peak_state, objective)[i]) + 1e-6
    reward_of = _player_reward_closure(env, tau, objective, peak_state, joint, i, base)
    state_of = _state_of(env, tau, i, mode, routed)
    own_logits = jnp.log(joint[..., i, :] + 1e-9).reshape(-1)

    def state0_fn(k):
        # start episodes around the current strategy with Dirichlet jitter
        alpha = joint[..., i, :] * 20.0 + 0.5
        fr = jax.random.dirichlet(
            k, jnp.broadcast_to(alpha, (ppo_cfg.episodes,) + alpha.shape))
        fr = fr.reshape(ppo_cfg.episodes, -1)
        if mode == "strategy":
            return fr
        ctxf = _ctx_features(env, tau, i, routed)
        return jnp.concatenate([fr, jnp.broadcast_to(ctxf, (ppo_cfg.episodes, ctxf.shape[0]))], axis=1)

    k_ppo, k_cand = jax.random.split(key)
    agent, info = ppo_improve(k_ppo, agent, state0_fn, state_of, reward_of, ppo_cfg)
    obs.tap("gt_drl/ppo", {"player": i, "actor_loss": info["actor_loss"],
                           "mean_reward": info["mean_reward"]})
    # Best response over the learned policy's support: the stochastic policy
    # proposes candidates (greedy mean + samples), the player adopts whichever
    # proposal minimizes its own objective, never regressing below its current
    # row. This is the game-theoretic step; PPO supplies the proposal
    # distribution (paper §5.3: "the agent determines the optimal strategy").
    state_now = state_of(own_logits)
    mu = nets.actor_mean(agent.actor, state_now)
    std = jnp.exp(jnp.clip(agent.actor["log_std"], -4.0, 1.0))
    n_cand = 16
    eps = jax.random.normal(k_cand, (n_cand,) + mu.shape)
    cand_logits = jnp.concatenate(
        [mu[None], own_logits[None], mu[None] + std * eps], axis=0)
    rewards = jax.vmap(reward_of)(cand_logits)
    best_logits = cand_logits[jnp.argmax(rewards)]
    # ... then the game's rapid best-reply refinement polishes BOTH the
    # policy's best proposal and the incumbent row, adopting whichever basin
    # wins (paper: GT-DRL "combin[es] the rapidness of a non-cooperative
    # optimization strategy with the exploration abilities of DRL"). Polishing
    # the incumbent too means a player's step never does worse than a pure
    # best-reply step — exploration can only help, never commit to a worse
    # basin.
    def polish(logits, _):
        g = jax.grad(lambda lg: -reward_of(lg))(logits)
        return logits - polish_lr * g / (jnp.linalg.norm(g) + 1e-9), None

    def run_polish(logits0):
        out, _ = jax.lax.scan(polish, logits0, None, length=polish_steps)
        return out

    starts = jnp.stack([best_logits, own_logits])
    polished = jax.vmap(run_polish)(starts)
    finals = jnp.concatenate([polished, starts], axis=0)
    final_rewards = jax.vmap(reward_of)(finals)
    row = jax.nn.softmax(finals[jnp.argmax(final_rewards)].reshape(shape), axis=-1)
    return agent, row


def _run_players(keys, agents, idx, env, tau, objective, peak_state, joint, cfg):
    """vmap ``_one_player_round`` over the given player rows.

    ``keys``/``agents`` carry a leading axis matching ``idx``; module-level
    lookup of ``_one_player_round`` keeps the dispatch observable in tests.
    """
    def run(k, a, i):
        return _one_player_round(
            k, a, env=env, tau=tau, objective=objective, peak_state=peak_state,
            joint=joint, i=i, mode=cfg.state_mode, ppo_cfg=cfg.ppo,
            polish_steps=cfg.polish_steps, polish_lr=cfg.polish_lr)

    return jax.vmap(run)(keys, agents, idx)


def half_update(agents, joint, key_r, parity: int, ctx: GameContext,
                peak_state, cfg: GTDRLConfig):
    """Red-black Gauss-Seidel half-step: players with index%2==parity
    best-respond simultaneously (vmapped); the other half hold — sequential
    information flow at Jacobi's vmap efficiency.

    ``cfg.half_update`` selects the implementation:

    - ``"gather"`` (default): gather the active half's rows/agents, dispatch
      ``_one_player_round`` for ceil(I/2) players only, scatter back — half
      the per-round FLOPs of the full-width version.
    - ``"masked"``: reference — dispatch all I players and discard the
      inactive half's updates with a parity mask. Same results (the per-player
      keys are identical), twice the work; kept for parity tests/benchmarks.

    Both modes give each agent ceil(rounds) PPO updates per round. The
    original implementation also trained the *inactive* half's agents each
    half-step (two updates per round, against a stale joint, discarding only
    their rows) — that extra compute is exactly what this restructure
    removes, so gt-drl trajectories differ numerically from the seed commit.
    """
    env = ctx.env
    i_n = E.num_players(env)
    routed = joint.ndim == 3
    keys = jax.random.split(key_r, i_n)
    # vmapped rows arrive player-major ((n,) + row_shape); a routed joint is
    # source-major (S, I, D), so scatters move the player axis back to -2
    to_joint = (lambda rows: jnp.moveaxis(rows, 0, 1)) if routed else (lambda rows: rows)
    if cfg.half_update == "gather":
        idx = jnp.arange(parity, i_n, 2)
        sub = jax.tree_util.tree_map(lambda x: x[idx], agents)
        sub, rows = _run_players(keys[idx], sub, idx, env, ctx.tau,
                                 ctx.objective, peak_state, joint, cfg)
        agents = jax.tree_util.tree_map(
            lambda full, new: full.at[idx].set(new), agents, sub)
        return agents, joint.at[..., idx, :].set(to_joint(rows))
    if cfg.half_update != "masked":
        raise ValueError(f"unknown half_update {cfg.half_update!r}")
    new_agents, rows = _run_players(keys, agents, jnp.arange(i_n), env, ctx.tau,
                                    ctx.objective, peak_state, joint, cfg)
    active = jnp.arange(i_n) % 2 == parity
    agents = jax.tree_util.tree_map(
        lambda old, new: jnp.where(
            active.reshape((i_n,) + (1,) * (new.ndim - 1)), new, old),
        agents, new_agents)
    mask = active[None, :, None] if routed else active[:, None]
    return agents, jnp.where(mask, to_joint(rows), joint)


def solve_epoch(
    key,
    agents: AgentState,
    ctx: GameContext,
    peak_state: jnp.ndarray,
    cfg: GTDRLConfig,
    init_fracs: Optional[jnp.ndarray] = None,
) -> Tuple[AgentState, SolveResult]:
    """Run the game for one epoch: rounds × (red half, black half).

    Each best-response round is divergence-checked: a round whose joint
    strategy or game value goes non-finite (an exploding PPO update) is
    rewound — agents and joint revert to the previous iterate, the round is
    counted in ``info["diverged_rounds"]``, and the game keeps playing from
    the last healthy state instead of poisoning every later round (and the
    epoch's best) with NaNs. Finite trajectories are bit-for-bit unchanged:
    the rewind is a ``jnp.where`` select that always picks the new iterate.
    """
    joint0 = init_fracs if init_fracs is not None else uniform_fractions(ctx)

    def one_round(carry, key_r):
        agents, joint, best_joint, best_val, diverged = carry
        prev_agents, prev_joint = agents, joint
        k1, k2 = jax.random.split(key_r)
        with jax.named_scope("best_response"):
            agents, joint = half_update(agents, joint, k1, 0, ctx,
                                        peak_state, cfg)
            agents, joint = half_update(agents, joint, k2, 1, ctx,
                                        peak_state, cfg)
        val = jnp.sum(player_rewards(ctx, joint, peak_state))
        ok = jnp.all(jnp.isfinite(joint)) & jnp.isfinite(val)
        agents = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old), agents, prev_agents)
        joint = jnp.where(ok, joint, prev_joint)
        diverged = diverged + jnp.where(ok, 0, 1).astype(jnp.int32)
        better = ok & (val < best_val)
        best_joint = jnp.where(better, joint, best_joint)
        best_val = jnp.where(better, val, best_val)
        obs.tap("gt_drl/round",
                {"value": val, "best": best_val,
                 "delta": jnp.max(jnp.abs(joint - prev_joint))})
        return (agents, joint, best_joint, best_val, diverged), val

    val0 = jnp.sum(player_rewards(ctx, joint0, peak_state))
    carry0 = (agents, joint0, joint0, val0, jnp.int32(0))
    (agents, joint, best_joint, best_val, diverged), vals = jax.lax.scan(
        one_round, carry0, jax.random.split(key, cfg.rounds))
    return agents, SolveResult(best_joint,
                               {"round_values": vals, "best": best_val,
                                "diverged_rounds": diverged})


def deploy(key, env: E.EnvParams, objective: str,
           cfg: Optional[GTDRLConfig] = None, routed: bool = False,
           pretrain_agents: bool = True) -> AgentState:
    """The deploy-once snapshot the engines thread through their carries.

    ``pretrain_agents=True`` runs offline pretraining on ``key`` (the paper's
    protocol); ``False`` returns fresh agents from the fixed ``PRNGKey(0)``
    init — exactly the two states the engines' key discipline has always
    produced, now reachable by name so the technique registry (and
    ``ExperimentSpec``) can build the carry without special-casing gt-drl.
    """
    cfg = cfg or GTDRLConfig()
    if pretrain_agents:
        return pretrain(key, env, objective, cfg, routed)
    return init_agents(jax.random.PRNGKey(0), env, cfg, routed)


# ---------------------------------------------------------------------------
# offline pretraining (paper §6: random uniformly-sampled arrival rates)
# ---------------------------------------------------------------------------

def pretrain(
    key,
    env: E.EnvParams,
    objective: str,
    cfg: GTDRLConfig,
    routed: bool = False,
) -> AgentState:
    """Offline training over random (tau, arrival-scale, strategy) contexts.

    Contexts are trained ``pretrain_batch`` at a time: each scan step vmaps
    the all-player round over a batch of independently sampled (tau, joint)
    contexts from the same starting agents, then averages the resulting
    parameter/moment trees (parallel-SGD averaging). Total contexts seen is
    ``>= pretrain_iters``; wall-clock shrinks by ~the batch factor since the
    sequential scan is ``pretrain_iters / pretrain_batch`` steps long.
    """
    i_n, d = E.num_players(env), E.num_dcs(env)
    joint_shape = _row_shape(env, routed)[:-1] + (i_n, d)
    agents = init_agents(key, env, cfg, routed)
    peak0 = jnp.zeros((d,))
    batch = max(1, cfg.pretrain_batch)
    steps = -(-cfg.pretrain_iters // batch)  # ceil

    def one_ctx(agents, key_t):
        k1, k2, k3 = jax.random.split(key_t, 3)
        tau = jax.random.randint(k1, (), 0, 24)
        joint = jax.random.dirichlet(k2, jnp.ones(joint_shape))
        keys = jax.random.split(k3, i_n)
        agents, _ = _run_players(keys, agents, jnp.arange(i_n), env, tau,
                                 objective, peak0, joint, cfg)
        return agents

    def one(agents, key_s):
        agents_b = jax.vmap(one_ctx, in_axes=(None, 0))(
            agents, jax.random.split(key_s, batch))
        return average_agents(agents_b), None

    agents, _ = jax.lax.scan(one, agents, jax.random.split(key, steps))
    return agents
