"""Proximal Policy Optimization in pure JAX (paper §5.3 and baseline [33]).

The MDP (paper §5.2): state = the current strategy (simplex fractions,
flattened); action = new desired-fraction logits; next state = the action's
fractions; reward = −objective (the paper minimizes, the agent maximizes).
The same machinery drives both the per-player GT-DRL agents (|D| actions)
and the joint-PPO baseline (|I|·|D| actions) — only the callbacks differ.

Fully jitted: rollouts are lax.scan over time, episodes are vmapped, and
update epochs are a scan over minibatch gradient steps.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from . import networks as nets


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    horizon: int = 8          # steps per episode
    episodes: int = 64        # parallel episodes per iteration
    iters: int = 12           # rollout+update cycles
    update_epochs: int = 4
    clip: float = 0.2
    gamma: float = 0.9
    lam: float = 0.95
    lr: float = 3e-3
    vf_coef: float = 0.5
    ent_coef: float = 1e-3
    hidden: Tuple[int, ...] = (64, 64)


class AgentState(NamedTuple):
    actor: Any
    critic: Any
    actor_opt: Any
    critic_opt: Any


def agent_init(key, state_dim: int, action_dim: int, cfg: PPOConfig) -> AgentState:
    k1, k2 = jax.random.split(key)
    actor = nets.actor_init(k1, state_dim, action_dim, cfg.hidden)
    critic = nets.critic_init(k2, state_dim, cfg.hidden)
    oc = AdamWConfig(lr=cfg.lr, weight_decay=0.0, grad_clip=1.0)
    return AgentState(actor, critic, adamw_init(actor, oc), adamw_init(critic, oc))


class Rollout(NamedTuple):
    states: jnp.ndarray    # (B, T, S)
    actions: jnp.ndarray   # (B, T, A) logits
    logps: jnp.ndarray     # (B, T)
    rewards: jnp.ndarray   # (B, T)
    values: jnp.ndarray    # (B, T+1)


def _rollout(
    key,
    agent: AgentState,
    state0: jnp.ndarray,                    # (B, S) initial states
    state_of: Callable[[jnp.ndarray], jnp.ndarray],   # logits -> next state
    reward_of: Callable[[jnp.ndarray], jnp.ndarray],  # logits -> scalar reward
    cfg: PPOConfig,
) -> Rollout:
    b = state0.shape[0]

    def step(carry, key_t):
        s = carry
        keys = jax.random.split(key_t, b)
        logits, logp = jax.vmap(lambda st, k: nets.actor_sample(agent.actor, st, k))(s, keys)
        r = jax.vmap(reward_of)(logits)
        v = jax.vmap(lambda st: nets.critic_value(agent.critic, st))(s)
        s_next = jax.vmap(state_of)(logits)
        return s_next, (s, logits, logp, r, v)

    keys = jax.random.split(key, cfg.horizon)
    s_last, (ss, aa, lp, rr, vv) = jax.lax.scan(step, state0, keys)
    v_last = jax.vmap(lambda st: nets.critic_value(agent.critic, st))(s_last)
    # scan stacks time first: (T, B, ...) -> (B, T, ...)
    tx = lambda x: jnp.swapaxes(x, 0, 1)
    values = jnp.concatenate([tx(vv), v_last[:, None]], axis=1)
    return Rollout(tx(ss), tx(aa), tx(lp), tx(rr), values)


def _gae(ro: Rollout, cfg: PPOConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    deltas = ro.rewards + cfg.gamma * ro.values[:, 1:] - ro.values[:, :-1]

    def back(carry, d):
        adv = d + cfg.gamma * cfg.lam * carry
        return adv, adv

    _, adv_rev = jax.lax.scan(back, jnp.zeros(deltas.shape[0]), deltas.T[::-1])
    adv = adv_rev[::-1].T
    returns = adv + ro.values[:, :-1]
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    return adv, returns


def _update(agent: AgentState, ro: Rollout, adv, returns, cfg: PPOConfig) -> Tuple[AgentState, Dict]:
    s = ro.states.reshape(-1, ro.states.shape[-1])
    a = ro.actions.reshape(-1, ro.actions.shape[-1])
    lp_old = ro.logps.reshape(-1)
    adv_f = adv.reshape(-1)
    ret_f = returns.reshape(-1)
    oc = AdamWConfig(lr=cfg.lr, weight_decay=0.0, grad_clip=1.0)

    def actor_loss(actor):
        mu = jax.vmap(lambda st: nets.actor_mean(actor, st))(s)
        std = jnp.exp(jnp.clip(actor["log_std"], -4.0, 1.0))
        lp = nets.gaussian_logp(a, mu, std)
        ratio = jnp.exp(lp - lp_old)
        unclipped = ratio * adv_f
        clipped = jnp.clip(ratio, 1 - cfg.clip, 1 + cfg.clip) * adv_f
        ent = jnp.sum(jnp.clip(actor["log_std"], -4.0, 1.0))
        return -jnp.mean(jnp.minimum(unclipped, clipped)) - cfg.ent_coef * ent

    def critic_loss(critic):
        v = jax.vmap(lambda st: nets.critic_value(critic, st))(s)
        return cfg.vf_coef * jnp.mean((v - ret_f) ** 2)

    def epoch(carry, _):
        ag = carry
        la, ga = jax.value_and_grad(actor_loss)(ag.actor)
        new_actor, aopt, _ = adamw_update(ga, ag.actor_opt, ag.actor, oc)
        lc, gc = jax.value_and_grad(critic_loss)(ag.critic)
        new_critic, copt, _ = adamw_update(gc, ag.critic_opt, ag.critic, oc)
        return AgentState(new_actor, new_critic, aopt, copt), (la, lc)

    agent, (la, lc) = jax.lax.scan(epoch, agent, None, length=cfg.update_epochs)
    return agent, {"actor_loss": la[-1], "critic_loss": lc[-1]}


def ppo_improve(
    key,
    agent: AgentState,
    state0_fn: Callable[[Any], jnp.ndarray],   # key -> (B, S) initial states
    state_of: Callable[[jnp.ndarray], jnp.ndarray],
    reward_of: Callable[[jnp.ndarray], jnp.ndarray],
    cfg: PPOConfig,
) -> Tuple[AgentState, Dict[str, jnp.ndarray]]:
    """Run ``iters`` × (rollout → GAE → clipped update)."""

    def it(carry, key_i):
        ag = carry
        k1, k2 = jax.random.split(key_i)
        with jax.named_scope("ppo_rollout"):
            ro = _rollout(k1, ag, state0_fn(k2), state_of, reward_of, cfg)
        with jax.named_scope("ppo_update"):
            adv, ret = _gae(ro, cfg)
            ag, losses = _update(ag, ro, adv, ret, cfg)
        return ag, (jnp.mean(ro.rewards), losses["actor_loss"])

    agent, (rew, al) = jax.lax.scan(it, agent, jax.random.split(key, cfg.iters))
    return agent, {"mean_reward": rew, "actor_loss": al}


def greedy_fractions(agent: AgentState, state: jnp.ndarray) -> jnp.ndarray:
    """Deterministic action: softmax of the policy mean."""
    return jax.nn.softmax(nets.actor_mean(agent.actor, state))


def average_agents(agents_b: AgentState) -> AgentState:
    """Collapse a leading batch axis by parameter averaging (parallel SGD).

    Float leaves (params, AdamW moments) are averaged; integer leaves (the
    optimizer step counters, identical across a batch of equal-length
    updates) take the first copy so their dtype survives.
    """
    def avg(x):
        if jnp.issubdtype(x.dtype, jnp.integer):
            return x[0]
        return jnp.mean(x, axis=0)

    return jax.tree_util.tree_map(avg, agents_b)
