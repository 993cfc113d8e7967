"""Actor / critic MLPs for the DRL components (pure JAX pytrees)."""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# The configurations state float32. On a TPU a float32 matmul at the default
# precision is one bfloat16 pass: the learner's batched forward passes then
# miss a float32 reference by ~2.6e-3 of their magnitude, against 2.6e-5 at
# HIGH (three bfloat16 passes) and 1.9e-7 at HIGHEST (six). HIGH costs a
# gt-drl day 2.7 % on a TPU v5e, HIGHEST 6.2 % (PERF.md).
PRECISION = jax.lax.Precision.HIGH


def mlp_init(key, sizes: Sequence[int], out_scale: float = 0.01) -> Params:
    p: Params = {}
    ks = jax.random.split(key, len(sizes) - 1)
    for li, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        scale = out_scale if li == len(sizes) - 2 else 1.0
        w = jax.random.normal(ks[li], (a, b), jnp.float32) * scale * math.sqrt(2.0 / a)
        p[f"w{li}"] = w
        p[f"b{li}"] = jnp.zeros((b,), jnp.float32)
    return p


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    n = len(p) // 2
    for li in range(n):
        x = jnp.matmul(x, p[f"w{li}"], precision=PRECISION) + p[f"b{li}"]
        if li < n - 1:
            x = jnp.tanh(x)
    return x


def actor_init(key, state_dim: int, action_dim: int, hidden=(64, 64)) -> Params:
    k1, _ = jax.random.split(key)
    return {
        "mlp": mlp_init(k1, (state_dim, *hidden, action_dim)),
        "log_std": jnp.full((action_dim,), -0.7, jnp.float32),
    }


def actor_mean(p: Params, state: jnp.ndarray) -> jnp.ndarray:
    return mlp_apply(p["mlp"], state)


def actor_sample(p: Params, state: jnp.ndarray, key) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Gaussian in logit space; fractions = softmax(logits).

    Returns (logits, log_prob). The softmax re-parameterization keeps the
    action on the simplex (paper eq. 21) while PPO's ratio lives in the
    Gaussian's density, which is measure-consistent between old/new.
    """
    mu = actor_mean(p, state)
    std = jnp.exp(jnp.clip(p["log_std"], -4.0, 1.0))
    eps = jax.random.normal(key, mu.shape)
    logits = mu + std * eps
    logp = gaussian_logp(logits, mu, std)
    return logits, logp


def gaussian_logp(x, mu, std):
    z = (x - mu) / std
    return jnp.sum(-0.5 * z * z - jnp.log(std) - 0.5 * math.log(2 * math.pi), axis=-1)


def critic_init(key, state_dim: int, hidden=(64, 64)) -> Params:
    return mlp_init(key, (state_dim, *hidden, 1), out_scale=1.0)


def critic_value(p: Params, state: jnp.ndarray) -> jnp.ndarray:
    return mlp_apply(p, state)[..., 0]
