"""Exact samplers for the learner's hot path.

``dirichlet(key, alpha)`` returns what ``jax.random.dirichlet(key, alpha)``
returns, bit for bit, for float32 ``alpha`` and threefry keys, at a
fraction of its device time.

JAX draws each gamma variate with Marsaglia–Tsang rejection
(``jax/_src/random.py``: ``_gamma_impl`` -> ``vmap(_gamma_one)``): a trial
loop around a loop that redraws the normal until ``v > 0``. Vmapped over
every element, both loops run until the slowest element is done and hash
fresh keys for *all* elements on every pass, though nearly all accept
their first trial. Here the first trial of every element is straight-line
code, and only the elements left (rejected, or ``v <= 0``) are compacted
into a buffer of ``_buffer(n)`` entries, by a one-hot matmul over their
bytes. The buffer runs ``_TRIALS`` more trials as straight-line code, then
``_gamma_one``'s own loop from the state they reached, which runs no pass
at all unless an element is still open. Compaction repeats while elements
remain, so any number of them is exact. Every key, split and draw is the
one ``_gamma_one`` makes, in its order.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
_THIRD = 1.0 / 3.0
_SQUEEZE = 0.0331
_TRIALS = 1     # straight-line trials on the buffer, before the exact loop


def _buffer(n: int) -> int:
    """Entries compacted per pass, for ``n`` gamma variates: some 2.5 % are
    left after the first trial."""
    return max(n // 32, 1)


class _Trial(NamedTuple):
    """``_gamma_one``'s trial-loop state (key, X, V, U) for a batch."""
    key: jax.Array
    X: jax.Array
    V: jax.Array
    U: jax.Array


def _rejects(s: _Trial, d) -> jax.Array:
    """``_gamma_one._cond_fn``: true while the trial loop must go on."""
    return ((s.U >= 1.0 - _SQUEEZE * (s.X * s.X))
            & (jnp.log(s.U) >= s.X * 0.5 + d * ((1.0 - s.V) + jnp.log(s.V))))


def _trial(key, c, candidates: int):
    """One pass of ``_gamma_one._body_fn`` for one element, with its inner
    ``v > 0`` loop cut to ``candidates`` draws. Returns the new state and
    whether a candidate had ``v > 0``; if none had, the state is not valid."""
    key, x_key, u_key = jax.random.split(key, 3)
    x = v = None
    for _ in range(candidates):
        x_key, sub = jax.random.split(x_key)
        xi = jax.random.normal(sub, (), _F32)
        vi = 1.0 + xi * c
        if x is None:
            x, v = xi, vi
        else:
            x, v = jnp.where(v <= 0.0, xi, x), jnp.where(v <= 0.0, vi, v)
    U = jax.random.uniform(u_key, (), _F32)
    return _Trial(key, x * x, (v * v) * v, U), v > 0.0


def _step(s: _Trial, d, c, candidates: int) -> _Trial:
    """Advance the elements that still reject by one trial, where the trial
    resolves within ``candidates`` normals; the others keep their state."""
    new, found = jax.vmap(lambda k, ci: _trial(k, ci, candidates))(s.key, c)
    take = _rejects(s, d) & found
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(take.reshape(take.shape + (1,) * (a.ndim - 1)),
                               a, b), new, s)


def _replay(s: _Trial, d, c) -> _Trial:
    """``_gamma_one``'s trial loop, as JAX writes it, from state ``s``."""
    def one(s, d, c):
        def body(s):
            key, x_key, u_key = jax.random.split(s.key, 3)

            def next_xv(kxv):
                k, sub = jax.random.split(kxv[0])
                x = jax.random.normal(sub, (), _F32)
                return k, x, 1.0 + x * c

            _, x, v = lax.while_loop(lambda kxv: kxv[2] <= 0.0, next_xv,
                                     (x_key, jnp.float32(0), jnp.float32(-1)))
            return _Trial(key, x * x, (v * v) * v,
                          jax.random.uniform(u_key, (), _F32))
        return lax.while_loop(lambda s: _rejects(s, d), body, s)
    return jax.vmap(one)(s, d, c)


def _planes(u) -> jax.Array:
    """The four bytes of each uint32 of ``u`` (m,), as (m, 4) bfloat16,
    which holds them exactly."""
    return jnp.stack([(u >> s) & 0xFF for s in (0, 8, 16, 24)],
                     -1).astype(jnp.bfloat16)


def _word(p) -> jax.Array:
    """``_planes``' inverse, from (m, 4) exact byte values."""
    b = p.astype(jnp.uint32)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def _f32(u):
    return lax.bitcast_convert_type(u, _F32)


def _u32(x):
    return lax.bitcast_convert_type(x, jnp.uint32)


def _gamma_v(keys, alpha):
    """The final ``V`` of ``_gamma_one``'s trial loop for each element of
    the flat ``alpha`` (raw threefry ``keys``, (n, 2)), and the counts
    ``residual``/``replayed``."""
    n = alpha.shape[0]
    size = _buffer(n)
    a = jnp.where(alpha >= 1.0, alpha, alpha + 1.0)
    d = a - _THIRD
    c = _THIRD / jnp.sqrt(d)
    init = _Trial(keys, jnp.zeros_like(a), jnp.ones_like(a),
                  jnp.full_like(a, 2.0))      # rejects: the loop's first pass
    s1 = _step(init, d, c, candidates=1)
    left = _rejects(s1, d)
    seen = jnp.cumsum(left.astype(jnp.int32))  # left variates up to each one
    residual = seen[-1]
    # What a left variate needs to go on: its loop key (a rejected trial's
    # next key, or its first if no normal had v > 0), d and c; as bytes, so
    # that a one-hot matmul moves them exactly (a gather or scatter of this
    # width runs one index at a time on the TPU).
    words = jnp.concatenate([_planes(s1.key[:, 0]), _planes(s1.key[:, 1]),
                             _planes(_u32(d)), _planes(_u32(c))], -1)

    def compact(carry):
        start, V, replayed = carry
        rank = start + 1 + jnp.arange(size)    # the buffer's left variates
        pick = ((seen[None, :] == rank[:, None])
                & left[None, :]).astype(jnp.bfloat16)
        got = jnp.dot(pick, words, preferred_element_type=_F32)
        db, cb = _f32(_word(got[:, 8:12])), _f32(_word(got[:, 12:16]))
        # each takes its next trial; padding starts accepted (U = 0)
        s = _Trial(jnp.stack([_word(got[:, 0:4]), _word(got[:, 4:8])], -1),
                   jnp.zeros_like(db), jnp.ones_like(db),
                   jnp.where(rank <= residual, 2.0, 0.0))
        for _ in range(_TRIALS):
            s = _step(s, db, cb, candidates=2)
        replayed = replayed + jnp.sum(_rejects(s, db))
        s = _replay(s, db, cb)
        back = jnp.dot(_planes(_u32(s.V)).T, pick, preferred_element_type=_F32)
        mine = left & (seen > start) & (seen <= start + size)
        V = jnp.where(mine, _f32(_word(back.T)), V)
        return start + size, V, replayed

    carry = compact((jnp.int32(0), s1.V, jnp.int32(0)))
    _, V, replayed = lax.while_loop(lambda c: c[0] < residual, compact, carry)
    return d, V, {"residual": residual, "replayed": replayed}


def dirichlet_counted(key, alpha) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``jax.random.dirichlet(key, alpha)`` bit for bit (float32 ``alpha``,
    threefry ``key``), and the counts ``residual`` (variates left after the
    first trial) and ``replayed`` (variates that reached the exact loop)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    alpha = jnp.asarray(alpha, _F32)
    a = alpha.reshape(-1)
    keys = jax.random.split(key, a.shape[0])
    # _gamma_one: key, subkey = split(key); the boost draws from subkey.
    pairs = jax.vmap(jax.random.split)(keys)
    d, V, counts = _gamma_v(pairs[:, 0], a)
    log_samples = -jax.vmap(
        lambda k: jax.random.exponential(k, (), _F32))(pairs[:, 1])
    log_boost = jnp.where((a >= 1.0) | (log_samples == 0.0), 0.0,
                          log_samples * (1.0 / a))
    log_gamma = (jnp.log(d) + jnp.log(V)) + log_boost
    return jax.nn.softmax(log_gamma.reshape(alpha.shape), -1), counts


def dirichlet(key, alpha) -> jax.Array:
    """``jax.random.dirichlet(key, alpha)``, bit for bit, for float32
    ``alpha`` and threefry keys (see ``dirichlet_counted``)."""
    return dirichlet_counted(key, alpha)[0]
