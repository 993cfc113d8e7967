"""Pallas TPU flash-decode: single-token attention over a long KV cache.

TPU-native design:
  * grid is (batch, kv_heads, kv_blocks); each program loads one
    ``block_k × head_dim`` KV tile into VMEM and scores it against the whole
    GQA *query group* at once (``group × head_dim`` tile), so MQA/GQA decode
    amortizes the KV stream over all query heads that share it — this is the
    decode-side bandwidth optimization the roofline demands (decode is HBM
    bound; KV bytes dominate);
  * the kv dimension is sequential ("arbitrary") and carries the online
    softmax state in VMEM scratch, exactly like the prefill kernel;
  * ragged cache lengths arrive as a scalar-prefetch operand in SMEM, so
    each program reads its own sequence's length as a scalar.

For multi-megabyte caches a real deployment would add a second split-KV grid
axis plus a cross-block reduction; block-sequential streaming is already
bandwidth-optimal on TPU because the kv grid dimension is executed as a
hardware loop with double-buffered VMEM copies.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def _decode_kernel(
    len_ref,     # (B,) int32 valid lengths, scalar-prefetched into SMEM
    q_ref,       # (1, 1, group, d)
    k_ref,       # (1, 1, block_k, d)
    v_ref,       # (1, 1, block_k, d)
    o_ref,       # (1, 1, group, d)
    m_scr, l_scr, acc_scr,
    *,
    sm_scale: float,
    softcap: float,
    block_k: int,
):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[bi]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    @pl.when(ki * block_k < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (group, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (group, block_k)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(k_pos < length, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        # zero padded rows: a partial tail block reads out-of-bounds garbage
        # and 0-weight × garbage would still poison the PV matmul
        v = jnp.where(k_pos.reshape(-1, 1) < length, v, 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "softcap", "block_k", "interpret"),
)
def decode_attention(
    q: jnp.ndarray,        # (B, H, D) one new token per sequence
    k_cache: jnp.ndarray,  # (B, S, KVH, D)
    v_cache: jnp.ndarray,  # (B, S, KVH, D)
    lengths: jnp.ndarray,  # (B,) int32 valid positions per sequence
    *,
    sm_scale: Optional[float] = None,
    softcap: float = 0.0,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, d = q.shape
    s_len, kvh = k_cache.shape[1], k_cache.shape[2]
    assert h % kvh == 0
    group = h // kvh
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5
    block_k = min(block_k, s_len)
    nk = pl.cdiv(s_len, block_k)

    qt = q.reshape(b, kvh, group, d)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, KVH, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _decode_kernel, sm_scale=scale, softcap=softcap, block_k=block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, lens: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, lens: (b_, h_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.reshape(b, h, d)
