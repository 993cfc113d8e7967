"""Pallas TPU flash attention (prefill) with explicit BlockSpec VMEM tiling.

TPU-native design notes (vs a CUDA flash port):
  * tiles are MXU-aligned: ``block_q`` × ``head_dim`` and ``block_k`` ×
    ``head_dim`` with 128-multiples preferred so the systolic array is full;
  * the grid is (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
    declared "arbitrary" (sequential) so the online-softmax accumulator in
    VMEM scratch carries across kv steps — this is the TPU analogue of a
    persistent CTA loop;
  * GQA is handled in the BlockSpec index maps (each q head reads kv head
    ``h // group``) so no repeated KV is materialized in HBM;
  * running max / sum live in VMEM scratch replicated across the 128-lane
    minor dimension, which is the layout the VPU wants.

Softmax statistics are fp32 regardless of input dtype.
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


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *,
    causal: bool,
    window: int,
    sm_scale: float,
    softcap: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_kv: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Causal / window block-level skip: only run the MXU work when this
    # (q_block, kv_block) tile intersects the mask support.
    block_needed = True
    if causal:
        first_q = q_offset + qi * block_q
        first_k = ki * block_k
        block_needed = jnp.logical_and(
            first_k <= first_q + block_q - 1,
            True if window <= 0 else (first_k + block_k - 1 > first_q - window),
        )

    @pl.when(block_needed if causal else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (block_q, block_k)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        mask = (k_pos < seq_kv)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (block_q, 1), lane-replicated storage
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (block_q, block_k)
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + l_cur
        v = v_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        # zero padded rows of a partial tail block (see decode kernel note)
        v = jnp.where(k_pos[:1].reshape(-1, 1) < seq_kv, v, 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "sm_scale", "softcap", "block_q", "block_k",
        "q_offset", "interpret",
    ),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Skv, KVH, D)
    v: jnp.ndarray,  # (B, Skv, KVH, D)
    *,
    causal: bool = True,
    window: int = 0,
    sm_scale: Optional[float] = None,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_k)

    # (B, H, S, D) layout inside the kernel: the head dim becomes a pure grid
    # dimension and each tile is a clean (block, d) VMEM rectangle.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        sm_scale=scale,
        softcap=softcap,
        block_q=block_q,
        block_k=block_k,
        seq_q=sq,
        seq_kv=skv,
        q_offset=q_offset,
    )

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
