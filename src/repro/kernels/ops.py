"""Jit'd public entry points for the kernels with backend dispatch.

``impl`` semantics:
  * ``auto``   — Pallas kernel on TPU; jnp reference elsewhere (the CPU
                 container, dry-run lowering, unit tests). FLOP/byte
                 accounting is identical either way.
  * ``ref``    — always the pure-jnp oracle.
  * ``pallas`` — force the kernel (real TPU).
  * ``interpret`` — kernel body emulated on CPU (used by the kernel tests).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    sm_scale: Optional[float] = None,
    softcap: float = 0.0,
    q_offset: int = 0,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """Batched multi-head (GQA) attention, (B, S, H, D) layout."""
    if impl == "auto":
        # CPU (tests + dry-run lowering): the chunked streaming form, whose
        # memory/byte profile matches the Pallas kernel's VMEM streaming.
        impl = "pallas" if _on_tpu() else "chunked"
    if impl == "chunked":
        from .flash_vjp import flash_attention_jnp

        return flash_attention_jnp(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale,
            softcap=softcap, q_offset=q_offset,
        )
    if impl == "ref":
        return _ref.attention_ref(
            q, k, v, causal=causal, window=window, sm_scale=sm_scale,
            softcap=softcap, q_offset=q_offset,
        )
    return _flash_pallas(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        softcap=softcap, q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=(impl == "interpret"),
    )


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    softcap: float = 0.0,
    impl: str = "auto",
    block_k: int = 512,
) -> jnp.ndarray:
    """Single-token decode attention over a KV cache, (B, H, D) query."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        # Distributed layout (GSPMD): the cache stays *sequence*-sharded over
        # "model" and the query is replicated across it — each model shard
        # scores its own KV span and the softmax/PV reductions psum across
        # shards (the multi-chip analogue of split-KV flash-decode). Without
        # these constraints GSPMD reshards the whole cache to head-sharded
        # every step — measured as the dominant collective of all decode
        # cells.
        from ..distributed.sharding import constrain

        q = constrain(q, ("pod", "data"), None, None)
        k_cache = constrain(k_cache, ("pod", "data"), "model", None, None)
        v_cache = constrain(v_cache, ("pod", "data"), "model", None, None)
        out = _ref.decode_attention_ref(
            q, k_cache, v_cache, lengths, sm_scale=sm_scale, softcap=softcap
        )
        return constrain(out, ("pod", "data"), None, None)
    return _decode_pallas(
        q, k_cache, v_cache, lengths, sm_scale=sm_scale, softcap=softcap,
        block_k=block_k, interpret=(impl == "interpret"),
    )
