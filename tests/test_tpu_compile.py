"""Ahead-of-time compiles for a described TPU v5e: what the chip's compiler
refuses fails here, with no chip attached. Nothing runs, so these say
nothing about results or times.

Covers the attention kernels at llama3.2-1b widths and the D=16 routed
``llm`` fd engines (day scan and batched). The topology is described inside
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ExperimentSpec
from repro.core import experiment as X
from repro.dcsim import env as E
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention

# llama3.2-1b attention widths
HEADS, KV_HEADS, HEAD_DIM, SEQ, BLOCK = 32, 8, 64, 2048, 512
DECODE_BATCH = 8
NUM_DCS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_flash_attention_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16,
                              sharding=one_chip)
    compiled = _compile(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, block_q=BLOCK,
                                           block_k=BLOCK), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_attention_compiles_for_v5e(one_chip):
    q = jax.ShapeDtypeStruct((DECODE_BATCH, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    cache = jax.ShapeDtypeStruct((DECODE_BATCH, SEQ, KV_HEADS, HEAD_DIM),
                                 jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((DECODE_BATCH,), jnp.int32,
                                   sharding=one_chip)
    compiled = _compile(
        lambda q_, k_, v_, n_: decode_attention(q_, k_, v_, n_, block_k=BLOCK),
        q, cache, cache, lengths)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("engine", ["scan", "batched"])
def test_fd_engine_compiles_for_v5e(engine, one_chip):
    """The D=16 routed llm fd engine, reached through the compile cache."""
    env = E.build_env(NUM_DCS, seed=0, workload="llm")
    spec = ExperimentSpec(technique="fd", objective="cost_sla",
                          engine=engine, routed=True, workload="llm")
    key, state0 = X._day_inputs(env, "fd", spec.objective, 0, True, None,
                                None, True)
    if engine == "batched":
        rows = 4
        env = E.tile_env(env, rows)
        key = jnp.broadcast_to(key, (rows,) + key.shape)
    args = _shapes((env, key, jnp.zeros((NUM_DCS,)), state0), one_chip)
    fn = X.compiled_engine(spec).__wrapped__
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
