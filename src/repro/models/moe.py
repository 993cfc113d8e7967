"""Mixture-of-Experts FFN: top-k routing with capacity, expert-parallel.

Two dispatch implementations:

* ``gather`` (default): tokens are placed into per-expert slots with a
  scatter, expert FFNs run as one batched einsum over (E, C, d), results
  come back with a gather. Zero "fake" FLOPs — the HLO FLOP count equals
  active-expert compute, which keeps the roofline's MODEL_FLOPS/HLO_FLOPs
  ratio honest. Dropped tokens (beyond capacity) lose their expert
  contribution, standard GShard behaviour.
* ``einsum``: classic GShard one-hot dispatch/combine einsums. More
  collective-friendly under some partitioners but adds B·S·E·C·d dispatch
  FLOPs; kept for A/B tests in §Perf.

Expert weights are stacked (E, d, f) so GSPMD shards the expert dim over
the "model" axis (expert parallelism) when E divides it, else the ffn width.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..distributed import sharding as shd
from ..distributed.sharding import constrain
from .layers import Params, activation, dense_init
from .mlp import mlp_apply, mlp_init


def moe_init(key, cfg) -> Params:
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.param_dtype]
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 8)
    std = 1.0 / math.sqrt(d)

    def stack(k, shape):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std).astype(dt)

    p: Params = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "experts": {
            "w_in": {"w": stack(ks[1], (e, d, f))},
            "w_gate": {"w": stack(ks[2], (e, d, f))},
            "w_out": {"w": stack(ks[3], (e, f, d)) * (1.0 / max(1, cfg.num_layers) ** 0.5)},
        },
    }
    if cfg.num_shared_experts:
        shared_f = (cfg.shared_d_ff or f) * cfg.num_shared_experts
        p["shared"] = mlp_init(ks[4], cfg, d_ff=shared_f)
    if cfg.moe_dense_residual:
        p["dense"] = mlp_init(ks[5], cfg, d_ff=cfg.moe_dense_d_ff or f)
    return p


def _route(p: Params, cfg, x2d: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router: returns (weights (T,k), experts (T,k), probs (T,E))."""
    logits = (x2d.astype(jnp.float32) @ p["router"]["w"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, sel, probs


def aux_load_balance(probs: jnp.ndarray, sel: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Switch-style load balancing loss: E * sum_e f_e * P_e."""
    counts = jnp.zeros((num_experts,), jnp.float32)
    onehot = jax.nn.one_hot(sel, num_experts, dtype=jnp.float32)  # (T, k, E)
    f_e = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f_e * p_e)


def _expert_ffn(experts: Params, cfg, h_in: jnp.ndarray) -> jnp.ndarray:
    """Batched per-expert FFN over (E, C, d)."""
    act = activation(cfg.act)
    up = jnp.einsum("ecd,edf->ecf", h_in, experts["w_in"]["w"])
    gate = jnp.einsum("ecd,edf->ecf", h_in, experts["w_gate"]["w"])
    h = act(gate) * up
    h = constrain(h, "model", None, None)
    return jnp.einsum("ecf,efd->ecd", h, experts["w_out"]["w"])


def moe_apply(
    p: Params,
    cfg,
    x: jnp.ndarray,  # (B, S, D)
    *,
    capacity_factor: float = 1.25,
    impl: str = "gather",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,D), aux load-balance loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    t = b * s
    x2d = x.reshape(t, d)
    weights, sel, probs = _route(p, cfg, x2d)
    aux = aux_load_balance(probs, sel, e)

    cap = int(math.ceil(t * k * capacity_factor / e))
    cap = max(cap, 1)

    flat_sel = sel.reshape(t * k)  # expert id per (token, choice)
    onehot = jax.nn.one_hot(flat_sel, e, dtype=jnp.int32)  # (T*k, E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) - onehot  # exclusive count
    pos = jnp.take_along_axis(pos_in_expert, flat_sel[:, None], axis=1)[:, 0]  # (T*k,)
    keep = pos < cap

    if impl == "einsum":
        # GShard dispatch/combine one-hot tensors.
        disp = (
            jax.nn.one_hot(flat_sel, e, dtype=x.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1, dtype=x.dtype)[:, None, :cap]
        ).reshape(t, k, e, cap)
        expert_in = jnp.einsum("tkec,td->ecd", disp, x2d)
        expert_out = _expert_ffn(p["experts"], cfg, expert_in)
        comb = disp * weights.astype(x.dtype)[:, :, None, None]
        y2d = jnp.einsum("tkec,ecd->td", comb, expert_out)
    else:
        token_ids = jnp.arange(t * k, dtype=jnp.int32) // k  # token of each choice
        # slot_owner[e, c] = flat token index occupying that slot (t = pad row)
        slot_owner = jnp.full((e, cap), t, jnp.int32)
        # dropped (token, choice) pairs scatter to row index ``e`` which is out
        # of bounds and silently dropped — they never clobber a live slot.
        slot_owner = slot_owner.at[
            jnp.where(keep, flat_sel, e),
            jnp.where(keep, pos, 0),
        ].set(token_ids, mode="drop")
        x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
        expert_in = x_pad[slot_owner]  # (E, C, D) gather
        expert_in = constrain(expert_in, "model", None, None)
        expert_out = _expert_ffn(p["experts"], cfg, expert_in)  # (E, C, D)
        # combine: each (token, choice) reads its slot back
        safe_pos = jnp.where(keep, pos, 0)
        out_choice = expert_out[flat_sel, safe_pos]  # (T*k, D)
        out_choice = jnp.where(keep[:, None], out_choice, 0.0)
        y2d = jnp.sum(
            out_choice.reshape(t, k, d) * weights.astype(x.dtype)[:, :, None], axis=1
        )

    y = y2d.reshape(b, s, d)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, x)
    if "dense" in p:
        y = y + mlp_apply(p["dense"], cfg, x)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# expert-parallel MoE (shard_map): the production path under a mesh
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition the global gather/scatter dispatch — it replicates
# the expert computation on every device (measured: useful-compute ratio
# 0.011 on qwen2-moe × train_4k). The shard_map formulation makes the
# parallelism explicit and collective-minimal:
#
#   * tokens stay sharded over the DP axes ("pod","data") and are REPLICATED
#     over "model" — so no token all-to-all is needed at all;
#   * experts are sharded over "model" (padded up to a multiple of its size;
#     padded experts get -inf router logits and are never selected);
#   * every (data, model) shard routes its local tokens, runs only its own
#     E/|model| experts, and one psum over "model" combines the results —
#     the same collective class as Megatron TP, amortized over k≪E experts.
#
# Per-device expert FLOPs = global_expert_FLOPs / (|data|·|model|), vs the
# global formulation's ≈ global_expert_FLOPs (replicated).

def _pad_experts(p: Params, e_pad: int, e: int):
    if e_pad == e:
        return p["experts"], p["router"]["w"]
    def padw(w):
        pad = jnp.zeros((e_pad - e,) + w.shape[1:], w.dtype)
        return jnp.concatenate([w, pad], axis=0)
    experts = {k: {"w": padw(v["w"])} for k, v in p["experts"].items()}
    rw = jnp.concatenate(
        [p["router"]["w"], jnp.full((p["router"]["w"].shape[0], e_pad - e), 0.0,
                                    p["router"]["w"].dtype)], axis=1)
    return experts, rw


def moe_apply_ep(
    p: Params,
    cfg,
    x: jnp.ndarray,  # (B, S, D)
    *,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE under the active mesh; falls back to the global
    formulation when un-meshed or the batch does not divide the DP axes."""
    from jax.sharding import PartitionSpec as P

    mesh = shd.active_mesh()
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if mesh is None or "model" not in mesh.axis_names:
        return moe_apply(p, cfg, x, capacity_factor=capacity_factor)
    msize = shd.axis_size(mesh, "model")
    dp_axes = shd.mesh_batch_axes(mesh)
    while dp_axes and b % shd.axis_size(mesh, dp_axes) != 0:
        dp_axes = dp_axes[1:]
    dp = shd.axis_size(mesh, dp_axes) if dp_axes else 1
    e_pad = ((e + msize - 1) // msize) * msize
    e_loc = e_pad // msize
    t_loc = (b // dp) * s
    cap = max(int(math.ceil(t_loc * k * capacity_factor / e_pad)), 1)

    experts, rw = _pad_experts(p, e_pad, e)
    act = activation(cfg.act)

    def local_fn(xl, rw_, w_in, w_gate, w_out):
        m_idx = jax.lax.axis_index("model")
        bl, s_, d_ = xl.shape
        t = bl * s_
        x2 = xl.reshape(t, d_)
        logits = x2.astype(jnp.float32) @ rw_.astype(jnp.float32)
        if e_pad != e:  # padded experts are unroutable
            logits = logits.at[:, e:].set(-1e9)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, sel = jax.lax.top_k(probs, k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        aux = aux_load_balance(probs[:, :e], jnp.minimum(sel, e - 1), e)
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux

        flat_sel = sel.reshape(t * k)
        onehot = jax.nn.one_hot(flat_sel, e_pad, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                                  flat_sel[:, None], axis=1)[:, 0]
        keep = pos < cap
        lo = m_idx * e_loc
        mine = keep & (flat_sel >= lo) & (flat_sel < lo + e_loc)
        local_e = flat_sel - lo
        token_ids = jnp.arange(t * k, dtype=jnp.int32) // k
        slot_owner = jnp.full((e_loc, cap), t, jnp.int32)
        slot_owner = slot_owner.at[
            jnp.where(mine, local_e, e_loc), jnp.where(mine, pos, 0)
        ].set(token_ids, mode="drop")
        x_pad = jnp.concatenate([x2, jnp.zeros((1, d_), x2.dtype)], axis=0)
        expert_in = x_pad[slot_owner]  # (E_loc, C, D) all local
        up = jnp.einsum("ecd,edf->ecf", expert_in, w_in)
        gate = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
        h = act(gate) * up
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_out)
        safe_e = jnp.where(mine, local_e, 0)
        safe_p = jnp.where(mine, pos, 0)
        out_choice = expert_out[safe_e, safe_p]
        out_choice = jnp.where(mine[:, None], out_choice, 0.0)
        y = jnp.sum(out_choice.reshape(t, k, d_) * weights.astype(x2.dtype)[:, :, None], axis=1)
        y = jax.lax.psum(y, "model")
        return y.reshape(bl, s_, d_), aux

    dp_spec = dp_axes if dp_axes else None
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(x, rw, experts["w_in"]["w"], experts["w_gate"]["w"], experts["w_out"]["w"])

    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, x)
    if "dense" in p:
        y = y + mlp_apply(p["dense"], cfg, x)
    return y, aux
