"""Sharding rules: param-path → PartitionSpec, activation constraints.

The production mesh axes are ("data", "model") per pod, plus a leading
"pod" axis in the multi-pod mesh. Assignment of tensor dims:

  * batch                → ("pod", "data")        (DP across pods and hosts)
  * attention/MLP width  → "model"                (TP / EP)
  * parameter storage    → optionally also "data" (FSDP / ZeRO-3), flag-gated

Every rule checks divisibility against the actual mesh axis size — GSPMD
rejects uneven shardings at jit boundaries — and falls back to replication
for that dimension (e.g. whisper's 51865 vocab).

Activation constraints are applied through :func:`constrain`, which is a
no-op unless a mesh has been installed with :func:`use_mesh` — so model code
is runnable un-meshed on CPU in the unit tests.
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "repro_active_mesh", default=None
)

BATCH_AXES = ("pod", "data")


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Install a mesh for activation sharding constraints."""
    token = _ACTIVE_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.reset(token)


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH.get()


def axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def mesh_batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def batch_spec(mesh: Mesh, batch: int, extra_dims: int = 0) -> P:
    """Shard a leading batch dim over as much of the DP axes as divides."""
    axes = mesh_batch_axes(mesh)
    while axes and batch % axis_size(mesh, axes) != 0:
        axes = axes[1:]  # drop "pod" first
    first = axes if axes else None
    return P(first, *([None] * extra_dims))


def _maybe(mesh: Mesh, axes, dim: int):
    """Use ``axes`` for a dim of size ``dim`` only if it divides evenly."""
    if axes is None:
        return None
    if dim % axis_size(mesh, axes) != 0:
        return None
    return axes


def constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint against the active mesh (no-op un-meshed).

    ``axes`` entries are mesh axis names / tuples / None, one per dim;
    dims that do not divide evenly fall back to None.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    fixed = []
    for dim, a in zip(x.shape, axes):
        if a is not None and isinstance(a, tuple):
            a = tuple(x_ for x_ in a if x_ in mesh.axis_names) or None
        if a is not None and isinstance(a, str) and a not in mesh.axis_names:
            a = None
        fixed.append(_maybe(mesh, a, dim))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


def constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Shard dim0 as batch, replicate the rest."""
    return constrain(x, BATCH_AXES, *([None] * (x.ndim - 1)))


# ---------------------------------------------------------------------------
# Parameter partition rules
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _param_spec(mesh: Mesh, path: str, shape: Tuple[int, ...], fsdp: bool) -> P:
    """Partition rule for one parameter leaf.

    Scanned layer stacks live under ``layers/`` (or ``groups/``, ``enc/``,
    ``dec/``) with a leading depth dim which is never sharded.
    """
    # only scan-stacked containers carry a leading depth dim; "blocks/<i>/"
    # holds ordinary per-layer params
    stacked = bool(re.search(r"(layers|groups)/", path))
    core = shape[1:] if stacked and len(shape) >= 2 else shape
    lead: Tuple = (None,) if stacked and len(shape) >= 2 else ()
    dp = "data" if fsdp else None

    def out(*axes) -> P:
        return P(*lead, *axes)

    name = path.rsplit("/", 2)[-2:]
    leaf = "/".join(name)

    if len(core) == 0:
        return out()
    if "embed/w" in path or "pos_embed" in path:
        # (vocab, d): shard the model dim; vocab replicated (gather-friendly).
        return out(None, _maybe(mesh, "model", core[-1]))
    if len(core) == 3 and "experts" in path:
        e, a, b_ = core
        if _maybe(mesh, "model", e):
            # expert-parallel: experts over "model", optional fsdp inside.
            if leaf.endswith("w_out/w"):
                return out("model", None, _maybe(mesh, dp, b_))
            return out("model", _maybe(mesh, dp, a), None)
        # experts not divisible (qwen2-moe's 60): shard the ffn width instead.
        if leaf.endswith("w_out/w"):
            return out(None, _maybe(mesh, "model", a), _maybe(mesh, dp, b_))
        return out(None, _maybe(mesh, dp, a), _maybe(mesh, "model", b_))
    if len(core) == 2:
        d_in, d_out = core
        if any(k in path for k in ("wo/", "w_out/", "down/")):
            return out(_maybe(mesh, "model", d_in), _maybe(mesh, dp, d_out))
        # default: output-feature sharding (wq/wk/wv/w_in/w_gate/router/head)
        return out(_maybe(mesh, dp, d_in), _maybe(mesh, "model", d_out))
    if len(core) == 1:
        # biases of model-sharded projections follow their outputs; norms and
        # small recurrence params replicate.
        if any(k in path for k in ("wq/", "wk/", "wv/", "w_in/", "w_gate/")):
            return out(_maybe(mesh, "model", core[0]))
        return out(None)
    return out(*([None] * len(core)))


def _cache_spec(mesh: Mesh, path: str, shape: Tuple[int, ...], batch: int) -> P:
    """Partition rule for a decode-cache / recurrent-state leaf.

    KV caches shard their *sequence* dim over "model" (the GSPMD analogue of
    split-KV flash-decode: each model shard holds a contiguous KV span and
    the softmax reduction psums across shards) and batch over the DP axes.
    kv_heads are typically < |model| (GQA/MQA) so the head dim is never the
    sharded one.
    """
    dp = mesh_batch_axes(mesh)
    while dp and batch % axis_size(mesh, dp) != 0:
        dp = dp[1:]
    dpa = dp if dp else None
    leaf = path.rsplit("/", 1)[-1]
    nd = len(shape)
    if nd == 0:
        return P()
    stacked = nd >= 2 and shape[0] != batch and shape[1] == batch
    lead: Tuple = (None,) if stacked else ()
    core = shape[1:] if stacked else shape

    def out(*axes):
        axes = [_maybe(mesh, a, d) for a, d in zip(axes, core)]
        return P(*lead, *axes)

    if leaf in ("k", "v", "cross_k", "cross_v") and len(core) == 4:
        return out(dpa, "model", None, None)  # (B, S, KVH, hd): shard seq
    if leaf == "pos":
        return P(*lead) if len(core) == 0 else out(dpa)
    if leaf == "conv_buf" and len(core) == 3:
        return out(dpa, None, "model")
    if leaf == "h" and len(core) == 2:
        return out(dpa, "model")
    if leaf in ("c", "n", "m", "C") or len(core) >= 1:
        return out(dpa, *([None] * (len(core) - 1)))
    return P()


def cache_specs_tree(cache: Any, mesh: Mesh, batch: int):
    def rule(path, leaf):
        return _cache_spec(mesh, _path_str(path), tuple(leaf.shape), batch)

    return jax.tree_util.tree_map_with_path(rule, cache)


def batch_specs_tree(batch_tree: Any, mesh: Mesh, batch: int):
    """Model-input specs: shard dim0 (batch) over the DP axes."""
    def rule(path, leaf):
        dp = mesh_batch_axes(mesh)
        while dp and batch % axis_size(mesh, dp) != 0:
            dp = dp[1:]
        first = dp if dp else None
        return P(first, *([None] * (len(leaf.shape) - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def param_specs(params: Any, mesh: Mesh, fsdp: bool = False):
    """Pytree of PartitionSpec mirroring ``params`` (works on shapes too)."""

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        return _param_spec(mesh, _path_str(path), shape, fsdp)

    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(params: Any, mesh: Mesh, fsdp: bool = False):
    specs = param_specs(params, mesh, fsdp)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def apply_shardings(params: Any, mesh: Mesh, fsdp: bool = False):
    """Device-put concrete params onto the mesh (used by real runs)."""
    sh = param_shardings(params, mesh, fsdp)
    return jax.tree_util.tree_map(jax.device_put, params, sh)
