"""Production mesh definitions.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state. The single-pod mesh is
16×16 = 256 chips (TPU v5e pod); multi-pod adds a leading "pod" axis:
2×16×16 = 512 chips. Axis roles:

  pod   — pure data parallelism across pods (DCI-connected; the gradient
          compression path targets this axis),
  data  — data parallelism + FSDP parameter storage within a pod,
  model — tensor / expert parallelism (ICI-connected ring).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU tests."""
    return _mesh((1, 1), ("data", "model"))
