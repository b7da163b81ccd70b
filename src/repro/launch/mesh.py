"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first
device query, and tests must keep seeing 1 device.

Axis roles (DESIGN.md §6):
  pod   — data parallelism across pods (slow inter-pod links)
  data  — FSDP + batch sharding within a pod
  model — tensor/expert/sequence parallelism within a pod

Every mesh is built with ``AxisType.Auto`` axes: the sharding layer
(``distributed.sharding.constrain``) places activations with
``with_sharding_constraint``, which only accepts Auto axes, while
``jax.make_mesh`` defaults to Explicit ones.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (compiler-propagated)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1):
    """Arbitrary mesh for tests/examples (e.g. (2, 2) on 4 CPU devices)."""
    if pods > 1:
        return auto_mesh((pods, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))
