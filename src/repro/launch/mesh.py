"""Mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state.

Axis roles:
  pod   — pure data parallelism across pods (DCN-crossing collectives are
          gradient all-reduces only; optionally the pipeline axis)
  data  — data parallel + FSDP (weights shard their contracting dim here)
  model — tensor/expert/context parallel within a pod (ICI)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """Arbitrary mesh for tests/examples (e.g. (1,1) on one CPU).

    Every axis is ``AxisType.Auto``: the sharding rules here annotate
    params and activations with ``NamedSharding``/constraints and let
    GSPMD propagate the rest.  ``jax.make_mesh``'s default (Explicit
    axes) would instead make sharding part of every array's type, and
    ops such as the embedding gather raise ``ShardingTypeError``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(tp: Optional[int] = None) -> jax.sharding.Mesh:
    """Whatever devices exist, as a (data, model) mesh.

    ``tp`` sets the ``model`` axis extent (default 1 — pure data
    parallel, the historical behavior); it must divide the local device
    count.  ``make_local_mesh(tp=2)`` on a 8-device host is the local
    TP testing mesh the hardcoded ``(n, 1)`` used to make impossible."""
    n = len(jax.devices())
    tp = tp or 1
    if tp < 1 or n % tp != 0:
        from ..serving.errors import MeshConfigError
        raise MeshConfigError(
            f"tp={tp} must be >= 1 and divide the local device "
            f"count ({n})")
    return make_mesh((n // tp, tp), ("data", "model"))


def mesh_for_serving(n_devices: Optional[int] = None, tp: int = 1
                     ) -> jax.sharding.Mesh:
    """A validated (data, model) serving mesh over ``n_devices``
    (default: all local devices) with tensor-parallel degree ``tp``.

    Raises :class:`repro.serving.errors.MeshConfigError` — never a bare
    ``ValueError`` — when the shape can't be built: ``tp`` not dividing
    ``n_devices``, or more devices requested than exist.  The serving
    engine takes the result directly: ``ServingEngine(..., mesh=...)``
    runs ``data`` replicas of the slot space and shards heads/MLP width
    over ``model``."""
    from ..serving.errors import MeshConfigError
    avail = len(jax.devices())
    n = n_devices if n_devices is not None else avail
    if n < 1 or n > avail:
        raise MeshConfigError(
            f"n_devices={n} out of range: {avail} device(s) available")
    if tp < 1 or n % tp != 0:
        raise MeshConfigError(
            f"tp={tp} must be >= 1 and divide n_devices={n}")
    devices = np.asarray(jax.devices()[:n]).reshape(n // tp, tp)
    return jax.sharding.Mesh(devices, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)


def data_axis_names(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes over which the batch is sharded (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_info(mesh: jax.sharding.Mesh) -> dict:
    return {
        "axis_names": mesh.axis_names,
        "shape": dict(mesh.shape),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
    }
