"""Production meshes (TPU v5e pods).

Defined as functions so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before any jax
import, ordinary runs see the real (single) device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with every axis Auto.

    ``jax.make_mesh`` defaults to Explicit axes, under which
    ``with_sharding_constraint`` (``models.sharding.shard_hint``) and the
    placement-only sharding of the batch engine refuse the mesh axes.
    Every mesh this package builds goes through here.
    """
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_engine_mesh(data_shards: int, model_shards: int = 1,
                     pods: int = 1):
    """("data", "model") — or, with ``pods > 1``,
    ("pod", "data", "model") — mesh over the first pods*data*model
    visible devices.

    Pod-major, then data-major, row-major device order — the layout the
    RANL engines assume and that ``hlo_analysis.mesh_axis_groups``
    reproduces when classifying collectives by mesh axis.  Devices of
    one pod are contiguous, so an intra-pod data-axis psum never
    crosses a pod boundary.  ``model_shards=1`` degenerates to the
    worker-only sharding of the sharded engine (plus a size-1 model
    axis); ``pods=1`` keeps the historical 2-D mesh (no pod axis).
    """
    n = pods * data_shards * model_shards
    if jax.device_count() < n:
        raise ValueError(
            f"mesh ({pods}, {data_shards}, {model_shards}) needs {n} "
            f"devices but jax sees {jax.device_count()}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} to emulate them")
    if pods > 1:
        devs = np.array(jax.devices()[:n]).reshape(
            pods, data_shards, model_shards)
        return jax.sharding.Mesh(devs, ("pod", "data", "model"))
    devs = np.array(jax.devices()[:n]).reshape(data_shards, model_shards)
    return jax.sharding.Mesh(devs, ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh with the production axis names (tests)."""
    return make_mesh((1, 1), ("data", "model"))


def data_shards(mesh) -> int:
    """Total batch/worker shards = product of pod-and-data axis sizes."""
    n = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            n *= mesh.shape[name]
    return n


def model_shards(mesh) -> int:
    return mesh.shape.get("model", 1)
