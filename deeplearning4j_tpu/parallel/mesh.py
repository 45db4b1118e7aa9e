"""Device-mesh helpers — the TPU-native replacement for the reference's
device-affinity machinery (AffinityManager / thread-per-device replicas,
deeplearning4j-scaleout/.../parallelism/ParallelWrapper.java:133-134).

On TPU, "workers" are mesh axes, not threads: a `jax.sharding.Mesh` names
the device grid and `PartitionSpec`s say how each array maps onto it. XLA
GSPMD then inserts the ICI collectives (psum/all-gather) that the reference
performed by explicit parameter copies between worker threads.

Axis vocabulary used throughout the framework:
    "data"  — data parallelism (batch axis sharding)
    "model" — tensor/model parallelism (feature axis sharding)
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
MODEL_AXIS = "model"


def data_parallel_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all (or the given) devices with a single "data" axis —
    the topology of the reference's ParallelWrapper (one replica per
    device)."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (DATA_AXIS,))


def mesh_2d(data: int, model: int, devices: Optional[Sequence] = None) -> Mesh:
    """data × model mesh for combined DP+TP. `data * model` must equal the
    device count."""
    devices = list(devices) if devices is not None else jax.devices()
    if data * model != len(devices):
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {len(devices)}"
        )
    return Mesh(np.array(devices).reshape(data, model), (DATA_AXIS, MODEL_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully replicated sharding (parameters, updater state)."""
    return NamedSharding(mesh, PartitionSpec())


def batch_sharded(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard dim 0 (the batch) across the data axis."""
    return NamedSharding(mesh, PartitionSpec(axis))


def n_devices() -> int:
    return jax.device_count()


def data_shards(mesh: Mesh) -> int:
    """Number of shards along the data axis (NOT the total device count —
    on a 2-D data×model mesh only the data axis splits the batch)."""
    return int(mesh.shape[DATA_AXIS])


def placement_for_batch(mesh: Mesh, n_examples: int) -> NamedSharding:
    """Placement policy for a batch of n examples: shard dim 0 over the
    data axis when divisible, otherwise fall back to replicated (the tail
    batch of an epoch) — still correct, just not distributed. The single
    source of truth for training AND serving paths."""
    if n_examples % data_shards(mesh) == 0:
        return batch_sharded(mesh)
    return replicated(mesh)


def pad_wrap(a: np.ndarray, multiple: int) -> np.ndarray:
    """Pad dim 0 up to the next multiple by cyclically repeating examples
    (np.resize wraps, correct even when the pad exceeds the batch). Used
    by every pad-and-slice serving/training path so the policy lives in
    one place."""
    n = a.shape[0]
    pad = (-n) % multiple
    return np.resize(a, (n + pad,) + a.shape[1:]) if pad else a
