"""Device discovery and mesh construction for TPU topologies.

ref: nd4j Nd4jBackend SPI + org.nd4j.jita.allocator (device discovery and
affinity) and the ParallelWrapper device-pinning logic
(org.deeplearning4j.parallelism.ParallelWrapper). On TPU there is no
per-device affinity management in user space: devices come from PJRT
(libtpu), and parallel placement is expressed declaratively as a
``jax.sharding.Mesh`` + ``PartitionSpec`` and compiled by XLA.

Canonical mesh axis names (used framework-wide, see parallel/specs.py):

- ``data``  — data parallelism (batch split, gradient all-reduce over ICI)
- ``fsdp``  — ZeRO-style parameter sharding (all-gather on use)
- ``model`` — tensor (Megatron-style) parallelism
- ``seq``   — sequence/context parallelism (ring attention)
- ``stage`` — pipeline parallelism (GPipe microbatch pipeline)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
STAGE_AXIS = "stage"
EXPERT_AXIS = "expert"

ALL_AXES = (DATA_AXIS, FSDP_AXIS, MODEL_AXIS, SEQ_AXIS, STAGE_AXIS,
            EXPERT_AXIS)


def devices(platform: Optional[str] = None):
    """Enumerate accelerator devices (ref: NativeOps getAvailableDevices)."""
    return jax.devices(platform) if platform else jax.devices()


def device_count() -> int:
    return jax.device_count()


def is_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: axis name → size. Size -1 means 'absorb remainder'.

    Example: ``MeshSpec(data=-1, model=4)`` on 32 chips → mesh (8, 4) with
    axes ("data", "model").
    """

    data: int = -1
    fsdp: int = 1
    model: int = 1
    seq: int = 1
    stage: int = 1
    expert: int = 1

    def resolve(self, n_devices: Optional[int] = None) -> dict:
        n = n_devices if n_devices is not None else jax.device_count()
        sizes = {
            DATA_AXIS: self.data,
            FSDP_AXIS: self.fsdp,
            MODEL_AXIS: self.model,
            SEQ_AXIS: self.seq,
            STAGE_AXIS: self.stage,
            EXPERT_AXIS: self.expert,
        }
        wildcard = [k for k, v in sizes.items() if v == -1]
        if len(wildcard) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wildcard:
            if n % fixed != 0:
                raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
            sizes[wildcard[0]] = n // fixed
        elif fixed != n:
            raise ValueError(f"mesh {sizes} wants {fixed} devices, have {n}")
        return sizes


def build_mesh(
    spec: MeshSpec | None = None,
    *,
    devices_: Optional[Sequence] = None,
    drop_trivial_axes: bool = True,
) -> Mesh:
    """Build a ``jax.sharding.Mesh`` over the available devices.

    Axes of size 1 are dropped by default so PartitionSpecs naming absent axes
    still work (PartitionSpec with an unknown axis errors; specs are built
    from the mesh's actual axis names via parallel/specs.py).
    """
    spec = spec or MeshSpec()
    devs = list(devices_) if devices_ is not None else jax.devices()
    sizes = spec.resolve(len(devs))
    if drop_trivial_axes:
        sizes = {k: v for k, v in sizes.items() if v > 1}
        if not sizes:
            sizes = {DATA_AXIS: 1}
    shape = tuple(sizes.values())
    names = tuple(sizes.keys())
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, names)


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,)), (DATA_AXIS,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def data_like_axes(mesh: Mesh) -> tuple:
    """Mesh axes the batch dimension shards over (single source of truth
    for specs.batch_spec / pipeline_apply / batch_sharding)."""
    return tuple(a for a in (DATA_AXIS, FSDP_AXIS) if a in mesh.axis_names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard leading (batch) dim over every data-like axis present."""
    axes = data_like_axes(mesh)
    if not axes:
        return NamedSharding(mesh, PartitionSpec())
    return NamedSharding(mesh, PartitionSpec(axes))
