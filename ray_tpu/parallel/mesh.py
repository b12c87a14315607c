"""Device-mesh planning: the axes every parallelism strategy hangs off.

The reference has no intra-model parallelism (SURVEY §2.5, verified grep);
its scaling unit is the process (NCCL groups between actor processes). Here
the scaling unit is the **mesh axis**: DP/FSDP/TP/SP/PP/EP are all just named
axes of one ``jax.sharding.Mesh``, and XLA inserts the collectives. Axis
order follows the scaling-book recipe: model axes (tensor) fastest-varying so
their collectives ride nearest-neighbor ICI links; pipeline outermost so its
point-to-point traffic can cross slices (DCN) if needed.
"""

from __future__ import annotations
import logging

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger("ray_tpu")

# Canonical axis order, outermost (slowest-varying, DCN-tolerant) first.
AXIS_ORDER = ("pipe", "data", "fsdp", "expert", "seq", "tensor")


@dataclass
class MeshConfig:
    """Sizes for each parallelism axis; -1 = absorb remaining devices.

    data   — pure data parallelism (gradient psum)
    fsdp   — data parallelism with parameter sharding (ZeRO-3 style)
    tensor — tensor/model parallelism (Megatron-style, innermost on ICI)
    seq    — sequence/context parallelism (ring attention)
    pipe   — pipeline stages (outermost; DCN across slices)
    expert — expert parallelism (MoE all_to_all)
    """

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def resolved(self, n_devices: int) -> "MeshConfig":
        sizes = {name: getattr(self, name) for name in AXIS_ORDER}
        wild = [k for k, v in sizes.items() if v == -1]
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if len(wild) > 1:
            raise ValueError("at most one axis may be -1")
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wild[0]] = n_devices // fixed
        total = math.prod(sizes.values())
        if total != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {total} devices, have {n_devices}")
        out = MeshConfig(**sizes)
        return out

    def axis_sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, name) for name in AXIS_ORDER)

    def nontrivial_axes(self) -> List[str]:
        return [n for n in AXIS_ORDER if getattr(self, n) > 1]


def build_mesh(config: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build the named mesh. On real TPU topologies use
    ``mesh_utils.create_device_mesh`` so axis adjacency matches the physical
    torus; elsewhere (CPU tests) a plain reshape suffices."""
    devices = list(devices if devices is not None else jax.devices())
    cfg = config.resolved(len(devices))
    shape = cfg.axis_sizes()
    arr = None
    if devices and devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        try:
            arr = mesh_utils.create_device_mesh(shape, devices=devices)
        except Exception as e:  # noqa: BLE001 - mesh_utils raises several types
            logger.warning(
                "create_device_mesh%s failed on %d %s device(s); axis "
                "adjacency will not match the physical torus: %s: %s",
                shape, len(devices), devices[0].device_kind,
                type(e).__name__, e)
    if arr is None:
        arr = np.array(devices).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def single_axis_mesh(axis: str = "data",
                     devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    sizes = {a: 1 for a in AXIS_ORDER}
    sizes[axis] = len(devices)
    return build_mesh(MeshConfig(**sizes), devices)
