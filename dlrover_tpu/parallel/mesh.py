"""Device-mesh construction over ICI / DCN.

TPU-native replacement for the reference's named-process-group fabric
(``create_parallel_group``, atorch/atorch/distributed/distributed.py:323):
instead of NCCL process groups per parallelism kind, one
``jax.sharding.Mesh`` carries every axis and XLA compiles the collectives
onto ICI (intra-slice) and DCN (cross-slice).

Axis conventions (innermost = most ICI-local):

- ``dp``   pure data parallel (replicated params) — rides DCN across slices
- ``pp``   pipeline stages (collective-permute microbatching)
- ``ep``   expert parallel (MoE all-to-all)
- ``fsdp`` fully-sharded data parallel (ZeRO-3 ≡ params sharded on this axis)
- ``sp``   sequence/context parallel (Ulysses all-to-all / ring permute)
- ``tp``   tensor (Megatron-style) model parallel — innermost, pure ICI
"""

import math
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import AxisType, Mesh

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

MESH_AXES = ("dp", "pp", "ep", "fsdp", "sp", "tp")


@dataclass
class MeshConfig:
    """Sizes for each mesh axis; -1 means "absorb remaining devices"."""

    dp: int = -1
    pp: int = 1
    ep: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    # Number of DCN-connected slices; the outermost axes (dp first) are laid
    # out across slices so their collectives ride DCN.
    num_slices: int = 1

    def resolved_sizes(self, n_devices: int) -> Dict[str, int]:
        sizes = {
            "dp": self.dp,
            "pp": self.pp,
            "ep": self.ep,
            "fsdp": self.fsdp,
            "sp": self.sp,
            "tp": self.tp,
        }
        wildcard = [k for k, v in sizes.items() if v == -1]
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if n_devices % fixed:
            raise ValueError(
                f"mesh sizes {sizes} do not divide device count {n_devices}"
            )
        if len(wildcard) > 1:
            raise ValueError("at most one axis may be -1")
        if wildcard:
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh sizes {sizes} (={fixed}) != device count {n_devices}"
            )
        return sizes

    @classmethod
    def from_dict(cls, d: Dict) -> "MeshConfig":
        return cls(**{k: v for k, v in d.items() if k in cls.__dataclass_fields__})


def build_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 6-axis mesh; ICI-aware device order on real TPU topologies.

    On TPU, ``mesh_utils.create_device_mesh`` permutes devices so that
    innermost axes map to physically-adjacent chips (tp collectives never
    leave a torus neighborhood). Multi-slice jobs use
    ``create_hybrid_device_mesh`` so outer axes cross DCN.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    sizes = config.resolved_sizes(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)

    if config.num_slices > 1:
        if sizes["dp"] % config.num_slices:
            raise ValueError(
                f"dp={sizes['dp']} must be divisible by "
                f"num_slices={config.num_slices}"
            )
        per_slice = tuple(
            (sizes[a] // config.num_slices if a == "dp" else sizes[a])
            for a in MESH_AXES
        )
        dcn = tuple(
            (config.num_slices if a == "dp" else 1) for a in MESH_AXES
        )
        # Gate on the number of DISTINCT slice ids, not the mere
        # presence of the attribute: multi-process CPU devices carry a
        # slice_index too (all 0), which must take the emulation path.
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        if len(slice_ids) > 1:
            # real multi-slice hardware: let any misconfiguration
            # (wrong num_slices vs the job's actual slices, ...) raise —
            # a silent row-major fallback here would span inner axes
            # across DCN with no error, just drastically slow collectives
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn, devices=devices
            )
        else:
            # single-slice or virtual/CPU devices: a plain row-major
            # reshape IS slice-major order (dp is the outermost mesh
            # axis, so contiguous device blocks land one per emulated
            # slice) — keeping the multi-slice code path compilable and
            # testable off multi-slice hardware. Safe because with at
            # most one real slice no inner axis can silently span DCN.
            logger.info(
                "single physical slice; emulating %d slices "
                "with contiguous device blocks",
                config.num_slices,
            )
            dev_array = np.asarray(devices).reshape(shape)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except (ValueError, AssertionError, NotImplementedError):
            # CPU / odd topologies: plain row-major order is fine.
            dev_array = np.asarray(devices).reshape(shape)

    mesh = Mesh(
        dev_array,
        MESH_AXES,
        axis_types=(AxisType.Auto,) * len(MESH_AXES),
    )
    logger.info("built mesh %s over %d devices", sizes, len(devices))
    return mesh


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(
        np.asarray([device]).reshape((1,) * len(MESH_AXES)),
        MESH_AXES,
        axis_types=(AxisType.Auto,) * len(MESH_AXES),
    )


def data_axes() -> tuple:
    """Mesh axes over which the global batch is sharded."""
    return ("dp", "fsdp")


def axis_crosses_dcn(mesh: Mesh, axis: str) -> bool:
    """True when stepping along ``axis`` can change TPU slice — i.e. a
    collective over ``axis`` pays DCN bandwidth, not just ICI. Devices
    without a ``slice_index`` (CPU, single-slice) never cross."""
    if mesh.shape.get(axis, 1) <= 1:
        return False
    dev = mesh.devices
    idx = mesh.axis_names.index(axis)
    # one pencil along `axis` through each point of the complementary grid
    moved = np.moveaxis(dev, idx, 0)
    for pencil in moved.reshape(moved.shape[0], -1).T:
        ids = {getattr(d, "slice_index", None) for d in pencil}
        ids.discard(None)
        if len(ids) > 1:
            return True
    return False
