"""Device mesh construction over ICI × DCN.

The TPU-native communication substrate (SURVEY.md §2.3, §5.8): where the
reference wires NCCL process groups per parallelism strategy
(/root/reference/python/ray/train/torch/config.py:73,
python/ray/util/collective/collective.py:166), this framework expresses every
parallelism as axes of a single `jax.sharding.Mesh` — XLA emits the
collectives (psum/all-gather/reduce-scatter/ppermute/all-to-all) over ICI
within a slice and DCN across slices.

Canonical axis order (outer → inner, slowest → fastest varying):
    ("replica", "data", "fsdp", "expert", "pipeline", "context", "tensor")
DCN-parallel axes (replica/data) go outermost so cross-slice traffic is
minimized; tensor goes innermost so its collectives ride the shortest ICI
links (the scaling-book layout recipe).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# canonical axis order, outermost first
AXIS_ORDER = ("replica", "data", "fsdp", "expert", "pipeline", "context", "tensor")
# axes whose collectives may cross DCN (slices); the rest must stay on ICI
DCN_AXES = ("replica", "data")


@dataclass
class MeshSpec:
    """Logical parallelism spec, independent of physical devices."""

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    pipeline: int = 1
    expert: int = 1
    context: int = 1
    replica: int = 1
    # multislice: how many slices the replica/data axes span (1 = single slice)
    num_slices: int = 1

    def axis_sizes(self) -> dict[str, int]:
        return {name: getattr(self, name) for name in AXIS_ORDER}

    def total_devices(self) -> int:
        return math.prod(self.axis_sizes().values())

    def active_axes(self) -> tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if self.axis_sizes()[a] > 1)

    @classmethod
    def infer(cls, n_devices: int, *, tensor: int = 1, pipeline: int = 1,
              expert: int = 1, context: int = 1, fsdp: int | None = None,
              num_slices: int = 1) -> "MeshSpec":
        """Fill the fsdp/data axes to cover all devices: explicit model axes
        first, fsdp soaks up the rest (pure-DP when fsdp=1 is requested)."""
        model = tensor * pipeline * expert * context
        if n_devices % model != 0:
            raise ValueError(f"{n_devices} devices not divisible by model axes {model}")
        rest = n_devices // model
        if fsdp is None:
            fsdp = rest
            data = 1
        else:
            if rest % fsdp != 0:
                raise ValueError(f"residual {rest} not divisible by fsdp={fsdp}")
            data = rest // fsdp
        return cls(data=data, fsdp=fsdp, tensor=tensor, pipeline=pipeline,
                   expert=expert, context=context, num_slices=num_slices)


def build_mesh(spec: MeshSpec, devices=None) -> Mesh:
    """Build a Mesh whose physical layout respects ICI topology.

    Single-slice: `mesh_utils.create_device_mesh` lays axes onto the torus so
    inner axes get contiguous ICI neighborhoods. Multislice:
    `create_hybrid_device_mesh` puts DCN axes across slices.
    """
    if devices is None:
        devices = jax.devices()
    sizes = spec.axis_sizes()
    names = tuple(sizes.keys())
    shape = tuple(sizes[n] for n in names)
    n = math.prod(shape)
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    devices = devices[:n]
    if spec.num_slices > 1:
        dcn_shape = tuple(
            sizes[a] if a in DCN_AXES else 1 for a in names)
        if math.prod(dcn_shape) != spec.num_slices:
            raise ValueError(
                f"DCN axes {DCN_AXES} product {math.prod(dcn_shape)} "
                f"!= num_slices {spec.num_slices}")
        ici_shape = tuple(
            1 if a in DCN_AXES else sizes[a] for a in names)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
    else:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, names)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshSpec(), jax.devices()[:1])


def validate_spec_for_slice(spec: MeshSpec, *, ici_devices: int) -> None:
    """Reject specs whose ICI-only axes don't fit in one slice — collectives on
    tensor/context/pipeline axes must never cross DCN."""
    ici = math.prod(v for a, v in spec.axis_sizes().items() if a not in DCN_AXES)
    if ici > ici_devices:
        raise ValueError(
            f"ICI axes need {ici} devices but a slice has {ici_devices}; "
            f"move parallelism to the data/replica (DCN) axes")
