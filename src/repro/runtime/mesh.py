"""Mesh construction helpers.

The production mesh (see launch/mesh.py) is (data=16, model=16) per pod and
(pod=2, data=16, model=16) for the multi-pod dry-run.  Everything in this
module is a pure function of an existing `jax.sharding.Mesh`; importing it
never touches jax device state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

# Canonical physical axis names, outermost first.  "pod" is the slowest /
# cross-ICI axis, "data" is the pure-replication/batch axis, "model" is the
# tensor-parallel axis (fast ICI ring).
POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"
ALL_AXES = (POD_AXIS, DATA_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh description (used by configs and the pilot system).

    A PilotSlice is provisioned against a MeshSpec; the payload never gets to
    change it (late binding swaps the executable, not the resource grant).
    """

    shape: tuple[int, ...]
    axes: tuple[str, ...]

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} / axes {self.axes} mismatch")
        for a in self.axes:
            if a not in ALL_AXES:
                raise ValueError(f"unknown mesh axis {a!r}; expected {ALL_AXES}")

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    def axis_size(self, name: str) -> int:
        if name not in self.axes:
            return 1
        return self.shape[self.axes.index(name)]

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        """Auto axes: sharding follows the constraints the model code sets
        (``with_sharding_constraint`` refuses Explicit axes)."""
        if devices is None:
            return jax.make_mesh(self.shape, self.axes,
                                 axis_types=auto_axes(len(self.axes)))
        import numpy as np

        devs = np.asarray(devices).reshape(self.shape)
        return Mesh(devs, self.axes, axis_types=auto_axes(len(self.axes)))


def auto_axes(n: int) -> tuple[AxisType, ...]:
    return (AxisType.Auto,) * n


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    return MeshSpec(tuple(shape), tuple(axes)).build()


def parse_mesh_shape(text: str) -> tuple[int, ...]:
    """Parse the CLI/image mesh-shape syntax ``"AxB"`` (e.g. ``"1x2"``,
    ``"2x4"``) into a shape tuple.  A bare integer means ``1xN`` (pure
    tensor parallelism)."""
    parts = [p for p in str(text).lower().split("x") if p]
    if not parts:
        raise ValueError(f"bad mesh shape {text!r}; expected 'AxB'")
    try:
        shape = tuple(int(p) for p in parts)
    except ValueError as e:
        raise ValueError(f"bad mesh shape {text!r}; expected 'AxB'") from e
    if any(s < 1 for s in shape):
        raise ValueError(f"bad mesh shape {text!r}; dims must be >= 1")
    if len(shape) == 1:
        shape = (1,) + shape
    if len(shape) != 2:
        raise ValueError(f"bad mesh shape {text!r}; serve meshes are 2-D "
                         f"(data x model)")
    return shape


def serve_mesh_spec(shape: tuple[int, ...] | str) -> MeshSpec:
    """The serve-path mesh: ``(data, model)``.  The model axis carries the
    tensor-parallel shards of params and paged-KV pools; the data axis is
    pure replication headroom (slots are not batch-sharded in serve)."""
    if isinstance(shape, str):
        shape = parse_mesh_shape(shape)
    shape = tuple(int(s) for s in shape)
    if len(shape) != 2:
        raise ValueError(f"serve mesh shape must be 2-D (data, model), "
                         f"got {shape}")
    return MeshSpec(shape, (DATA_AXIS, MODEL_AXIS))


def serve_mesh(shape: tuple[int, ...] | str,
               devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the serve mesh for ``shape`` (``"AxB"`` or a tuple)."""
    return serve_mesh_spec(shape).build(devices)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    """Size of a named axis; 1 if the mesh does not have it."""
    return mesh.shape.get(name, 1) if hasattr(mesh.shape, "get") else dict(
        zip(mesh.axis_names, mesh.devices.shape)
    ).get(name, 1)


def tp_heads(mesh: Mesh | None, num_kv_heads: int, num_heads: int) -> bool:
    """True iff attention kernels can be head-sharded on this mesh: the
    model axis must divide the KV head count (whole kv-groups per shard)."""
    if mesh is None:
        return False
    m = mesh_axis_size(mesh, MODEL_AXIS)
    return m > 1 and num_kv_heads % m == 0 and num_heads % m == 0


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Physical axes the global batch is sharded over (pod+data)."""
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.axis_names)


def batch_parallelism(mesh: Mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh_axis_size(mesh, a)
    return out


def model_parallelism(mesh: Mesh) -> int:
    return mesh_axis_size(mesh, MODEL_AXIS)
