"""Production mesh construction.

``make_production_mesh()`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and nothing here may run before that.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis is the
cross-pod (DCN/slower-ICI) axis and carries only batch-parallel traffic.
"""

from __future__ import annotations

import jax

from repro.runtime.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS, auto_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod else (DATA_AXIS, MODEL_AXIS)
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_smoke_mesh(*, data: int = 1, model: int = 1):
    """Tiny mesh over however many devices the test environment has."""
    return jax.make_mesh((data, model), (DATA_AXIS, MODEL_AXIS),
                         axis_types=auto_axes(2))
