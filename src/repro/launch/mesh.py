"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model); 'pod' is the DCI
axis that carries only the data-parallel gradient reduction (lowest
collective depth on the highest-latency fabric — the schedule EDAN's cost
model recommends, DESIGN.md §5).  Defined as a function so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_axis_sizes(mesh) -> list:
    return [(name, int(mesh.shape[name])) for name in mesh.axis_names]
