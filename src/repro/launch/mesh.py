"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device;
only launch/dryrun.py forces the 512-device host platform.

Topology: TPU v5e pods of 256 chips in a 16x16 ICI torus. Single-pod mesh
(16, 16) = ("data", "model"); multi-pod (2, 16, 16) = ("pod", "data",
"model") where the "pod" axis crosses the slower DCN links (gradient
all-reduce over it is hierarchical + optionally int8-compressed, see
optim/grad_compress.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many devices exist (tests / CPU)."""
    return _make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def axis_size(mesh, name) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]
