"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The single-pod mesh is 16×16 = 256 chips (("data", "model"));
multi-pod adds a leading "pod" axis: 2×16×16 = 512 chips.  The dry-run
launcher force-creates 512 host devices BEFORE importing jax (see
dryrun.py); ``chip_smoke.py --chips 4`` lays ``make_test_mesh`` over the
four chips of one host.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(n_nodes: int = 8, axis: str = "node"):
    """1-D mesh for λPipe multicast / pipeline: forced host devices in
    tests, real chips in ``chip_smoke.py --chips 4``."""
    return _make_mesh((n_nodes,), (axis,))


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
