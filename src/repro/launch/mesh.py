"""Production mesh definitions.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches JAX device state.  The single-pod mesh
is 16×16 = 256 chips (``data``, ``model``); the multi-pod mesh adds a
``pod`` axis: 2×16×16 = 512 chips, with the pod axis traversing DCN.
"""

from __future__ import annotations

import jax

from repro.core.cost_model import MeshSpec


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    The installed JAX defaults new meshes to ``Explicit`` axes; plans
    are applied through GSPMD propagation (``jit`` in/out shardings plus
    the models' ``constrain`` hooks), which needs ``Auto`` axes.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    """Abstract description for the cost model (no devices touched)."""
    if multi_pod:
        return MeshSpec(("pod", "data", "model"), (2, 16, 16),
                        dcn_axes=("pod",))
    return MeshSpec(("data", "model"), (16, 16))


def smoke_mesh_spec() -> MeshSpec:
    return MeshSpec(("data", "model"), (2, 2))
