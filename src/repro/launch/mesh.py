"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  The production target is TPU v5e:
one pod = 16x16 = 256 chips, multi-pod = 2 pods = 512 chips with the outer
``pod`` axis crossing the DCN.

Axes are ``Auto``: the model places its activations with
``with_sharding_constraint`` against the ambient mesh, which explicit
axes (``jax.make_mesh``'s default since JAX 0.7) do not allow.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Small mesh for CI on 8 forced host devices."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def describe(mesh) -> str:
    return " x ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
