"""Production mesh factories.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init — the dry-run sets
XLA_FLAGS before importing anything else).

Mesh construction goes through :func:`repro.compat.make_mesh` (every
axis ``AxisType.Auto``); the tests build their meshes with the same
helper.
"""
from __future__ import annotations

from ..compat import make_mesh

__all__ = ["make_production_mesh", "make_test_mesh", "DP_AXES", "MODEL_AXIS"]

MODEL_AXIS = "model"


def DP_AXES(multi_pod: bool = False):
    return ("pod", "data") if multi_pod else ("data",)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for multi-device tests (requires forced host devices)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
