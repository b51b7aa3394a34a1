"""The JAX sharding calls the mesh code shares, written for JAX 0.9.

The code targets the installed JAX (0.9.0): top-level ``jax.shard_map``
with varying-manual-axes typing (``check_vma``), ``jax.sharding.AxisType``
and ``lax.axis_size``/``lax.pcast``.  These wrappers give the mesh code
one spelling of each; if a later JAX renames one of them, this module is
the one place to change (``tests/test_skeleton.py`` asserts that no
version probe lives anywhere else).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax import lax

__all__ = ["make_mesh", "shard_map", "pvary", "needs_pvary", "axis_size",
           "vma_align"]


def axis_size(axis_name: str) -> int:
    """Static size of a manual mesh axis, from inside ``shard_map``."""
    return lax.axis_size(axis_name)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              ) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    types = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, axis_types=types)


def shard_map(fn, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None):
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default (on)."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def pvary(x: Any, axis_names: Sequence[str]) -> Any:
    """Mark ``x`` as varying over ``axis_names`` (``lax.pcast``)."""
    return lax.pcast(x, tuple(axis_names), to="varying")


def needs_pvary(x: Any, axis_name: str) -> bool:
    """True if ``x`` does not yet vary over ``axis_name``."""
    return axis_name not in jax.typeof(x).vma


def vma_align(x: Any, axis_names: Sequence[str]) -> Any:
    """Make ``x`` vary over every axis in ``axis_names`` it does not vary
    over yet.

    The skeleton mesh lowering mixes values of different provenance inside
    one ``shard_map`` body — stage-invariant microbatches, worker-varying
    farm buffers, ``axis_index``-derived stage selectors — and the
    varying-manual-axes typing requires the operands of ``select_n`` /
    ``where`` / ``ppermute`` to agree."""
    missing = tuple(a for a in axis_names if needs_pvary(x, a))
    return pvary(x, missing) if missing else x
