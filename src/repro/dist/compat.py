"""The one module that knows the installed JAX release (0.9).

Call sites pass ``check=`` to :func:`shard_map`; only this module names
JAX's own keyword for the replication check (``CHECK_KW``), which is
also the parameter the static analysis reads on a staged ``shard_map``
equation.
"""
from __future__ import annotations

import jax

CHECK_KW = "check_vma"


def shard_map(f, *, mesh, in_specs, out_specs, check: bool):
    """``jax.shard_map`` with the replication check named ``check``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **{CHECK_KW: check})


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis in ``Auto`` mode."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
