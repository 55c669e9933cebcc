"""The two mesh entry points this repo shares, spelled once for the
installed JAX (0.9): every mesh is Auto-typed, and ``shard_map`` is
manual over ``axis_names`` and auto over the rest of the mesh.
"""
from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """Auto-typed mesh."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names),
                         devices=devices)


def shard_map(f, mesh, in_specs, out_specs, axis_names: frozenset,
              check: bool = False):
    """Manual over ``axis_names``, auto over the rest of ``mesh``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset(axis_names), check_vma=check)
