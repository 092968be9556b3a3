"""Mesh and shard_map construction, in one place.

Every module that builds a mesh or a ``shard_map`` region goes through these
two helpers, so the repository's choices for them (Auto axis types; no
replication check) are made once.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), names, axis_types=(AxisType.Auto,) * len(names)
    )


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off — dictionary builds
    start from shard-invariant empties, which the checker cannot see."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
