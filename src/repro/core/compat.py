"""Mesh and shard_map constructors with the repo's fixed defaults.

Every mesh/shard_map construction in the repo goes through this module:
meshes get explicit ``Auto`` axis types (GSPMD places the collectives),
and shard_map bodies run with ``check_vma=False`` because the dataplane
threads per-rank state whose replication JAX cannot infer.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with explicit-Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["make_mesh", "shard_map"]
