"""The repo's one convention for building meshes and shard_maps.

Every mesh and shard_map construction goes through these two wrappers so
the choice lives in one file: meshes use ``Auto`` axis types (JAX's
default is ``Explicit``, which would put shardings into array types), and
shard_maps turn the varying-manual-axes check off, because the
collectives inside are hand-written and the checker rejects valid manual
patterns like the all_to_all routing.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
