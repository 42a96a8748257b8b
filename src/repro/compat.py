"""The one place the code base builds meshes, ``shard_map``s and Pallas-TPU
compiler params (staticcheck rule C3 routes every such call through here).

Written for the installed jax 0.9: ``jax.make_mesh`` with explicit
``AxisType.Auto`` axes, top-level ``jax.shard_map`` and
``pltpu.CompilerParams``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto axis types."""
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)


def pltpu_compiler_params(**kwargs):
    """Pallas-TPU compiler params."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
