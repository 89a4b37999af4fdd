"""The one import point for ``shard_map`` and the ``jax.sharding`` names.

Every shard_map call in this package goes through this module, and hot
modules import ``Mesh``/``NamedSharding``/``PartitionSpec`` from here
instead of from jax directly, so a relocation in jax (``shard_map`` has
moved before) means editing one file. mxlint **MX020** enforces the
routing statically. The wrapper also accepts the package's
``DeviceMesh`` in place of a raw ``jax.sharding.Mesh``.
"""
from __future__ import annotations

import functools

# the sharding type names, re-exported for the whole package (MX020)
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: F401

from jax import shard_map as _shard_map

__all__ = ["shard_map", "Mesh", "NamedSharding", "PartitionSpec"]


@functools.wraps(_shard_map)
def shard_map(f, mesh, in_specs, out_specs, check_vma=True, **kwargs):
    # accept the package's DeviceMesh wrapper transparently (every
    # caller otherwise repeats the getattr unwrap by hand)
    mesh = getattr(mesh, "mesh", mesh)
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_vma, **kwargs)
