"""Ambient-mesh sharding constraints usable inside model code.

``constrain(x, *spec)`` applies ``with_sharding_constraint`` when a
non-trivial mesh is ambient (``jax.set_mesh``), and is a no-op on a single
device / no mesh — model code stays mesh-agnostic and smoke tests run
unchanged.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


def ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None when there is none."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_in_mesh(name: str) -> bool:
    m = ambient_mesh()
    return bool(m and name in m.axis_names)


def dp_spec() -> Optional[Tuple[str, ...]]:
    m = ambient_mesh()
    if not m:
        return None
    axes = tuple(a for a in m.axis_names if a in ("pod", "data"))
    return axes or None


def constrain(x, *spec):
    """with_sharding_constraint against the ambient mesh (no-op without
    one). Axis names absent from the mesh are dropped to None."""
    m = ambient_mesh()
    if m is None:
        return x
    fixed = []
    for s in spec:
        if s is None:
            fixed.append(None)
        elif isinstance(s, str):
            fixed.append(s if s in m.axis_names else None)
        else:  # tuple of axis names
            kept = tuple(a for a in s if a in m.axis_names)
            fixed.append(kept if kept else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))
