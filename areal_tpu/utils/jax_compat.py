"""The one jax seam the engines still need on the supported jax (0.9.0).

``jax.set_mesh``, ``jax.shard_map``, ``jax.lax.axis_size`` and
``jax.sharding.get_abstract_mesh`` are used directly at their call sites.
What remains here is a sharding constraint that model code can issue
without knowing where it is traced:

- outside any mesh context (single-device tests, a bare ``jit``) the
  constraint is a no-op — ``jax.lax.with_sharding_constraint`` raises
  there when given a ``PartitionSpec``;
- inside a ``shard_map`` region the mapped axes are Manual, and a spec may
  only name Auto axes ("can only refer to Auto axes of the mesh"): manual
  axes are dropped from the spec — the array is already a local slice
  along them — and a spec with nothing left is a no-op.

Any other refusal (an axis the mesh does not have, a dimension the axis
does not divide) is an error and is raised. All model/engine code routes
constraints through this function, not ``jax.lax.with_sharding_constraint``
directly (arealint MSH003).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as _P


def with_sharding_constraint(x, spec):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x  # no ambient mesh
    manual = frozenset(mesh.manual_axes)
    if manual:
        def keep(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a not in manual)
                return kept if kept else None
            return None if entry in manual else entry
        spec = _P(*(keep(e) for e in spec))
        if all(e is None for e in spec):
            return x
    # arealint: disable-next=MSH003 this IS the shim every other raw call must route through
    return jax.lax.with_sharding_constraint(x, spec)
