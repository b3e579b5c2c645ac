"""Two workarounds the parallel package still needs on jax 0.9.

  * ``with_sharding_constraint`` with a bare ``PartitionSpec`` raises unless
    a mesh context is ambient; ``constraint_sharding`` binds the spec to a
    concrete ``NamedSharding`` so call sites work either way.
  * ``jnp.roll`` on sharded operands — the SPMD partitioner miscompiles a
    rolled array consumed by a gather (garbage values, NaN losses);
    ``spmd_roll`` lowers to a mod-iota gather that partitions correctly.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


def constraint_sharding(mesh, spec):
    """Bind a ``PartitionSpec`` to ``mesh`` for ``with_sharding_constraint``.

    jax refuses a bare spec unless a mesh context manager is active at
    the *trace* site; a ``NamedSharding`` works with or without one. Passes
    through unchanged when there is no mesh (or no spec) to bind."""
    if mesh is None or spec is None or not isinstance(spec, PartitionSpec):
        return spec
    return NamedSharding(mesh, spec)


def spmd_roll(x, shift: int, axis: int):
    """``jnp.roll`` that survives the SPMD partitioner.

    On jax 0.9 / XLA a ``jnp.roll`` whose output feeds a gather
    (``take_along_axis``) returns garbage when the operands are sharded —
    the partitioner mis-propagates the roll's halo exchange. An explicit
    mod-iota gather expresses the same permutation with a replicated index
    vector, which partitions correctly."""
    axis = axis % x.ndim
    n = x.shape[axis]
    idx = (jnp.arange(n) - shift) % n
    return jnp.take(x, idx, axis=axis)
