"""Pipeline parallelism: GPipe-style microbatch schedule inside one SPMD
program.

The reference leaves PP to compiled DAGs + user frameworks (SURVEY §2.5
"expressible via compiled DAGs", ``python/ray/dag/compiled_dag_node.py:278``);
here it is a mesh strategy: stage parameters shard over the ``pp`` axis, and
activations ride ``ppermute`` hops to the next stage — the compiled-DAG
"channel" becomes an ICI neighbor copy emitted by XLA.

Schedule: M microbatches over S stages take M + S - 1 ticks; device s idles
for s warm-up ticks (the standard GPipe bubble).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.ring import _to_varying


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches,
    axis_name: str = "pp",
):
    """Run inside shard_map. Each device holds one stage's params.

    stage_fn(params, x) -> y, with y.shape == x.shape (inter-stage width
    must match for the ring transport).
    stage_params: this device's stage parameters (pytree).
    microbatches: [M, ...] microbatch inputs (replicated across stages).
    Returns [M, ...] outputs (replicated — produced on the last stage and
    psum-broadcast).
    """
    n = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = microbatches.shape[0]
    x_shape = microbatches.shape[1:]

    right_perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        recv_buf, outputs = carry
        inject = lax.dynamic_index_in_dim(microbatches, jnp.minimum(t, M - 1), axis=0, keepdims=False)
        x = jnp.where(stage == 0, inject, recv_buf)
        y = stage_fn(stage_params, x)
        # last stage writes its result for microbatch (t - (n-1))
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        valid = jnp.logical_and(stage == n - 1, t >= n - 1)
        outputs = lax.cond(
            valid,
            lambda o: lax.dynamic_update_index_in_dim(o, y, out_idx, axis=0),
            lambda o: o,
            outputs,
        )
        recv_next = lax.ppermute(y, axis_name, right_perm)
        return (recv_next, outputs), None

    zeros = jnp.zeros(x_shape, microbatches.dtype)
    outputs0 = jnp.zeros((M,) + x_shape, microbatches.dtype)
    recv0, outputs0 = (_to_varying(x, axis_name) for x in (zeros, outputs0))
    (_, outputs), _ = lax.scan(tick, (recv0, outputs0), jnp.arange(M + n - 1))
    # only the last stage holds real outputs; broadcast to all stages
    outputs = jnp.where(stage == n - 1, outputs, 0.0)
    return lax.psum(outputs, axis_name)


def pipeline_sharded(
    stage_fn: Callable,
    stacked_params,
    microbatches,
    mesh: Mesh,
    axis_name: str = "pp",
):
    """Bind a pipeline onto a mesh.

    stacked_params: pytree whose leaves have a leading stage dimension of
    size mesh.shape[axis_name]; leaf i goes to stage i.
    microbatches: [M, ...] replicated input microbatches.
    """
    def inner(params_local, mb):
        # shard_map passes the stage's [1, ...] slice; drop the leading dim
        params = jax.tree.map(lambda p: p[0], params_local)
        return pipeline_apply(stage_fn, params, mb, axis_name)

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    return shard_map(
        inner, mesh=mesh, in_specs=(param_specs, P()), out_specs=P()
    )(stacked_params, microbatches)
