"""Parallelism layer: meshes, collectives, sequence/pipeline parallelism.

First-class in the TPU rebuild (SURVEY §2.5/§5.7): DP/TP via shardings, SP
via ring attention / Ulysses, PP via the SPMD microbatch pipeline, plus both
functional (SPMD) and actor-group collectives.
"""

from ray_tpu.parallel.mesh import (
    MeshManager,
    P,
    mesh_manager,
    named_sharding,
    replicate,
    shard_array,
)
from ray_tpu.parallel import collective
from ray_tpu.parallel.collective import (
    allgather,
    allreduce,
    allreduce_mean,
    all_to_all,
    barrier,
    broadcast,
    init_collective_group,
    ppermute,
    reducescatter,
    send_recv,
)
from ray_tpu.parallel.distributed import (
    initialize as distributed_initialize,
    multihost_mesh,
    rendezvous_via_cluster,
)
from ray_tpu.parallel.pipeline import pipeline_apply, pipeline_sharded
from ray_tpu.parallel.ring import (
    ring_attention,
    ring_attention_sharded,
    ring_layout,
    ring_order,
    ulysses_attention,
    ulysses_attention_sharded,
)

__all__ = [
    "MeshManager", "P", "mesh_manager", "named_sharding", "replicate",
    "shard_array", "collective", "allgather", "allreduce", "allreduce_mean",
    "all_to_all", "barrier", "broadcast", "init_collective_group",
    "distributed_initialize", "multihost_mesh", "rendezvous_via_cluster",
    "ppermute", "reducescatter", "send_recv", "pipeline_apply",
    "pipeline_sharded", "ring_attention", "ring_attention_sharded", "ring_layout", "ring_order",
    "ulysses_attention", "ulysses_attention_sharded",
]
