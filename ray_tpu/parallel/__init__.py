"""Parallelism layer: device meshes, sharding rules, distributed init.

The TPU-native replacement for the reference's process-group/NCCL plumbing
(reference: python/ray/train/torch/config.py:66,116 _setup_torch_process_group;
torch-xla precedent train/torch/xla/config.py:20).  Here parallelism is
declarative: pick a mesh, annotate shardings, let XLA insert collectives over
ICI (GSPMD), following the mesh/axis conventions of the scaling playbook:

- ``dp``   data parallelism (pure replication of params, sharded batch)
- ``fsdp`` fully-sharded data parallelism (params sharded over this axis too)
- ``tp``   tensor parallelism (weight matrices split; activations all-gathered/
           reduce-scattered by XLA)
- ``sp``   sequence/context parallelism (long-context: ring attention over this
           axis — absent from the reference entirely, SURVEY §5.7)
- ``ep``   expert parallelism (MoE all-to-all)
"""

from ray_tpu.parallel.mesh import (
    MeshConfig,
    build_mesh,
    local_mesh,
    mesh_shape_for,
)
from ray_tpu.parallel.sharding import (
    PartitionRules,
    constrain_residual,
    gpt_partition_rules,
    match_partition_rules,
    shard_pytree,
)

__all__ = [
    "MeshConfig", "build_mesh", "local_mesh", "mesh_shape_for",
    "PartitionRules", "constrain_residual", "gpt_partition_rules",
    "match_partition_rules", "shard_pytree",
]
