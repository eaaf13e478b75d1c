"""TPU device plane: meshes, topology, sharding rules, collectives.

This layer is what makes the framework TPU-native: instead of the
reference's NCCL process groups (python/ray/util/collective/), tensor
communication is expressed as shardings over a `jax.sharding.Mesh` and
XLA inserts ICI/DCN collectives.  The reference's three comm planes
(SURVEY §5.8) map as: control plane → ray_tpu RPC, object plane →
shared-memory object store, tensor plane → THIS package.
"""

from ray_tpu.parallel.topology import (
    TpuGeneration,
    SliceTopology,
    parse_accelerator_type,
    ici_domains,
)
from ray_tpu.parallel.mesh import (
    MeshSpec,
    make_mesh,
    make_hybrid_mesh,
    active_mesh,
    fake_mesh,
    local_mesh,
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_TENSOR,
    AXIS_SEQ,
    AXIS_EXPERT,
    AXIS_PIPELINE,
)
from ray_tpu.parallel.sharding import (
    LogicalAxisRules,
    logical_to_mesh_axes,
    mesh_axes_for_shape,
    shard_by_shape,
    shardings_by_shape,
    shard_params,
    with_logical_constraint,
    DEFAULT_RULES,
    DECODE_RULES,
)
from ray_tpu.parallel import collective

__all__ = [
    "TpuGeneration", "SliceTopology", "parse_accelerator_type",
    "ici_domains", "MeshSpec", "make_mesh", "make_hybrid_mesh",
    "active_mesh", "fake_mesh", "local_mesh",
    "LogicalAxisRules", "logical_to_mesh_axes",
    "mesh_axes_for_shape", "shard_by_shape", "shardings_by_shape",
    "shard_params", "with_logical_constraint", "DEFAULT_RULES",
    "DECODE_RULES", "collective",
    "AXIS_DATA", "AXIS_FSDP", "AXIS_TENSOR", "AXIS_SEQ", "AXIS_EXPERT",
    "AXIS_PIPELINE",
]
