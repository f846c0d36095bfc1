from ddim_cold_tpu.parallel.mesh import (
    ambient,
    batch_sharding,
    make_mesh,
    replicated,
    shard_batch,
    shard_params,
    shard_train_state,
)
from ddim_cold_tpu.parallel.pipeline import make_pipelined_apply, pipeline_blocks
from ddim_cold_tpu.parallel.sharding import param_partition_specs, pipeline_param_specs
from ddim_cold_tpu.parallel.ulysses import SeqParallelConfigError

__all__ = [
    "SeqParallelConfigError",
    "make_mesh",
    "ambient",
    "batch_sharding",
    "replicated",
    "shard_batch",
    "shard_params",
    "shard_train_state",
    "param_partition_specs",
    "pipeline_param_specs",
    "make_pipelined_apply",
    "pipeline_blocks",
]
