"""Mesh-axis → parameter-layout/apply-fn selection for the trainer.

* a ``pipe`` axis: stacked-blocks params sharded stage-per-device +
  the GPipe pipelined apply_fn (parallel/pipeline.py);
* a ``model`` axis: Megatron column/row partition specs (parallel/sharding.py);
* otherwise: replicated params (gradient psum implicit in jit) — plain dp.
"""

from __future__ import annotations

from typing import Callable, Optional

from jax.sharding import Mesh


def layout_for_mesh(model, mesh: Mesh, params, *,
                    n_microbatch: int = 2) -> tuple[Optional[dict], Optional[Callable]]:
    """→ (partition_specs_or_None, apply_fn_or_None) for ``shard_train_state``
    and ``make_train_step``."""
    from ddim_cold_tpu.parallel.pipeline import make_pipelined_apply
    from ddim_cold_tpu.parallel.sharding import (
        param_partition_specs, pipeline_param_specs,
    )

    if int(mesh.shape.get("pipe", 1)) > 1:
        # 'expert' rides along like 'model': both stay GSPMD-auto inside the
        # pipeline's manual region, so MoE expert banks Megatron-shard the
        # same way tp kernels do (pipe×ep, the last composition gap)
        tensor_axes = tuple(a for a in ("model", "expert")
                            if int(mesh.shape.get(a, 1)) > 1)
        return (pipeline_param_specs(params, tensor_axes=tensor_axes),
                make_pipelined_apply(model, mesh, n_microbatch=n_microbatch))
    shard_axes = tuple(a for a in ("model", "expert")
                       if int(mesh.shape.get(a, 1)) > 1)
    if shard_axes:
        return param_partition_specs(params, axes=shard_axes), None
    return None, None
