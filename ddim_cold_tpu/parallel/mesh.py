"""Mesh/communication layer — replaces the reference's NCCL/DDP stack (C17).

The reference runs one OS process per GPU, rendezvouses over TCP
(multi_gpu_trainer.py:25-30), wraps the model in DDP for ring-allreduce of
gradients, and shards data with DistributedSampler. Under JAX SPMD all of that
collapses: one process per *host*, a ``jax.sharding.Mesh`` over the chips,
sharding annotations on params/batch, and XLA emits the collectives (psum for
gradients over ICI, all-gather where layouts require) fused into the step.

Mesh axes:
* ``data``  — batch (data parallelism; gradient psum is implicit in jit)
* ``model`` — attention heads / MLP hidden (Megatron-style tensor parallelism)

Multi-host: call ``initialize_distributed()`` once per host before device
queries; each host then feeds its data shard (data/loader.py shard_index =
``jax.process_index()``).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddim_cold_tpu.obs import spans


def initialize_distributed(coordinator: Optional[str] = None, num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host process coordination over DCN (replaces the TCP rendezvous at
    multi_gpu_trainer.py:25-30). No-op for single-host runs."""
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=num_processes, process_id=process_id
    )


def make_mesh(shape: Optional[dict[str, int]] = None, devices=None) -> Mesh:
    """Build a Mesh. Default: every visible device on the 'data' axis with a
    trivial 'model' axis, so dp-only configs and tp-aware code share one layout.

    ``shape`` e.g. ``{"data": 4, "model": 2}`` must multiply to the device
    count (axis order = dict order, data-major outermost so model groups are
    ICI-adjacent).
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    if shape is None:
        shape = {"data": devices.size, "model": 1}
    sizes = tuple(shape.values())
    if int(np.prod(sizes)) != devices.size:
        raise ValueError(f"mesh shape {shape} does not match {devices.size} devices")
    return Mesh(devices.reshape(sizes), tuple(shape.keys()))


def ambient(mesh: Optional[Mesh]):
    """``mesh`` as JAX's ambient mesh (``jax.set_mesh``) for the ``with``
    block; no-op for ``None``. Whoever traces a program over a mesh enters
    this around it: the Pallas kernel call sites read the ambient mesh to
    launch one kernel per device (ops/flash_attention.per_device) — jit
    cannot partition a Mosaic kernel itself and refuses to lower one over
    several devices."""
    return jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_axis_size(mesh: Optional[Mesh]) -> int:
    """Number of shards a batch's leading dim splits into on this mesh — 1
    for no mesh or a mesh without a 'data' axis (batch replicated). The serve
    engine validates its bucket sizes against this: a bucket that does not
    divide the data axis cannot be placed without a gather."""
    if mesh is None or "data" not in mesh.shape:
        return 1
    return int(mesh.shape["data"])


def batch_sharding(mesh: Mesh, grouped: bool = False) -> NamedSharding:
    """Batch arrays shard their leading dim over 'data' (DistributedSampler's
    role, now expressed as a sharding annotation). Meshes without a 'data'
    axis (e.g. pure sequence-parallel ``{seq: N}``) replicate the batch.

    ``grouped``: the batch carries a leading steps-per-dispatch axis (see
    train.step ``steps_per_dispatch``) — the scan axis stays unsharded and
    'data' moves to the per-step batch dim behind it."""
    if "data" not in mesh.shape:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(None, "data") if grouped else P("data"))


def shard_batch(batch, mesh: Mesh, grouped: bool = False):
    """Place a host-local batch as a global array sharded on 'data'.

    Multi-host: each process contributes its shard of the global batch
    (``make_array_from_process_local_data`` — the SPMD replacement for
    DistributedSampler rank interleaving)."""
    s = batch_sharding(mesh, grouped=grouped)
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(s, np.asarray(x)), batch
        )
    return jax.tree.map(lambda x: jax.device_put(x, s), batch)


def shard_params(params, mesh: Mesh, specs=None):
    """Place params on the mesh: replicated by default, or per-leaf
    PartitionSpecs (parallel/sharding.py) for tensor parallelism."""
    if specs is None:
        return jax.device_put(params, replicated(mesh))
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)), params, specs
    )


def shard_train_state(state, mesh: Mesh, specs=None):
    """Place a TrainState on the mesh: params per ``specs`` (or replicated),
    optimizer moments co-sharded with their params.

    The optimizer-state layout is derived by re-running ``tx.init`` on the
    *already-sharded* params — optax moments are ``zeros_like(params)`` so they
    inherit the param shardings — and restored/initial values are then placed
    leaf-by-leaf onto that layout. Keeps Adam's mu/nu from silently living
    replicated next to tensor-sharded params (2× HBM + a reshard per step).

    Recorded as one ``parallel/place_state`` layer span (``bytes``, ``leaves``
    of everything placed, ``devices`` of the mesh). The span is the host's
    side of the placement: ``device_put`` returns before a transfer is done,
    and the one wait inside it is the host copy of each moment
    (``np.asarray``) that the placement always made.
    """
    with spans.layer("parallel/place_state", devices=mesh.devices.size) as span:
        params = shard_params(state.params, mesh, specs)
        layout = state.tx.init(params)
        mesh_devices = set(mesh.devices.flat)

        def place(value, ref):
            sharding = ref.sharding
            if getattr(sharding, "device_set", None) != mesh_devices:
                sharding = replicated(mesh)  # scalars (e.g. adam count) from init
            return jax.device_put(np.asarray(value), sharding)

        opt_state = jax.tree.map(place, state.opt_state, layout)
        extra = {}
        if getattr(state, "ema_params", None) is not None:
            # the EMA shadow mirrors the params' tree and must mirror their
            # sharding too (elementwise update: no resharding in the step)
            extra["ema_params"] = shard_params(state.ema_params, mesh, specs)
        placed = jax.tree.leaves((params, opt_state, extra))
        span.set(bytes=sum(x.nbytes for x in placed), leaves=len(placed))
    return state.replace(params=params, opt_state=opt_state, **extra)
