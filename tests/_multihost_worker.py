"""Worker for test_multihost.py — one simulated host in an N-process run.

Run as: python _multihost_worker.py <coordinator> <num_procs> <proc_id> \
            <out_dir> [mode]

Each process gets its virtual CPU devices (xla_force_host_platform_device_count,
set by the parent), initializes `jax.distributed` over the local coordinator
(the DCN-rendezvous path, parallel/mesh.py:28-36), builds a global mesh,
feeds its process-local shard of the global batch through ``shard_batch``
(make_array_from_process_local_data — the multi-host branch,
parallel/mesh.py:74-77), runs one train step, and writes the loss it saw to
``<out_dir>/loss_<proc_id>.txt`` for the parent to compare.

Modes:

* ``dp`` (default) — pure data-parallel over all devices, plus a grouped
  steps_per_dispatch=2 step and a collective orbax save (the 2-process
  matrix entry);
* ``dptpsp`` — the composed {data, model, seq} mesh: tensor-parallel params
  over 'model', ring attention over 'seq', grouped steps_per_dispatch
  dispatch — the layout the virtual-mesh dryrun compiles, here under REAL
  processes over DCN (VERDICT r4 item 7). Two processes share each data
  shard, so the worker derives its shard index from its addressable
  devices' mesh coordinates rather than from proc_id.
* ``spsample`` — sequence-parallel SAMPLING (the serving tentpole's
  (data, seq) mesh) with the 'seq' axis ACROSS the process boundary:
  {seq:2, data:4} over 2 processes × 4 devices, ulysses all-to-alls over
  DCN, k-step ddim scan, dense-local-reference parity asserted in-worker
  and a global-mean digest written for the parent's cross-process check.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def data_shard_bounds(mesh, batch_rows: int) -> tuple[int, int]:
    """[lo, hi) rows of the global batch held by THIS process, from the mesh
    coordinates of its addressable devices along 'data' (the general form of
    the 2-proc test's proc_id*rows slicing — correct even when several
    processes replicate one data shard across 'model'/'seq')."""
    axis = list(mesh.axis_names).index("data")
    coords = {
        int(np.argwhere(np.asarray(mesh.devices) == d)[0][axis])
        for d in mesh.local_devices
    }
    assert len(coords) == 1, (
        f"process spans data shards {sorted(coords)} — the P('data') batch "
        "contract needs each process inside one shard")
    n = int(mesh.shape["data"])
    rows = batch_rows // n
    lo = coords.pop() * rows
    return lo, lo + rows


def main():
    coordinator, num_procs, proc_id, out_dir = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else "dp"

    import jax

    # the workers run on the virtual-CPU platform whatever the ambient
    # JAX_PLATFORMS says, exactly like tests/conftest.py
    jax.config.update("jax_platforms", "cpu")

    from ddim_cold_tpu.parallel.mesh import (
        initialize_distributed, make_mesh, shard_batch,
    )

    initialize_distributed(coordinator, num_procs, proc_id)
    assert jax.process_count() == num_procs, jax.process_count()

    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step
    from ddim_cold_tpu.utils import checkpoint as ckpt

    if mode == "dptpsp":
        run_dptpsp(jax, jnp, out_dir, proc_id)
        jax.distributed.shutdown()
        return
    if mode == "pipemoe":
        run_pipemoe(jax, jnp, out_dir, proc_id)
        jax.distributed.shutdown()
        return
    if mode == "spsample":
        run_spsample(jax, jnp, out_dir, proc_id)
        jax.distributed.shutdown()
        return
    assert jax.local_device_count() == 4, jax.local_device_count()

    mesh = make_mesh({"data": jax.device_count()})

    model = DiffusionViT(img_size=(8, 8), patch_size=4, embed_dim=16,
                         depth=1, num_heads=2, total_steps=10)
    # deterministic per-process shard of a notional global batch of 16:
    # process r holds rows [r*8, r*8+8) — identical data either way the
    # global array is assembled, so the loss must agree across processes.
    rng = np.random.RandomState(0)
    gx = rng.randn(16, 8, 8, 3).astype(np.float32)
    gy = rng.randn(16, 8, 8, 3).astype(np.float32)
    gt = rng.randint(1, 4, size=(16,)).astype(np.int32)
    lo, hi = proc_id * 8, proc_id * 8 + 8
    local = (gx[lo:hi], gy[lo:hi], gt[lo:hi])

    batch = shard_batch(local, mesh)
    assert not batch[0].is_fully_addressable  # genuinely multi-host global

    state = create_train_state(model, jax.random.PRNGKey(0), lr=1e-3,
                               total_steps=10, sample_batch=local)
    train_step = make_train_step(model)
    state, loss, _ = train_step(state, batch, jax.random.PRNGKey(1),
                                jnp.float32(5.0))
    loss = float(loss)  # global-mean loss: identical on both processes

    # grouped (steps_per_dispatch) sharding across REAL processes: each host
    # contributes its (n, local_B, …) stack and the P(None, 'data') global
    # assembles — the multi-host form of the grouped-dispatch batch contract
    grouped_local = tuple(np.stack([a, a]) for a in local)
    gbatch = shard_batch(grouped_local, mesh, grouped=True)
    assert not gbatch[0].is_fully_addressable
    multi_step = make_train_step(model, steps_per_dispatch=2)
    state, gloss, _ = multi_step(state, gbatch, jax.random.PRNGKey(1),
                                 jnp.float32(5.0))
    assert np.isfinite(float(gloss)), gloss

    # collective orbax save: every process calls save (trainer.py:284-287)
    ckpt.save_checkpoint(os.path.join(out_dir, "ckpt"), state.params)

    with open(os.path.join(out_dir, f"loss_{proc_id}.txt"), "w") as f:
        f.write(repr(loss))
    jax.distributed.shutdown()


def run_dptpsp(jax, jnp, out_dir: str, proc_id: int):
    """The composed {data:2, model:2, seq:2} layout under REAL processes
    (VERDICT r4 item 7): 4 processes × 2 local devices = 8 global devices —
    tensor-parallel params over 'model' (param_partition_specs), ring
    attention over 'seq', and ONE grouped steps_per_dispatch=2 dispatch.
    Mirrors __graft_entry__.dryrun_multichip's dp×tp×sp recipe, swapping the
    virtual single-process mesh for a DCN-rendezvoused one."""
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.ops import degrade
    from ddim_cold_tpu.parallel import (
        make_mesh, param_partition_specs, shard_batch, shard_train_state,
    )
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    assert jax.local_device_count() == 2, jax.local_device_count()
    mesh = make_mesh({"data": 2, "model": 2, "seq": 2})

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=2, num_heads=4, total_steps=10,
                         seq_mesh=mesh, seq_axis="seq", batch_axis="data",
                         head_axis="model", attn_drop_rate=0.0)
    # deterministic global batch; THIS process's rows come from its
    # addressable devices' 'data' coordinate (two processes per shard here —
    # proc_id arithmetic from the dp worker would be wrong)
    rng = np.random.RandomState(0)
    B = 8
    gu = rng.randint(0, 256, size=(B, 16, 16, 3)).astype(np.uint8)
    gt = rng.randint(1, 5, size=(B,)).astype(np.int32)
    lo, hi = data_shard_bounds(mesh, B)
    local = (gu[lo:hi], gt[lo:hi])

    state = create_train_state(
        model, jax.random.PRNGKey(0), lr=1e-3, total_steps=10,
        sample_batch=(np.zeros((2, 16, 16, 3), np.float32),
                      np.zeros((2, 16, 16, 3), np.float32),
                      np.ones((2,), np.int32)))
    state = shard_train_state(state, mesh,
                              param_partition_specs(state.params))
    prepare = degrade.make_cold_prepare(size=16, max_step=4, chain=True,
                                        mesh=mesh)
    step = make_train_step(model, prepare=prepare)
    batch = shard_batch(local, mesh)
    assert not batch[0].is_fully_addressable
    state, loss, _ = step(state, batch, jax.random.PRNGKey(1),
                          jnp.float32(5.0))
    loss = float(loss)
    assert np.isfinite(loss), loss

    # grouped dispatch: 2 stacked optimizer steps, scan axis unsharded,
    # 'data' on the per-step batch dim — under real processes
    g_step = make_train_step(model, prepare=prepare, steps_per_dispatch=2)
    grouped = tuple(np.stack([a, a]) for a in local)
    gbatch = shard_batch(grouped, mesh, grouped=True)
    assert not gbatch[0].is_fully_addressable
    state, gloss, _ = g_step(state, gbatch, jax.random.PRNGKey(1),
                             jnp.float32(5.0))
    assert np.isfinite(float(gloss)), gloss

    with open(os.path.join(out_dir, f"loss_{proc_id}.txt"), "w") as f:
        f.write(repr(loss))


def run_spsample(jax, jnp, out_dir: str, proc_id: int):
    """Sequence-parallel k-step SAMPLING over DCN: mesh {seq:2, data:4} over
    2 processes × 4 local devices puts the 'seq' coordinate on the PROCESS
    index — every ulysses all-to-all crosses the process boundary — while
    the batch stays data-sharded among each host's four devices. The same
    (data, seq) geometry the serve engine warms, minus the engine (whose
    device_put/assemble path is host-local by design); the scan family and
    attention front are exactly the served code."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddim_cold_tpu.models import DiffusionViT, sp_clone
    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.parallel import make_mesh, shard_batch

    assert jax.local_device_count() == 4, jax.local_device_count()
    mesh = make_mesh({"seq": 2, "data": 4})
    # the claim under test is the all-to-all CROSSING DCN: this process must
    # own exactly one seq shard (and hence span every data shard). If device
    # enumeration ever stops being process-major, fail loud instead of
    # green-lighting an intra-process reshard.
    seq_ax = list(mesh.axis_names).index("seq")
    coords = {
        int(np.argwhere(np.asarray(mesh.devices) == d)[0][seq_ax])
        for d in mesh.local_devices
    }
    assert len(coords) == 1, (
        f"process spans seq shards {sorted(coords)} — the DCN-crossing "
        "all-to-all claim needs one seq shard per process")

    base = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                        depth=2, num_heads=4, total_steps=2000,
                        attn_drop_rate=0.0)
    sp = sp_clone(base, mesh, sp_mode="ulysses")
    assert sp.sp_mode == "ulysses", sp.sp_mode  # 4 heads % 2 — no fallback
    # params replicated as ONE global placement (every process runs the same
    # init under out_shardings — the multi-host analogue of shard_params)
    init = jax.jit(base.init, out_shardings=NamedSharding(mesh, P()))
    params = init(jax.random.PRNGKey(0),
                  np.zeros((2, 16, 16, 3), np.float32),
                  np.array([0, 1], np.int32))["params"]

    rng = np.random.RandomState(0)
    B = 8
    x0 = rng.randn(B, 16, 16, 3).astype(np.float32)  # same on both procs
    x_init = shard_batch(x0, mesh)  # every data shard is addressable here
    assert not x_init.is_fully_addressable
    out = sampling.ddim_sample(sp, params, jax.random.PRNGKey(1), k=500,
                               x_init=x_init, mesh=mesh)
    digest = float(jnp.mean(out))  # replicated scalar — a true global mean

    # dense local reference: replicated params are fully-replicated global
    # arrays, so each process can pull a host copy and run the plain model
    # on its own device 0 — reduction reordering is the only difference
    params_host = jax.tree.map(np.asarray, params)
    ref = sampling.ddim_sample(base, params_host, jax.random.PRNGKey(1),
                               k=500, x_init=x0)
    ref_digest = float(jnp.mean(ref))
    assert abs(digest - ref_digest) < 5e-4, (digest, ref_digest)

    with open(os.path.join(out_dir, f"loss_{proc_id}.txt"), "w") as f:
        f.write(repr(digest))


def run_pipemoe(jax, jnp, out_dir: str, proc_id: int):
    """GPipe ACROSS PROCESSES + the pipe×MoE aux path (round 5): mesh
    {pipe: 2, data: 2} over 2 processes × 2 local devices puts stage 0 on
    process 0 and stage 1 on process 1, so every schedule ppermute and the
    aux psum cross the DCN boundary; the Switch aux loss rides the
    pipelined apply's mutable=["losses"] path into the step objective."""
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.parallel import (
        make_mesh, make_pipelined_apply, pipeline_param_specs,
        shard_batch, shard_train_state,
    )
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    assert jax.local_device_count() == 2, jax.local_device_count()
    mesh = make_mesh({"pipe": 2, "data": 2})
    # the claim under test is GPipe ppermute CROSSING the process boundary:
    # this process must own exactly one pipe stage (and hence span both data
    # shards). If device enumeration ever stops being process-major, fail
    # loud here instead of green-lighting a vacuous single-process pipeline.
    pipe_ax = list(mesh.axis_names).index("pipe")
    stages = {
        int(np.argwhere(np.asarray(mesh.devices) == d)[0][pipe_ax])
        for d in mesh.local_devices
    }
    assert len(stages) == 1, (
        f"process spans pipe stages {sorted(stages)} — the DCN-crossing "
        "ppermute claim needs one stage per process")

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=2, num_heads=4, total_steps=10,
                         scan_blocks=True, num_experts=2)
    rng = np.random.RandomState(0)
    B = 8
    gx = rng.randn(B, 16, 16, 3).astype(np.float32)
    gy = rng.randn(B, 16, 16, 3).astype(np.float32)
    gt = rng.randint(1, 5, size=(B,)).astype(np.int32)
    # the pipe axis crosses processes here, so EACH process addresses a
    # device in every data shard — its process-local slab is the full
    # batch (data_shard_bounds' one-shard contract applies to dp-style
    # layouts where a process sits inside a single shard)
    local = (gx, gy, gt)

    state = create_train_state(
        model, jax.random.PRNGKey(0), lr=1e-3, total_steps=10,
        sample_batch=(np.zeros((2, 16, 16, 3), np.float32),
                      np.zeros((2, 16, 16, 3), np.float32),
                      np.ones((2,), np.int32)))
    state = shard_train_state(state, mesh, pipeline_param_specs(state.params))
    step = make_train_step(
        model, moe_aux_weight=0.01,
        apply_fn=make_pipelined_apply(model, mesh, n_microbatch=2))
    batch = shard_batch(local, mesh)
    assert not batch[0].is_fully_addressable
    state, loss, _ = step(state, batch, jax.random.PRNGKey(1),
                          jnp.float32(5.0))
    loss = float(loss)
    assert np.isfinite(loss), loss

    with open(os.path.join(out_dir, f"loss_{proc_id}.txt"), "w") as f:
        f.write(repr(loss))


if __name__ == "__main__":
    main()
