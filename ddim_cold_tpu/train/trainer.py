"""The training loop — replaces ``multi_gpu_trainer.main`` (SURVEY.md §3.1).

The reference spawns one process per GPU, rendezvouses over NCCL, and runs a
per-rank loop with DDP allreduce inside backward. Here one process per host
drives a pjit'd step over the mesh; the call stack collapses to:

    run(config)
    ├─ make_mesh / shard params+batch          (parallel/mesh.py — was NCCL init)
    ├─ ShardedLoader per host                  (data/loader.py — was DataLoader×8 workers)
    ├─ create_train_state                      (train/step.py — was model+DDP+AdamW+scaler)
    ├─ optional warm-start / resume            (utils/checkpoint.py)
    └─ epoch loop: train_step scan → evaluate → log → checkpoint

Behavioral parity preserved: EMA(0.99) train loss starting at 5.0, every-100-
step log line, per-epoch val line, best/last dual checkpoints, epoch-granular
resume restoring scheduler position (the step count), best metric and EMA
loss (multi_gpu_trainer.py:53-55,94-106,126,135-163).
"""

from __future__ import annotations

import os
import threading
import time
import traceback
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.config import ExperimentConfig
from ddim_cold_tpu.data import ColdDownSampleDataset, DiffusionDataset, ShardedLoader
from ddim_cold_tpu.data.loader import device_prefetch, group_batches
from ddim_cold_tpu.ops import degrade
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.obs import scopes
from ddim_cold_tpu.parallel import ambient, make_mesh, shard_batch, shard_train_state
from ddim_cold_tpu.parallel.layout import layout_for_mesh
from ddim_cold_tpu.train.step import create_train_state, make_eval_step, make_train_step
from ddim_cold_tpu.utils import checkpoint as ckpt
from ddim_cold_tpu.utils import profiling
from ddim_cold_tpu.utils.logging import ScalarWriter, asctime, print_log


@dataclass
class TrainResult:
    best_loss: float
    last_val_loss: float
    steps: int
    run_dir: str


class _GracefulStop:
    """SIGTERM/SIGINT → set a flag; the epoch loop finishes the current step,
    evaluates, checkpoints, and returns normally.

    A hard-killed training process loses the epoch in flight; exiting through
    the normal path releases the device cleanly and leaves a resumable
    lastepoch.ckpt.
    A SECOND signal restores the previous dispositions and re-delivers
    itself — truly urgent kill, not a second graceful pass. Handlers are only
    installable from the main thread — elsewhere this is a no-op
    (``requested`` stays False).

    Multi-host: the local flag must NOT gate collective control flow directly
    (only the signaled host would leave the loop — mismatched collectives
    deadlock the slice); callers consult :meth:`agreed` at loop points every
    host reaches at the same step.
    """

    def __init__(self):
        self.requested = False
        self._prev: dict = {}

    def agreed(self) -> bool:
        """Cross-host consensus on the stop flag: True when ANY process was
        signaled. Every process must call this at the same loop point."""
        if jax.process_count() == 1:
            return self.requested
        from jax.experimental import multihost_utils

        return bool(
            multihost_utils.process_allgather(np.asarray([self.requested])).any())

    def __enter__(self):
        import signal

        def handler(signum, frame):
            if self.requested:  # second signal: restore + re-deliver → die now
                for s, h in self._prev.items():
                    signal.signal(s, h)
                os.kill(os.getpid(), signum)
                return
            self.requested = True

        try:
            for s in (signal.SIGTERM, signal.SIGINT):
                self._prev[s] = signal.signal(s, handler)
        except ValueError:  # not the main thread
            self._prev = {}
        return self

    def __exit__(self, *exc):
        import signal

        for s, h in self._prev.items():
            signal.signal(s, h)
        return False


class _AsyncSaver:
    """Runs each epoch's checkpoint writes in a background thread so the
    device→host pull + serialization overlap the next epoch's compute. At most
    one epoch's saves are in flight (``wait`` before the next ``submit``); save
    errors re-raise at the next wait point. Multi-host runs stay synchronous —
    orbax saves are collective and host-side thread scheduling must not
    reorder them against other collectives.
    """

    def __init__(self, sync: bool):
        self.sync = sync
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def submit(self, fn) -> None:
        if self.sync:
            fn()
            return

        def run():
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — re-raised on the main thread at wait()
                self._error = e

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            e, self._error = self._error, None
            raise e


def _fully_addressable(tree) -> bool:
    """True when every array shard lives on this host (single-host runs) —
    the precondition for materializing params into a torch-style pickle."""
    return all(
        getattr(x, "is_fully_addressable", True) for x in jax.tree.leaves(tree)
    )


def _check_loaded_params(loaded, expected, src_path: str) -> None:
    """Fail LOUDLY on a config-mismatched warm-start/resume source (e.g. a
    stale pkl/ckpt from a different-sized run under the same name): orbax
    returns the ON-DISK shapes when they differ from a numpy template
    (measured), and silently replacing the tree would surface only as an
    opaque jit shape error — fatal for unattended evidence runs."""
    if jax.tree.structure(loaded) != jax.tree.structure(expected):
        raise ValueError(
            f"initializing file {src_path} does not match this model config "
            "(different param tree — wrong depth, positional-embedding mode, "
            "or bias layout)")
    paths = jax.tree_util.tree_flatten_with_path(expected)[0]
    mism = [
        f"{jax.tree_util.keystr(p)}: file {np.shape(a)} vs model {np.shape(b)}"
        for (p, b), a in zip(paths, jax.tree.leaves(loaded))
        if np.shape(a) != np.shape(b)]
    if mism:
        raise ValueError(
            f"initializing file {src_path} does not match this model config "
            f"— {'; '.join(mism[:4])}"
            + (f"; +{len(mism) - 4} more" if len(mism) > 4 else ""))


def _build_dataset(config: ExperimentConfig, root: str):
    cache = config.cache_images
    if config.dataset == "cold":
        return ColdDownSampleDataset(root, imgSize=config.image_size,
                                     target_mode="chain", cache_images=cache)
    if config.dataset == "cold_direct":
        return ColdDownSampleDataset(root, imgSize=config.image_size,
                                     target_mode="direct", cache_images=cache)
    if config.dataset == "gaussian":
        return DiffusionDataset(root, imgSize=config.image_size,
                                max_step=config.total_steps, cache_images=cache)
    raise ValueError(f"unknown dataset kind {config.dataset!r}")


def _build_hybrid(config: ExperimentConfig, mesh):
    """``trunk:`` in the yaml: ``HybridDenoiser`` over the layer stack its
    ``model_type`` names (jamba, laguna) at the yaml's image, patch and step
    sizes. What reaches into ``Block`` is refused by
    name, a mesh axis that would split tokens or layers included."""
    from ddim_cold_tpu.models import hybrid

    hybrid.refuse_any({
        "use_flash": config.use_flash, "flash_blocks": config.flash_blocks,
        "scan_blocks": config.scan_blocks, "num_experts": config.num_experts,
        "moe_dispatch": config.moe_dispatch})
    mesh_shape = getattr(mesh, "shape", {}) if mesh is not None else {}
    for axis, option in (("seq", "sp_mode"), ("pipe", "scan_blocks"),
                         ("expert", "num_experts")):
        if int(mesh_shape.get(axis, 1)) > 1:
            raise hybrid.refuse(option)
    return hybrid.HybridDenoiser(
        trunk=dict(config.trunk), img_size=tuple(config.image_size),
        patch_size=config.patch_size, total_steps=config.total_steps,
        dtype=jnp.bfloat16 if config.amp else jnp.float32)


def build_model(config: ExperimentConfig, mesh=None):
    """Model from config. With a mesh carrying a ``seq`` axis, attention runs
    as ring attention sharded over it (sequence parallelism); attention-
    dropout is zeroed then — the ring path never materializes the weights, and
    silently training dense while configured for sp would be worse. The same
    holds for ``use_flash``: the kernel has no weights to drop, so a flash
    config trains without attention-dropout. A ``pipe`` axis forces the
    stacked scan_blocks layout (the pipeline's substrate)."""
    if config.trunk is not None:
        return _build_hybrid(config, mesh)
    kwargs = dict(config.model_kwargs())
    if config.use_flash:
        kwargs["attn_drop_rate"] = 0.0
    mesh_shape = getattr(mesh, "shape", {}) if mesh is not None else {}
    if "pipe" in mesh_shape:
        # composition is mesh-driven inside the pipeline executor
        # (make_pipelined_apply): the model stays plain — seq/model fields
        # would nest a shard_map inside the pipeline's manual region.
        # sp_mode is the one field that travels: it picks the manual kernel
        # (ring rotation or ulysses all-to-all) the stage attention runs.
        kwargs["scan_blocks"] = True
        if "seq" in mesh_shape:
            kwargs["attn_drop_rate"] = 0.0  # manual sp: same dropout rule
            kwargs["sp_mode"] = config.sp_mode
    if "seq" in mesh_shape and "pipe" not in mesh_shape:
        # pure-sp meshes ({seq: N}, no data axis) replicate the batch; with a
        # tp axis the ring keeps heads sharded over it (no qkv all-gather)
        batch_axis = "data" if "data" in mesh_shape else None
        head_axis = "model" if int(mesh_shape.get("model", 1)) > 1 else None
        kwargs.update(seq_mesh=mesh, seq_axis="seq", batch_axis=batch_axis,
                      head_axis=head_axis, attn_drop_rate=0.0,
                      sp_mode=config.sp_mode)
    return DiffusionViT(
        dtype=jnp.bfloat16 if config.amp else jnp.float32, **kwargs
    )


def run(config: ExperimentConfig, base_dir: str, *, max_steps: Optional[int] = None,
        log_every: int = 100) -> TrainResult:
    """Train per the config; returns the best/final metrics. ``max_steps``
    bounds total optimizer steps (test/bench hook, not in the reference)."""
    from ddim_cold_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # repeat compiles (resume, re-run, bench) become
    # disk reads instead of re-paying the cold-start compile on short runs
    saved_dir = os.path.join(base_dir, "Saved_Models")
    run_dir = os.path.join(saved_dir, config.run_name)
    os.makedirs(run_dir, exist_ok=True)
    log = os.path.join(run_dir, "train.log")

    # -- mesh over the requested device count ------------------------------
    avail = jax.devices()
    if config.mesh:
        # explicit mesh: the global batch and lr both derive from mesh['data']
        # (config.data_parallel_size), so clamping num_devices would change
        # nothing but the lr — a too-small host is a hard error instead.
        mesh_shape = dict(config.mesh)
        need = int(np.prod(list(mesh_shape.values())))
        if need > len(avail):
            raise ValueError(
                f"config.mesh {mesh_shape} needs {need} devices, "
                f"only {len(avail)} visible")
    else:
        # same rule as the explicit mesh: training on fewer devices than the
        # config asks for is a different run (global batch and lr both derive
        # from num_devices), not a degraded one
        if config.num_devices > len(avail):
            raise ValueError(
                f"num_gpus {config.num_devices} needs {config.num_devices} "
                f"devices, only {len(avail)} visible")
        mesh_shape = {"data": config.num_devices}
    mesh = make_mesh(mesh_shape, devices=avail[: int(np.prod(list(mesh_shape.values())))])
    with ambient(mesh):  # init, train and eval steps all trace under it
        return _train(config, mesh, saved_dir, run_dir, log, max_steps,
                      log_every)


def _train(config: ExperimentConfig, mesh, saved_dir: str, run_dir: str,
           log: str, max_steps: Optional[int], log_every: int) -> TrainResult:
    """:func:`run` from the built mesh on."""
    exp_size = int(mesh.shape.get("expert", 1))
    if exp_size > 1 and (config.num_experts <= 1
                         or config.num_experts % exp_size):
        raise ValueError(
            f"mesh 'expert' axis of {exp_size} needs num_experts (got "
            f"{config.num_experts}) set and divisible by it")

    # -- data --------------------------------------------------------------
    # per-device batch × devices = the global batch fed each step; sharding on
    # the 'data' axis routes each device its slice (replaces DistributedSampler
    # rank interleaving + per-rank DataLoader).
    # build the model first: it validates mesh-axis composition (pipe vs
    # model/seq) before any batch-arithmetic error can mask that message
    model = build_model(config, mesh=mesh)
    data_mesh_size = int(mesh.shape.get("data", 1))
    global_batch = config.effective_batch * data_mesh_size
    pipe_stages = int(mesh.shape.get("pipe", 1))
    n_micro = (config.microbatches or 2 * pipe_stages) if pipe_stages > 1 else 1
    if pipe_stages > 1 and (
        global_batch % n_micro or (global_batch // n_micro) % data_mesh_size
    ):
        raise ValueError(
            f"pipeline needs global batch {global_batch} divisible by "
            f"microbatches {n_micro} and each microbatch by data={data_mesh_size}")
    if config.grad_accum > 1:
        if pipe_stages > 1:
            raise ValueError(
                "grad_accum composes with dp/tp/sp only — the pipe axis has "
                "its own microbatching (config.microbatches)")
        if (global_batch % config.grad_accum
                or (global_batch // config.grad_accum) % data_mesh_size):
            raise ValueError(
                f"grad_accum needs global batch {global_batch} divisible by "
                f"{config.grad_accum} and each slice by data={data_mesh_size}")
    shard_index, shard_count = jax.process_index(), jax.process_count()
    train_set = _build_dataset(config, config.data_storage[0])
    test_set = _build_dataset(config, config.data_storage[1])
    # device-side corruption: datasets ship clean bases and the jitted step
    # rebuilds the corrupted batch on device — for cold, bit-identical gathers
    # (both loaders); for gaussian, device-drawn ε (train loader only: the val
    # loss stays on the deterministic host path). 2-8× less host→device
    # traffic.
    is_cold = config.dataset in ("cold", "cold_direct")
    raw_train = config.device_degrade and config.dataset in (
        "cold", "cold_direct", "gaussian")
    raw_eval = config.device_degrade and is_cold
    prepare = eval_prepare = None
    if raw_train:
        if is_cold:
            prepare = degrade.make_cold_prepare(
                size=int(config.image_size[0]), max_step=train_set.max_step,
                chain=(config.dataset == "cold"), mesh=mesh)
            eval_prepare = prepare
        else:
            prepare = degrade.make_gaussian_prepare(config.total_steps,
                                                    mesh=mesh)
    train_loader = ShardedLoader(
        train_set, global_batch // shard_count, shuffle=True, seed=config.seed,
        drop_last=True, shard_index=shard_index, shard_count=shard_count,
        raw=raw_train,
    )
    test_loader = ShardedLoader(
        test_set, global_batch // shard_count, shuffle=False, drop_last=False,
        shard_index=shard_index, shard_count=shard_count,
        pad_final_batch=True,  # sharded leading dim needs even divisibility
        raw=raw_eval,
    )
    train_batches, test_batches = len(train_loader), len(test_loader)
    if train_batches == 0:
        raise ValueError("dataset smaller than one global batch (drop_last)")

    # -- model state -------------------------------------------------------
    rng = jax.random.PRNGKey(config.seed)
    # init traces the real step (incl. any ring-attention shard_map), so the
    # sample's leading dim must divide over the data axis like a real batch
    sample_n = 2 * data_mesh_size
    sample = next(iter(ShardedLoader(train_set, sample_n, shuffle=False,
                                     drop_last=False, pad_final_batch=True,
                                     num_threads=1)))
    sample = shard_batch(sample, mesh)
    # no ema_decay here: the EMA shadow is seeded AFTER warm-start/resume
    # resolve the actual starting params (below) — a create-time seed would
    # be a dead full-tree copy on every warm-started run.
    # Cosine-schedule length = the steps that will actually run: grouped
    # dispatch drops epoch tails shorter than steps_per_dispatch, and a
    # schedule sized for the ungrouped count would end the run mid-cosine
    # (LR never reaching its configured floor).
    steps_per_epoch = (train_batches // config.steps_per_dispatch
                       ) * config.steps_per_dispatch
    if steps_per_epoch == 0:
        raise ValueError(
            f"steps_per_dispatch {config.steps_per_dispatch} exceeds the "
            f"{train_batches} batches in an epoch — every epoch would drop")
    state = create_train_state(
        model, rng, config.lr, steps_per_epoch * config.epoch[1], sample
    )

    # warm start (the reference's `initializing` key, C18): load if present,
    # else persist this init for future runs. No broadcast needed under SPMD.
    epoch_start = config.epoch[0]
    steps, loss_rec, best_loss = 0, 5.0, 5.0
    if config.initializing not in ("", "none"):
        init_path = os.path.join(saved_dir, config.initializing)
        ckpt.recover_swap(init_path)  # owner-side heal of a crashed save swap
        loaded = None
        if os.path.isfile(init_path):
            loaded = ckpt.load_torch_pkl(init_path, config.patch_size)
        elif os.path.isdir(init_path):
            # orbax restore with a template returns the ON-DISK shapes when
            # they differ (measured) — validated below like the pkl branch
            loaded = ckpt.restore_checkpoint(init_path, state.params)
        elif jax.process_index() == 0:
            # best-effort convenience cache (same seed reproduces the init
            # regardless): torch-less hosts still write the pkl via the
            # native writer; anything the pkl bridge refuses (e.g. MoE
            # params have no reference torch layout) falls back to orbax —
            # the isdir branch above loads that form on the next run
            try:
                ckpt.save_torch_pkl(state.params, init_path, config.patch_size)
            except Exception as e:  # noqa: BLE001
                print_log(f"init pkl export unavailable ({e}); "
                          "persisting orbax instead", log)
                if os.path.isfile(init_path):  # partial file from the failed
                    os.remove(init_path)  # write would poison later runs AND
                    # break save_checkpoint's dir rename onto it
                ckpt.save_checkpoint(init_path, state.params)
        if loaded is not None:
            _check_loaded_params(loaded, state.params, init_path)
            state = state.replace(params=loaded)

    if config.resume != "none":
        ckpt.recover_swap(config.resume)  # owner-side heal (crashed save swap)
        base_tpl = {"epoch": 0, "steps": 0, "loss_rec": 0.0, "metric": 0.0,
                    "params": state.params, "opt_state": state.opt_state}
        want_ema = bool(config.ema_decay)
        template = dict(base_tpl,
                        **({"ema_params": state.params} if want_ema else {}))
        try:
            restored = ckpt.restore_checkpoint(config.resume, template)
        except ValueError as first_err:
            # orbax is strict BOTH ways about the optional ema_params key
            # (measured: template-extra and template-missing each raise
            # ValueError) — so ema_decay can be toggled across a resume:
            # retry with the key flipped; if that fails too the mismatch was
            # something else, so surface the ORIGINAL error, not the
            # doubly-mutated retry's
            alt = (dict(base_tpl) if want_ema
                   else dict(base_tpl, ema_params=state.params))
            try:
                restored = ckpt.restore_checkpoint(config.resume, alt)
            except Exception:  # noqa: BLE001 — retry failed for any reason: surface the ORIGINAL error
                raise first_err
            if want_ema:
                print_log("resume checkpoint has no ema_params — re-seeding "
                          "the EMA shadow from the restored params", log)
            else:
                print_log("resume checkpoint carries ema_params but "
                          "ema_decay is off — dropping the shadow", log)
        _check_loaded_params(restored["params"], state.params, config.resume)
        epoch_start = int(restored["epoch"]) + 1
        steps = int(restored["steps"])
        loss_rec = float(restored["loss_rec"])
        best_loss = float(restored["metric"])
        state = state.replace(
            params=restored["params"], opt_state=restored["opt_state"], step=steps,
            **({"ema_params": restored["ema_params"]}
               if want_ema and "ema_params" in restored else {}),
        )
        print_log(f"resuming from epoch {epoch_start:8d} of " + config.resume, log)
        print_log(f"recovering best_loss {best_loss:4f}", log)
    else:
        print_log(f"Date: {asctime()}", log)
        print_log("TrainSet batchs:" + str(train_batches), log)
        print_log("TestSet batchs:" + str(test_batches), log)

    if config.ema_decay and (config.resume == "none"
                             or "ema_params" not in restored):
        # seed the EMA shadow from whatever params the run actually starts
        # with (fresh init, warm-start, or an ema-less resume). jnp.copy, not
        # aliasing: params and ema_params are both donated into the first
        # step, and aliased donated buffers are rejected.
        state = state.replace(
            ema_params=jax.tree.map(jnp.copy, state.params))

    # parallelism-dependent param layout: pipeline shards the stacked blocks
    # over 'pipe'; tensor parallelism shards Megatron column/row kernels over
    # 'model'; pure-dp stays replicated (gradient psum implicit in jit).
    specs, apply_fn = layout_for_mesh(model, mesh, state.params,
                                      n_microbatch=n_micro)
    state = shard_train_state(state, mesh, specs)
    spd = config.steps_per_dispatch
    if (max_steps is not None and spd > 1 and max_steps > steps
            and (max_steps - steps) % spd):
        # the loop advances `steps` in whole dispatches of spd optimizer
        # steps (one compiled lax.scan), so a bound not reachable in whole
        # dispatches FROM THE (possibly resumed) START STEP would silently
        # run up to spd-1 steps past max_steps — and the cosine schedule/
        # checkpoint counters would include them (ADVICE r4). A bench/test
        # comparing against a step-bounded baseline must get the exact step
        # count it asked for, so fail loud instead of rounding.
        raise ValueError(
            f"max_steps={max_steps} is not reachable in whole dispatches of "
            f"steps_per_dispatch={spd} from start step {steps}; the dispatch "
            "granularity makes the bound inexact — use a compatible bound, "
            "or steps_per_dispatch=1")
    train_step = make_train_step(
        model, apply_fn, prepare=prepare,
        ema_decay=config.ema_decay, grad_accum=config.grad_accum,
        moe_aux_weight=(config.moe_aux_weight
                        if config.num_experts > 1 else 0.0),
        steps_per_dispatch=spd)
    eval_step = make_eval_step(model, apply_fn, prepare=eval_prepare)
    writer = ScalarWriter(run_dir)
    step_rng = jax.random.PRNGKey(config.seed + 1)

    if config.nan_checks:
        profiling.enable_nan_checks()
    # step-bounded device trace (SURVEY.md §5: the reference only had
    # wall-clock prints); host 0 traces its own devices
    profiling_until = steps + config.profile_steps if config.profile_steps else 0
    if profiling_until and jax.process_index() == 0:
        profiling.start_trace(os.path.join(run_dir, "trace"))

    vloss = float("nan")
    loss_rec_dev = jnp.float32(loss_rec)
    time_start = time.time()
    done = False
    # the host→device copy of batch n+1 overlaps the compute of batch n —
    # an unprefetched loop would serialize transfer and compute
    place = lambda b: shard_batch(b, mesh)  # noqa: E731
    # grouped batches carry a leading scan axis — 'data' shards dim 1 there
    place_train = (lambda b: shard_batch(b, mesh, grouped=True)) if spd > 1 else place
    saver = _AsyncSaver(
        sync=jax.process_count() > 1 or not config.async_checkpoint)
    stopper = _GracefulStop()
    stopper.__enter__()  # released AFTER the finally block below — a signal
    # during the last in-flight checkpoint write must stay graceful too
    try:
        for epoch in range(epoch_start, config.epoch[1]):
            train_loader.set_epoch(epoch)
            # steps_per_dispatch > 1: n batches stack into one dispatch that
            # scans n optimizer steps on device (n× fewer host round trips).
            # Log/stop checks fire on
            # log-window BOUNDARY CROSSINGS, which for spd=1 reduces to the
            # old `steps % log_every == 0`.
            for batch in device_prefetch(
                    group_batches(train_loader, spd) if spd > 1 else train_loader,
                    place_train):
                state, _, loss_rec_dev = train_step(
                    state, batch, step_rng, loss_rec_dev
                )
                prev_steps = steps
                steps += spd
                crossed = steps // log_every > prev_steps // log_every
                if profiling_until and steps >= profiling_until and jax.process_index() == 0:
                    float(loss_rec_dev)  # drain the device before the trace stops
                    profiling.stop_trace()
                    # beside the trace: which layer each of its instructions
                    # belongs to (built now, once, from the step's own
                    # caches). The run does not depend on it.
                    try:
                        scopes.write(os.path.join(run_dir, "scopes.json"))
                    except Exception:  # noqa: BLE001 — reported, training goes on
                        print_log("scopes.json was not written:\n"
                                  + traceback.format_exc(), log)
                    profiling_until = 0
                if crossed and jax.process_index() == 0:
                    loss_rec = float(loss_rec_dev)  # the only per-step host sync
                    time_end = time.time()
                    print_log(
                        f"steps: {steps:8d} loss: {loss_rec:.4f} "
                        f"time_cost: {time_end - time_start:.2f}", log)
                    time_start = time.time()
                # consensus check at an aligned loop point (every log window)
                # — gating collectives on the host-local flag would leave
                # only the signaled host's loop, deadlocking the slice
                if crossed and stopper.agreed():
                    done = True
                    if jax.process_index() == 0:
                        print_log(f"stop signal at step {steps:8d} — "
                                  "evaluating, checkpointing, exiting", log)
                    break
                if max_steps is not None and steps >= max_steps:
                    done = True
                    break
            # epoch end is also an aligned loop point every host reaches —
            # without this check a run whose epoch is shorter than log_every
            # ignores a stop signal for ⌈log_every/steps_per_epoch⌉ epochs
            if not done and stopper.agreed():
                done = True
                if jax.process_index() == 0:
                    print_log(f"stop signal at epoch {epoch:4d} end — "
                              "evaluating, checkpointing, exiting", log)
            loss_rec = float(loss_rec_dev)

            # -- evaluate: global-mean loss per batch, mean over batches --------
            # losses stay on device so dispatch pipelines across the val set; the
            # single float() below is the only host sync (the reference's
            # loss.item()-per-batch pattern would idle the TPU between batches)
            test_loader.set_epoch(epoch)
            batch_losses = [
                eval_step(state.params, b) for b in device_prefetch(test_loader, place)
            ]
            vloss = float(jnp.mean(jnp.stack(batch_losses)))

            if jax.process_index() == 0:
                print_log(f"epoch: {epoch:4d}    loss: {vloss:.5f}    time:{asctime()}", log)
                writer.add_scalar("loss", vloss, epoch)
            # orbax writes of sharded global arrays are collective — EVERY process
            # calls save_checkpoint (vloss is a global mean, identical on all
            # hosts, so the branch agrees); only logging and the host-local torch
            # pkl export stay process-0-gated.
            saver.wait()  # at most one epoch's saves in flight
            if saver.sync:
                # synchronous saves finish before the next (donating) step
                params_snap, opt_snap = state.params, state.opt_state
                ema_snap = state.ema_params
            else:
                # snapshot on device: the live buffers are donated to the next
                # train_step, so the async saver must read from its own copy
                params_snap = jax.tree.map(jnp.copy, state.params)
                opt_snap = jax.tree.map(jnp.copy, state.opt_state)
                ema_snap = (jax.tree.map(jnp.copy, state.ema_params)
                            if state.ema_params is not None else None)

            # NaN-safe: a diverged epoch (vloss NaN) compares False and leaves
            # best_loss finite — min() would store NaN and poison resume
            improved = vloss < best_loss
            if improved:
                best_loss = vloss

            def save_epoch(epoch=epoch, steps=steps, loss_rec=loss_rec,
                           improved=improved, best=best_loss,
                           params=params_snap, opt_state=opt_snap,
                           ema=ema_snap):
                if improved:
                    ckpt.save_checkpoint(os.path.join(run_dir, "bestloss.ckpt"), params)
                    if ema is not None:
                        # the smoothed weights diffusion users actually sample
                        # from; saved beside (never instead of) the live best
                        ckpt.save_checkpoint(
                            os.path.join(run_dir, "bestloss_ema.ckpt"), ema)
                    if (jax.process_index() == 0 and _fully_addressable(params)
                            and config.num_experts == 1):
                        # (MoE params have no reference torch layout — the
                        # bridge refuses them, so don't retry every epoch)
                        # best-effort bridge export (torch-less hosts fall
                        # back to the native writer internally): a refused
                        # export must never kill the run at its best-loss
                        # moment — the orbax ckpt above is already safe
                        try:
                            ckpt.save_torch_pkl(params,
                                                os.path.join(run_dir, "bestloss.pkl"),
                                                config.patch_size)
                            if ema is not None:  # reference-bridge export of
                                ckpt.save_torch_pkl(  # the smoothed weights
                                    ema,
                                    os.path.join(run_dir, "bestloss_ema.pkl"),
                                    config.patch_size)
                        except Exception as e:  # noqa: BLE001
                            print_log(f"bestloss pkl export skipped: {e}", log)
                if config.snapshot_epochs and epoch % config.snapshot_epochs == 0:
                    # bare-params snapshot for the FID trend
                    # (scripts/fid_trend.py); keyed by epoch, never rewritten.
                    # With EMA on, the smoothed weights land beside as
                    # epoch_<E>_ema (the trend's strict epoch_(\d+) match
                    # keeps its raw-params series uncontaminated).
                    snap_dir = os.path.join(run_dir, "snapshots")
                    os.makedirs(snap_dir, exist_ok=True)
                    ckpt.save_checkpoint(
                        os.path.join(snap_dir, f"epoch_{epoch}"), params)
                    if ema is not None:
                        ckpt.save_checkpoint(
                            os.path.join(snap_dir, f"epoch_{epoch}_ema"), ema)
                ckpt.save_checkpoint(
                    os.path.join(run_dir, "lastepoch.ckpt"),
                    {"epoch": epoch, "steps": steps, "loss_rec": loss_rec,
                     "metric": best, "params": params,
                     "opt_state": opt_state,
                     **({"ema_params": ema} if ema is not None else {})},
                )

            saver.submit(save_epoch)
            if done:
                break
    finally:
        # every cleanup step must run even when an earlier one raises: an
        # abandoned in-flight checkpoint write (saver.wait skipped) loses the
        # final epoch, and a leaked signal handler outlives run()
        try:
            try:
                if profiling_until and jax.process_index() == 0:
                    profiling.stop_trace()  # run ended inside the trace window
            finally:
                writer.close()
        finally:
            try:
                saver.wait()
            finally:
                # hand signals back LAST — a SIGTERM during the waits above
                # stayed graceful (second signal escalates to immediate kill)
                stopper.__exit__()
    return TrainResult(best_loss=best_loss, last_val_loss=vloss, steps=steps,
                       run_dir=run_dir)
