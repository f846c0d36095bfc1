"""Training through the program's own step and loader: ``make_train_step``
with the device-side cold degradation, ``ShardedLoader`` over a seeded
``scripts/make_dataset.py`` set with the native decoder and the decoded
cache, ``device_prefetch`` to a ``data`` mesh of the cell's chips.

Traffic file: {"driver": "train_loader", "per_chip_batch": images per chip
and step (AMP doubling included), "base_lr": as the yaml, "epochs": length of
the cosine, "dataset": {"size": px, "train": images}, "reference_rows": rows
per block of the reference (0: whole batch)}.

Set-up builds ONE object -- the compiled step with its state and its feed --
drives it through its first three steps and hands it to the window; the
reference follows those three. The configuration has to state every dropout
rate as 0 (and list the keys under ``reduced``): the masks come from the
program's random stream, which the reference cannot reproduce, and a step
built without them for the check would be a second program, not the timed one.
"""

from __future__ import annotations

import itertools
import os
import sys
import time

import numpy as np

from benchmark import weights
from benchmark.drivers import common
from benchmark.harness import Compared, log
from benchmark.manifest import ROOT

FOLLOWED = 3


def dataset_dir(spec: dict) -> str:
    """Made once per checkout from the recipe's own fixed seed, then reused:
    every pixel is a function of (split, index), not of ``--seed``."""
    path = os.path.join(ROOT, ".bench_data",
                        f"flowers_{spec['size']}px_{spec['train']}")
    done = os.path.join(path, ".complete")
    if not os.path.exists(done):
        sys.path.insert(0, os.path.join(ROOT, "scripts"))
        import make_dataset as recipe

        log(f"making the dataset at {path}")
        recipe.main(["--out", path, "--size", str(spec["size"]),
                     "--train", str(spec["train"]), "--val", "1"])
        open(done, "w").close()
    return os.path.join(path, "train")


class Feed:
    """The loader's batches, epoch after epoch, placed on the mesh two ahead
    (the trainer's own ``device_prefetch``); the first ``keep`` host batches
    are kept for the reference."""

    def __init__(self, loader, mesh, keep: int):
        from ddim_cold_tpu.data.loader import device_prefetch
        from ddim_cold_tpu.parallel import shard_batch

        self.kept: list = []

        def place(batch):
            if len(self.kept) < keep:
                self.kept.append(tuple(np.array(x) for x in batch))
            return shard_batch(batch, mesh)

        def stream():
            for epoch in itertools.count():
                loader.set_epoch(epoch)
                yield from device_prefetch(loader, place)

        self._it = stream()

    def __next__(self):
        return next(self._it)

    def close(self) -> None:
        self._it.close()


def _find_mu(opt_state):
    import jax

    for node in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu")):
        if hasattr(node, "mu"):
            return node.mu
    raise LookupError("no Adam moments in the optimizer state")


def require_no_dropout(config: dict) -> None:
    active = {k: config[k] for k in ("drop_rate", "attn_drop_rate",
                                     "drop_path_rate") if config[k]}
    if active:
        raise ValueError(f"the reference cannot follow a step with dropout "
                         f"active: the configuration states {active}")


def setup(run) -> dict:
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.data import ColdDownSampleDataset, ShardedLoader
    from ddim_cold_tpu.ops import degrade
    from ddim_cold_tpu.parallel import ambient, make_mesh, shard_train_state
    from ddim_cold_tpu.train.step import (EmaTrainState, make_optimizer,
                                          make_train_step)

    require_no_dropout(run.config)
    tr = run.traffic
    chips = len(run.devices)
    global_batch = int(tr["per_chip_batch"]) * chips
    size = int(run.config["img_size"][0])
    with run.spans.span("dataset"):
        data = ColdDownSampleDataset(dataset_dir(tr["dataset"]),
                                     imgSize=(size, size), target_mode="chain",
                                     cache_images=True)
        loader = ShardedLoader(data, global_batch, shuffle=True,
                               seed=run.seed % (2 ** 31), drop_last=True,
                               raw=True)
        steps_per_epoch = len(loader)
        if steps_per_epoch == 0:
            raise ValueError("dataset smaller than one global batch")
        # decode every image once now: the window measures the warm cache
        for _ in loader:
            pass
    mesh = make_mesh({"data": chips}, devices=run.devices)
    model = common.build_model(run.config)
    lr = float(tr["base_lr"]) * global_batch / 512.0
    decay_steps = steps_per_epoch * int(tr["epochs"])
    params0 = weights.make(run.config, run.seed)
    state = {"mesh": mesh, "model": model, "lr": lr,
             "decay_steps": decay_steps, "global_batch": global_batch,
             "params0": jax.device_get(params0)}
    ctx = ambient(mesh)
    ctx.__enter__()  # the step traces, and its kernels launch, under the mesh
    state["ambient"] = ctx
    train_state = EmaTrainState.create(
        apply_fn=model.apply, params=params0, tx=make_optimizer(lr, decay_steps)
    ).replace(step=jnp.asarray(0, jnp.int32))
    train_state = shard_train_state(train_state, mesh, None)
    prepare = degrade.make_cold_prepare(
        size=size, max_step=data.max_step, chain=True, mesh=mesh)
    state["step"] = make_train_step(model, prepare=prepare)
    state["rng"] = weights.seed_key(run.seed, 2)
    state["train"] = train_state
    state["rec"] = jnp.float32(5.0)
    state["feed"] = Feed(loader, mesh, keep=FOLLOWED)
    # the first steps, through the window's own call and feed
    first = {"losses": []}
    with run.spans.span("first_steps"):
        for i in range(FOLLOWED):
            first["losses"].append(float(one_step(run, state)))
            if i == 0:
                first["mu"] = jax.device_get(_find_mu(state["train"].opt_state))
        first["params"] = jax.device_get(state["train"].params)
    state["first"] = first
    return state


def one_step(run, state):
    """The window's call: next batch from the feed, one optimizer step."""
    with run.spans.span("next_batch"):
        batch = next(state["feed"])
    with run.spans.span("dispatch"):
        state["train"], loss, state["rec"] = state["step"](
            state["train"], batch, state["rng"], state["rec"])
    return loss


def window(run, state, seconds: float) -> dict:
    import jax

    losses = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        losses.append(one_step(run, state))
    with run.spans.span("block_until_ready"):
        jax.block_until_ready(state["train"].params)
    t1 = time.perf_counter()
    steps = len(losses)
    losses = np.asarray(jax.device_get(losses), np.float64)
    batch = state["global_batch"]
    log(f"{steps} steps of {batch} images in {t1 - t0:.3f} s; loss "
        f"{losses[0]:.5f} -> {losses[-1]:.5f}")
    return {"attempted": steps, "failed": int((~np.isfinite(losses)).sum()),
            "window_s": t1 - t0, "t0": t0, "t1": t1,
            "e2e": {"train_img_per_s": steps * batch / (t1 - t0)},
            "counters": {"steps": steps, "global_batch": batch},
            "losses": losses}


def close(run, state) -> None:
    state["feed"].close()
    state["ambient"].__exit__(None, None, None)
    # the program's state is freed before the reference runs
    for key in ("train", "step", "rec"):
        state.pop(key, None)


def leaf_norms(tree) -> np.ndarray:
    import jax

    return np.asarray([float(np.sqrt(np.sum(np.square(
        np.asarray(x, np.float64))))) for x in jax.tree.leaves(tree)])


def worst_leaf(got: np.ndarray, want: np.ndarray) -> float:
    """Worst leaf's gap between the two norms, against that leaf's reference
    norm or the median leaf's, whichever is larger."""
    return float(np.max(np.abs(got - want)
                        / np.maximum(want, np.median(want))))


def follow_reference(run, state, ops=None):
    """(losses, first-gradient leaf norms, parameter-change leaf norms) of
    the reference over the kept batches."""
    import jax

    from benchmark.reference import train as ref
    from benchmark.reference import vit

    batches = []
    for base, t in state["feed"].kept:
        noisy, target = ref.cold_batch(base, t)
        batches.append((noisy, target, t))
    params0 = state["params0"]
    losses, grad, params = ref.follow(
        jax.device_put(params0), batches, arch=weights.arch_of(run.config),
        lr=state["lr"], decay_steps=state["decay_steps"],
        ops=ops or vit.EXACT, rows=int(run.traffic.get("reference_rows", 0)))
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                          jax.device_get(params), params0)
    return losses, leaf_norms(grad), leaf_norms(change)


def check(run, state, result) -> list:
    import jax

    from benchmark.reference import train as ref

    first = state["first"]
    want = follow_reference(run, state)
    result["reference"] = want
    got_grad = leaf_norms(first["mu"]) / (1.0 - ref.B1)
    got_change = leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(a) - np.asarray(b), first["params"],
        state["params0"]))
    return _compare(run, (first["losses"], got_grad, got_change), want) + [
        Compared("window_losses_not_finite", float(result["failed"]), 0.0)]


def _compare(run, got, want) -> list:
    limits = run.cell.limits
    (losses, grad, change), (want_losses, want_grad, want_change) = got, want
    out = [Compared(f"loss_step{i}_rel",
                    abs(losses[i] - want_losses[i]) / want_losses[i],
                    limits["loss_rel"][i]) for i in range(FOLLOWED)]
    return out + [
        Compared("first_grad_worst_leaf", worst_leaf(grad, want_grad),
                 limits["first_grad_worst_leaf"]),
        Compared("param_change_worst_leaf", worst_leaf(change, want_change),
                 limits["param_change_worst_leaf"]),
    ]


def control(run, state, result) -> list:
    """The reference one precision below the configuration's, put in the
    program's place over the same three batches. Must fail a limit."""
    from benchmark.reference import lowprec

    below = lowprec.BY_NAME[lowprec.BELOW[run.config["precision"]]]
    return _compare(run, follow_reference(run, state, ops=below),
                    result["reference"])
