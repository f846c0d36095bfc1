#!/usr/bin/env python
"""Bring-up smoke on the chip: train → sample → serve at 200px/p4, through the
entry points a user calls, in ONE process.

    python chip_smoke.py              # one chip: train, sample, serve
    python chip_smoke.py --chips 4    # four chips: data-parallel training and
                                      # mesh-sharded sampling against the same
                                      # work on one device — and nothing else

The model is ``oxford_flower_200_p4`` exactly as ``20220822_200px.yaml`` trains
it (200×200, patch 4 → 2501 tokens, embed 256, depth 6, 4 heads, bf16,
``use_flash``, batch 8 ⇒ 16). Weights are whatever a few optimizer steps from
the yaml's seed give; the dataset is ``scripts/make_dataset.py`` at its own
fixed seed.

It fails — non-zero exit, no result line — when JAX's first device is not a
TPU, when fewer chips are visible than asked for, or when any phase raises or
misses a check. It never continues on another backend. The last stdout line of
a passing run is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it; everything worth reading is printed before it.

This is a smoke test, not a benchmark: the seconds it prints say that the
system starts and how long its compiles take, nothing about its speed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

YAML = os.path.join(HERE, "20220822_200px.yaml")  # what the train phase runs
MODEL_CONFIG = "oxford_flower_200_p4"             # the MODEL_CONFIGS entry it is
K = 100                          # sampler stride: 2000 / 100 = 20 steps
TRAIN_STEPS = 8                  # optimizer steps in the one smoke epoch
VAL_BATCHES = 2
SERVE_BUCKETS = (8, 32)          # a small and a large bucket
#: the serving contract is bitwise equality with direct sampling. Where the
#: chip does not give that, rows may differ by at most four bfloat16 ulps of
#: the model's [−1, 1] output, i.e. 4 · 2⁻⁸ / 2 in the [0, 1] images compared.
SERVE_MAX_ABS = 4 * 2.0 ** -8 / 2
#: four-chip and one-chip training see the same global batches; their losses
#: differ only by the all-reduce's summation order in bf16 compute.
DP_LOSS_RTOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from the
    persistent cache), and cache hits/misses, from ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += seconds

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, device):
    """Print what one phase cost: wall, compile share, cache traffic, peak."""
    t0, c0, h0, m0 = time.perf_counter(), clock.seconds, clock.hits, clock.misses
    log(f"--- {name} ---")
    yield
    wall = time.perf_counter() - t0
    # nested jits report their own tracing inside the outer one's, so the
    # summed events can exceed the wall of a phase that is all compile
    comp = min(clock.seconds - c0, wall)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"{name}: wall {wall:.1f} s = compile {comp:.1f} s + run "
        f"{wall - comp:.1f} s; compile cache {clock.hits - h0} hits / "
        f"{clock.misses - m0} misses; peak_bytes_in_use {peak}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    log(f"ok: {what}")


def cache_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path))


def n_custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# ------------------------------------------------------------------- train

def write_experiment(scratch: str, name: str, data_dir: str, **edits) -> None:
    """``<scratch>/<name>.yaml``: the repo's 200px yaml with its dataStorage
    pointed at the smoke dataset, a one-epoch range, and ``edits``."""
    import yaml

    with open(YAML) as f:
        raw = yaml.safe_load(f)
    raw["dataStorage"] = [os.path.join(data_dir, "train"),
                          os.path.join(data_dir, "val")]
    raw["epoch"] = [0, 1]
    raw.update(edits)
    with open(os.path.join(scratch, name + ".yaml"), "w") as f:
        yaml.safe_dump(raw, f)
    log(f"{name}.yaml = {os.path.basename(YAML)} with dataStorage → "
        f"{data_dir}, epoch → [0, 1]"
        + "".join(f", {k} → {v}" for k, v in edits.items()))


def make_dataset(data_dir: str, global_batch: int) -> None:
    import yaml

    sys.path.insert(0, os.path.join(HERE, "scripts"))
    import make_dataset as recipe

    with open(YAML) as f:
        size = yaml.safe_load(f)["image_size"][0]
    recipe.main(["--out", data_dir, "--size", str(size),
                 "--train", str(TRAIN_STEPS * global_batch),
                 "--val", str(VAL_BATCHES * global_batch)])


def run_trainer(scratch: str, name: str) -> dict:
    """``multi_gpu_trainer.main`` on ``<scratch>/<name>.yaml``, with the jitted
    train step it builds watched from outside: the step is compiled ahead of
    time at its first call (one compile, whose text can then be read) and
    run from that executable; every loss it returns is kept, and the devices
    holding the first step's params and batch are noted."""
    import jax

    import multi_gpu_trainer
    from ddim_cold_tpu.train import trainer

    seen = {"losses": [], "compiled": None}
    real_make = trainer.make_train_step

    def watched_make(*args, **kwargs):
        step = real_make(*args, **kwargs)

        def watched_step(state, batch, rng, loss_rec):
            if seen["compiled"] is None:
                seen["compiled"] = step.lower(state, batch, rng, loss_rec).compile()
                leaf = jax.tree.leaves(state.params)[0]
                seen["param_devices"] = {s.device for s in leaf.addressable_shards}
                seen["batch_devices"] = {
                    s.device for s in jax.tree.leaves(batch)[0].addressable_shards}
            out = seen["compiled"](state, batch, rng, loss_rec)
            seen["losses"].append(out[1])
            return out

        return watched_step

    trainer.make_train_step = watched_make
    cwd = os.getcwd()
    os.chdir(scratch)  # the launcher looks for <ExpName>.yaml in the cwd
    try:
        rc = multi_gpu_trainer.main(["multi_gpu_trainer.py", name],
                                    base_dir=scratch)
    finally:
        os.chdir(cwd)
        trainer.make_train_step = real_make
    check(rc == 0, f"multi_gpu_trainer.main({name}) returned 0")
    seen["losses"] = [float(x) for x in seen["losses"]]
    return seen


def check_run_dir(scratch: str, name: str, seen: dict, global_batch: int):
    """Losses, train.log and the lastepoch checkpoint of one trainer run.
    Returns (model as trained, params restored from lastepoch.ckpt, run dir)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model
    from ddim_cold_tpu.utils import checkpoint as ckpt

    losses = seen["losses"]
    log(f"{name}: per-step train loss " + " ".join(f"{x:.4f}" for x in losses))
    log(f"{name}: loss {'fell' if losses[-1] < losses[0] else 'did not fall'} "
        f"over {len(losses)} steps (reported, not gated)")
    check(len(losses) == TRAIN_STEPS and all(np.isfinite(losses)),
          f"{TRAIN_STEPS} optimizer steps, every loss finite")

    config = load_config(os.path.join(scratch, name + ".yaml"), name)
    run_dir = os.path.join(scratch, "Saved_Models", config.run_name)
    with open(os.path.join(run_dir, "train.log")) as f:
        lines = f.read().splitlines()
    log(f"{name}: train.log: " + " | ".join(lines))
    check(lines[0].startswith("Date: ")
          and lines[1] == f"TrainSet batchs:{TRAIN_STEPS}"
          and lines[2] == f"TestSet batchs:{VAL_BATCHES}"
          and re.fullmatch(r"epoch:    0    loss: \d+\.\d{5}    time:.+",
                           lines[-1]) is not None,
          "train.log in the reference format")
    val = float(lines[-1].split("loss:")[1].split()[0])
    check(np.isfinite(val), f"validation loss {val} finite")

    model = build_model(config)
    template = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *model.img_size, 3)),
        jnp.zeros((1,), jnp.int32))["params"])
    last = ckpt.restore_checkpoint(os.path.join(run_dir, "lastepoch.ckpt"))
    params = last["params"]
    check(int(last["steps"]) == TRAIN_STEPS and int(last["epoch"]) == 0,
          "lastepoch.ckpt restored with the step and epoch it was saved at")
    check(jax.tree.structure(params) == jax.tree.structure(template)
          and all(a.shape == b.shape and np.isfinite(a).all()
                  for a, b in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(template))),
          "restored params match the model's tree and are finite")
    check(config.effective_batch * config.num_devices == global_batch,
          f"global batch {global_batch}")
    return model, params, run_dir


def phase_train(scratch: str, clock, device) -> tuple:
    import jax.numpy as jnp

    from ddim_cold_tpu.models import MODEL_CONFIGS

    with phase("train", clock, device):
        data_dir = os.path.join(scratch, "OxfordFlowers200")
        make_dataset(data_dir, global_batch=16)
        from ddim_cold_tpu.data import native

        log("data loader: " + ("native libddim_data.so (built from "
                               "native/ddim_data.cc at first use when absent)"
                               if native.available() else "PIL/numpy"))
        name = "chip_smoke_200px"
        write_experiment(scratch, name, data_dir)
        seen = run_trainer(scratch, name)
        model, params, run_dir = check_run_dir(scratch, name, seen, 16)
        width = dict(img_size=tuple(model.img_size),
                     patch_size=model.patch_size, embed_dim=model.embed_dim,
                     depth=model.depth, num_heads=model.num_heads)
        check(width == MODEL_CONFIGS[MODEL_CONFIG] and model.dtype == jnp.bfloat16
              and model.use_flash is True,
              f"trained {MODEL_CONFIG} at full width in bf16 with use_flash: "
              f"{model.num_patches + 1} tokens, {width}")
        calls = n_custom_calls(seen["compiled"])
        log(f"train step: {calls} tpu_custom_call in the compiled program")
        check(calls > 0, "the jitted train step contains the flash kernels")
    return model, params, run_dir


# ------------------------------------------------------------------ sample

def check_images(x, shape, what: str) -> None:
    import numpy as np

    x = np.asarray(x)
    check(x.shape == shape and np.isfinite(x).all()
          and x.min() >= 0.0 and x.max() <= 1.0,
          f"{what}: shape {shape}, finite, in [0, 1]")


def phase_sample(run_dir: str, clock, device) -> None:
    import numpy as np

    import ViT
    from ddim_cold_tpu.models import MODEL_CONFIGS

    n = 16
    hw = tuple(MODEL_CONFIGS[MODEL_CONFIG]["img_size"])
    with phase("sample", clock, device):
        seq, img = ViT.main.main(
            ["--config", MODEL_CONFIG, "--acc_k", str(K), "--sample_n", str(n),
             "--checkpoint", os.path.join(run_dir, "bestloss.ckpt")],
            standalone_mode=False)
        # the figure is always the k=100 trajectory: the start noise, then
        # the 20 x̂0 predictions — only those are images
        check(seq.shape[0] == 21 and bool(np.isfinite(np.asarray(seq[0])).all()),
              "ViT.py trajectory: finite start noise + 20 frames")
        check_images(seq[1:], (20, 6, *hw, 3), "ViT.py k=100 trajectory frames")
        check_images(img, (n, *hw, 3), f"ViT.py --acc_k {K} samples")


# ------------------------------------------------------------------- serve

def phase_serve(model, params, clock, device) -> None:
    import jax
    import numpy as np

    from ddim_cold_tpu import serve
    from ddim_cold_tpu.ops import sampling

    with phase("serve", clock, device):
        eng = serve.Engine(model, params, buckets=SERVE_BUCKETS)
        cfg = serve.SamplerConfig(k=K)
        report = serve.warmup(eng, [cfg])
        log(f"warmup: {report['new_compiles']} compiles, "
            f"{report['programs']} programs, buckets {report['buckets']}")
        for bucket in SERVE_BUCKETS:
            calls = n_custom_calls(eng.ensure_program(cfg, bucket))
            log(f"sampler program, bucket {bucket}: {calls} tpu_custom_call")
            check(calls > 0, f"bucket-{bucket} sampler program contains the "
                             "flash kernel")
        compiles = eng.stats["compiles"]

        worst = 0.0
        # first drain pads one request into the small bucket, the second
        # coalesces three into the large one
        for group in ([(901, 3)], [(902, 8), (903, 5), (904, 12)]):
            tickets = [(seed, n, eng.submit(seed=seed, n=n, config=cfg))
                       for seed, n in group]
            eng.run()
            for seed, n, ticket in tickets:
                got = np.asarray(ticket.result(timeout=600))
                want = np.asarray(sampling.ddim_sample(
                    model, params, jax.random.PRNGKey(seed), k=K, n=n))
                check_images(got, (n, *model.img_size, 3),
                             f"ticket seed={seed} n={n}")
                diff = float(np.abs(got - want).max())
                worst = max(worst, diff)
                log(f"ticket seed={seed} n={n}: "
                    + ("bitwise equal to direct ddim_sample" if diff == 0.0
                       else f"max |Δ| vs direct ddim_sample {diff:.3e}"))
        check(eng.stats["compiles"] == compiles,
              f"no compile after warmup (compiles stayed {compiles})")
        if worst == 0.0:
            check(True, "every ticket bitwise equal to direct sampling")
        else:
            check(worst <= SERVE_MAX_ABS,
                  f"tickets within {SERVE_MAX_ABS:.3e} of direct sampling "
                  f"(worst {worst:.3e}; NOT bitwise on this backend)")


# --------------------------------------------------------------- four chips

def phase_four_chips(scratch: str, clock, device) -> None:
    """Data-parallel training over four chips against the same global batches
    on one of them, then mesh-sharded sampling against the unsharded call."""
    import jax
    import numpy as np

    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.parallel import make_mesh, shard_params

    with phase("train on 4 chips vs 1", clock, device):
        data_dir = os.path.join(scratch, "OxfordFlowers200")
        make_dataset(data_dir, global_batch=64)
        write_experiment(scratch, "chip_smoke_dp4", data_dir, num_gpus=4)
        dp4 = run_trainer(scratch, "chip_smoke_dp4")
        model, params, _ = check_run_dir(scratch, "chip_smoke_dp4", dp4, 64)
        for what in ("param_devices", "batch_devices"):
            log(f"dp4 {what}: {sorted(str(d) for d in dp4[what])}")
            check(len(dp4[what]) == 4, f"{what} are four distinct devices")
        check("all-reduce" in dp4["compiled"].as_text(),
              "dp4 train step all-reduces its gradients")
        check(n_custom_calls(dp4["compiled"]) > 0,
              "dp4 train step contains the flash kernels")

        # same global batch of 64 on one device: num_gpus 1 × batch_size 32 × 2
        write_experiment(scratch, "chip_smoke_dp1", data_dir, num_gpus=1,
                         batch_size=32)
        dp1 = run_trainer(scratch, "chip_smoke_dp1")
        check_run_dir(scratch, "chip_smoke_dp1", dp1, 64)
        check(len(dp1["param_devices"]) == 1, "the comparison ran on one device")
        np.testing.assert_allclose(dp4["losses"], dp1["losses"],
                                   rtol=DP_LOSS_RTOL)
        check(True, f"4-chip and 1-chip losses agree to rtol {DP_LOSS_RTOL}")

    with phase("sample on a 4-chip data mesh vs unsharded", clock, device):
        mesh = make_mesh({"data": 4})
        rng, n = jax.random.PRNGKey(7), 16
        sharded = sampling.ddim_sample(model, shard_params(params, mesh), rng,
                                       k=K, n=n, mesh=mesh)
        devices = {s.device for s in sharded.addressable_shards}
        log(f"sharded sample devices: {sorted(str(d) for d in devices)}")
        check(len(devices) == 4, "sampled batch lives on four distinct devices")
        single = sampling.ddim_sample(model, params, rng, k=K, n=n)
        check_images(sharded, (n, *model.img_size, 3), "mesh-sharded samples")
        diff = float(np.abs(np.asarray(sharded) - np.asarray(single)).max())
        log("mesh-sharded vs unsharded ddim_sample: "
            + ("bitwise equal" if diff == 0.0 else f"max |Δ| {diff:.3e}"))
        check(diff <= SERVE_MAX_ABS,
              f"mesh-sharded samples within {SERVE_MAX_ABS:.3e} of unsharded")


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparison")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no accelerator — jax {jax.__version__} found "
              f"{devices}; this script runs on a TPU only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {devices}", file=sys.stderr)
        return 2

    from ddim_cold_tpu.utils.platform import CACHE_ENV, enable_compile_cache

    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}; devices {devices}")
    cache_dir = enable_compile_cache()
    before = cache_entries(cache_dir)
    log(f"compile cache: {cache_dir} "
        f"({CACHE_ENV} {'set' if os.environ.get(CACHE_ENV) else 'unset'}), "
        f"{before} entries before — {'warm' if before else 'cold'}")
    clock = CompileClock()
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            phase_four_chips(scratch, clock, devices[0])
        else:
            model, params, run_dir = phase_train(scratch, clock, devices[0])
            phase_sample(run_dir, clock, devices[0])
            phase_serve(model, params, clock, devices[0])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    log(f"compile cache: {cache_entries(cache_dir)} entries after "
        f"({before} before); {clock.seconds:.1f} s compiling in all, "
        f"{clock.hits} cache hits / {clock.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
