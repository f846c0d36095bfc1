"""Training-layer tests: config derivation rules, end-to-end CPU training,
checkpoint/resume, converter round-trips (SURVEY.md §4 integration plan)."""

import json
import os

import numpy as np
import pytest
import yaml

from ddim_cold_tpu.config import ExperimentConfig, load_config


def _write_config(tmp_path, data_dir, **overrides):
    cfg = {
        "initializing": "none",
        "resume": "none",
        "AMP": False,
        "framework": "vit_test",
        "num_gpus": 1,
        "batch_size": 2,
        "epoch": [0, 2],
        "base_lr": 0.005,
        "dataStorage": [data_dir, data_dir],
        "image_size": [64, 64],
        "diff_step": 6,
        "patch_size": 8,
        "embed_dim": 32,
        "depth": 1,
        "head": 2,
    }
    cfg.update(overrides)
    path = os.path.join(tmp_path, "exp.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


def test_config_derivation_rules(tmp_path, synthetic_image_dir):
    """AMP doubles batch; lr = base·batch·devices/512 (multi_gpu_trainer.py:191-196)."""
    path = _write_config(str(tmp_path), synthetic_image_dir, AMP=True,
                         batch_size=16, num_gpus=4, base_lr=0.005)
    cfg = load_config(path, "exp")
    assert cfg.effective_batch == 32
    assert cfg.lr == pytest.approx(0.005 * 32 * 4 / 512)
    assert cfg.run_name == "expvit_test"
    # diff_step read but table stays 2000 by default (quirk #4)
    assert cfg.diff_step == 6 and cfg.total_steps == 2000
    cfg2 = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                     honor_diff_step=True), "exp")
    assert cfg2.total_steps == 6


def test_config_rejects_unknown_keys(tmp_path, synthetic_image_dir):
    """A typo'd key must fail loud with a did-you-mean hint — the .get()-
    based loader would otherwise silently ignore it and the run would be
    silently misconfigured (e.g. `use_flahs: true` training dense)."""
    path = _write_config(str(tmp_path), synthetic_image_dir, use_flahs=True)
    with pytest.raises(ValueError, match="use_flahs.*did you mean 'use_flash'"):
        load_config(path, "exp")
    path = _write_config(str(tmp_path), synthetic_image_dir,
                         totally_novel_knob=1)
    with pytest.raises(ValueError, match="totally_novel_knob"):
        load_config(path, "exp")


def test_config_flash_blocks_plumbed(tmp_path, synthetic_image_dir):
    """`flash_blocks: [bq, bkv]` reaches the model (the --flash-block-sweep
    winner is pinnable in the YAML); malformed values fail loud."""
    from ddim_cold_tpu.train.trainer import build_model

    path = _write_config(str(tmp_path), synthetic_image_dir,
                         use_flash=True, flash_blocks=[512, 1024])
    cfg = load_config(path, "exp")
    assert cfg.flash_blocks == (512, 1024)
    assert build_model(cfg).flash_blocks == (512, 1024)
    bad = _write_config(str(tmp_path), synthetic_image_dir,
                        use_flash=True, flash_blocks=[512])
    with pytest.raises(ValueError, match="flash_blocks"):
        load_config(bad, "exp")
    # blocks without use_flash would silently attend dense — fail loud
    noflash = _write_config(str(tmp_path), synthetic_image_dir,
                            flash_blocks=[512, 1024])
    with pytest.raises(ValueError, match="use_flash is false"):
        load_config(noflash, "exp")


@pytest.fixture(scope="module")
def trained_run(tmp_path_factory, synthetic_image_dir):
    """Train 2 epochs on the 10-image folder (shared by several tests)."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path_factory.mktemp("run"))
    cfg = load_config(_write_config(base, synthetic_image_dir,
                                    snapshot_epochs=1), "exp")
    result = run(cfg, base, log_every=2)
    return base, cfg, result


@pytest.mark.isolated
def test_train_end_to_end(trained_run):
    base, cfg, result = trained_run
    assert result.steps == 2 * (10 // 2)  # 2 epochs × 5 batches
    assert np.isfinite(result.last_val_loss)
    assert result.best_loss < 5.0  # improved from the init sentinel
    run_dir = result.run_dir
    assert os.path.isdir(os.path.join(run_dir, "bestloss.ckpt"))
    assert os.path.isdir(os.path.join(run_dir, "lastepoch.ckpt"))
    assert os.path.isfile(os.path.join(run_dir, "bestloss.pkl"))  # legacy bridge
    log = open(os.path.join(run_dir, "train.log")).read()
    assert "TrainSet batchs:5" in log
    assert "steps:" in log and "time_cost:" in log  # reference line format
    assert "epoch:    0" in log and "epoch:    1" in log
    assert os.path.isfile(os.path.join(run_dir, "metrics.jsonl"))


@pytest.mark.isolated
def test_snapshot_epochs_writes_trend_checkpoints(trained_run):
    """snapshot_epochs=N saves bare params to snapshots/epoch_<E> — the
    per-checkpoint FID-trend source (scripts/fid_trend.py collect_points)."""
    import jax

    from ddim_cold_tpu.utils import checkpoint as ckpt

    _, cfg, result = trained_run
    snap = os.path.join(result.run_dir, "snapshots")
    assert sorted(os.listdir(snap)) == ["epoch_0", "epoch_1"]
    raw = ckpt.restore_checkpoint(os.path.join(snap, "epoch_0"))
    best = ckpt.restore_checkpoint(os.path.join(result.run_dir, "bestloss.ckpt"))
    assert jax.tree.structure(raw) == jax.tree.structure(best)  # bare params


@pytest.mark.isolated
def test_resume_continues(trained_run, synthetic_image_dir):
    from ddim_cold_tpu.train.trainer import run

    base, cfg, result = trained_run
    resume_cfg = load_config(
        _write_config(base, synthetic_image_dir, epoch=[0, 3],
                      resume=os.path.join(result.run_dir, "lastepoch.ckpt")),
        "exp")
    r2 = run(resume_cfg, base, log_every=2)
    # resumed at epoch 2 → one more epoch of 5 steps on top of the restored 10
    assert r2.steps == 15
    log = open(os.path.join(r2.run_dir, "train.log")).read()
    assert "resuming from epoch" in log
    assert "recovering best_loss" in log
    assert "epoch:    2" in log


def test_save_checkpoint_preserves_previous_on_failed_write(tmp_path, monkeypatch):
    """A crashed/failed re-save must leave the previous checkpoint intact —
    the old force=True-onto-destination path deleted it before writing."""
    from ddim_cold_tpu.utils import checkpoint as ckpt

    p = str(tmp_path / "last.ckpt")
    ckpt.save_checkpoint(p, {"a": np.arange(3)})

    import orbax.checkpoint as ocp

    monkeypatch.setattr(
        ocp.PyTreeCheckpointer, "save",
        lambda self, *a, **k: (_ for _ in ()).throw(RuntimeError("disk full")))
    with pytest.raises(RuntimeError, match="disk full"):
        ckpt.save_checkpoint(p, {"a": np.arange(4)})
    monkeypatch.undo()

    got = ckpt.restore_checkpoint(p, {"a": np.zeros(3, np.int64)})
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(3))


def test_checkpoint_swap_crash_recovers_from_old(tmp_path):
    """Crash between the two swap renames leaves only <path>.old — the owner
    (recover_swap, called by the trainer's resume path and by save itself)
    must move it back, never delete it as a leftover. restore stays
    read-only (a concurrent reader must not race a writer's swap)."""
    from ddim_cold_tpu.utils import checkpoint as ckpt

    p = str(tmp_path / "last.ckpt")
    ckpt.save_checkpoint(p, {"a": np.arange(3)})
    os.rename(p, p + ".old")  # simulate the crash window

    ckpt.recover_swap(p)
    got = ckpt.restore_checkpoint(p, {"a": np.zeros(3, np.int64)})
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(3))

    os.rename(p, p + ".old")
    ckpt.save_checkpoint(p, {"a": np.arange(4)})  # save-side heal + overwrite
    got = ckpt.restore_checkpoint(p, {"a": np.zeros(4, np.int64)})
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(4))


def _sigterm_when(log_path, needle, timeout_s=120):
    """Background thread: SIGTERM this process once `needle` appears in the
    train log. The needle must be a line the trainer only writes AFTER the
    graceful handler is installed ("steps:"/"epoch:"; "TrainSet" is logged
    before it — a signal there would kill the interpreter)."""
    import os as _os
    import signal
    import threading
    import time

    def watch():
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            try:
                if needle in open(log_path).read():
                    _os.kill(_os.getpid(), signal.SIGTERM)
                    return
            except OSError:
                pass
            time.sleep(0.25)

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    return t


@pytest.mark.isolated
def test_sigterm_checkpoints_and_exits(tmp_path, synthetic_image_dir):
    """SIGTERM mid-training → the loop finishes the step, evaluates, saves
    both checkpoints, and run() returns normally (a hard kill would lose the
    epoch AND can wedge a remote TPU's session claim)."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path)
    cfg = load_config(_write_config(base, synthetic_image_dir, epoch=[0, 200]),
                      "exp")
    log_path = os.path.join(base, "Saved_Models", cfg.run_name, "train.log")
    t = _sigterm_when(log_path, "steps:")
    result = run(cfg, base, log_every=1)  # returns instead of dying
    t.join()
    assert result.steps < 200 * 5  # stopped early
    assert np.isfinite(result.last_val_loss)
    log = open(log_path).read()
    assert "stop signal at step" in log
    assert os.path.isdir(os.path.join(result.run_dir, "lastepoch.ckpt"))


@pytest.mark.isolated
def test_sigterm_with_short_epochs_stops_at_epoch_end(tmp_path,
                                                      synthetic_image_dir):
    """A stop signal must take effect at the next EPOCH boundary even when
    epochs are shorter than log_every — observed on a 16-step/epoch run with
    log_every=100, where the in-epoch check (steps % log_every) never fired
    and the signal was ignored for ~6 epochs."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path)
    cfg = load_config(_write_config(base, synthetic_image_dir, epoch=[0, 50]),
                      "exp")
    log_path = os.path.join(base, "Saved_Models", cfg.run_name, "train.log")
    # signal lands during epoch 1 (after epoch 0's eval line, handler live);
    # log_every=1000 >> the 5 steps/epoch: only the epoch-end check can stop
    t = _sigterm_when(log_path, "epoch:")
    result = run(cfg, base, log_every=1000)
    t.join()
    # delivery-lag-immune invariant (the signal thread can lag epochs when
    # the single core hiccups, so a raw step bound flakes): once the trainer
    # LOGS the stop, it must train zero further epochs — the stop-line epoch
    # is the run's last. The regression this guards ran all 50 epochs.
    import re as _re

    log_text = open(log_path).read()
    stop = _re.search(r"stop signal at epoch\s+(\d+) end", log_text)
    assert stop, "no epoch-end stop line"
    last_epoch = int(_re.findall(r"epoch:\s*(\d+)\s+loss", log_text)[-1])
    assert last_epoch == int(stop.group(1)), "trained past the stop epoch"
    assert result.steps < 50 * 5, "stop signal ignored entirely"
    assert os.path.isdir(os.path.join(result.run_dir, "lastepoch.ckpt"))


def test_loss_decreases_over_training(synthetic_image_dir):
    """Overfit one fixed batch through the real train_step: loss must drop."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.data import ColdDownSampleDataset, ShardedLoader
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.ops.losses import smooth_l1
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    ds = ColdDownSampleDataset(synthetic_image_dir, imgSize=[64, 64])
    batch = next(iter(ShardedLoader(ds, 5, shuffle=False, drop_last=False,
                                    num_threads=1)))
    batch = tuple(jnp.asarray(b) for b in batch)
    model = DiffusionViT(img_size=(64, 64), patch_size=8, embed_dim=32, depth=1,
                         num_heads=2)
    state = create_train_state(model, jax.random.PRNGKey(0), lr=1e-3,
                               total_steps=200, sample_batch=batch)

    def eval_loss(params):
        pred = model.apply({"params": params}, batch[0], batch[2])
        return float(smooth_l1(pred, batch[1]))

    before = eval_loss(state.params)
    train_step = make_train_step(model)
    rng = jax.random.PRNGKey(1)
    loss_rec = jnp.float32(5.0)
    for _ in range(100):
        state, _, loss_rec = train_step(state, batch, rng, loss_rec)
    after = eval_loss(state.params)
    assert after < before * 0.7, (before, after)


def test_steps_per_dispatch_matches_sequential():
    """spd=4 over a stacked batch ≡ 4 sequential single-step calls passing
    the same rng: the scan body folds per-step keys off state.step, which
    advances inside the scan, so the math is step-identical."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2)
    r = np.random.RandomState(0)
    batches = [
        (jnp.asarray(r.randn(2, 16, 16, 3), jnp.float32),
         jnp.asarray(r.randn(2, 16, 16, 3), jnp.float32),
         jnp.asarray(r.randint(1, 7, size=(2,)), jnp.int32))
        for _ in range(4)
    ]
    mk_state = lambda: create_train_state(  # noqa: E731
        model, jax.random.PRNGKey(0), lr=1e-3, total_steps=100,
        sample_batch=batches[0])
    rng = jax.random.PRNGKey(1)

    seq_state, seq_rec = mk_state(), jnp.float32(5.0)
    one_step = make_train_step(model)
    seq_losses = []
    for b in batches:
        seq_state, loss, seq_rec = one_step(seq_state, b, rng, seq_rec)
        seq_losses.append(float(loss))

    multi_state, multi_rec = mk_state(), jnp.float32(5.0)
    multi_step = make_train_step(model, steps_per_dispatch=4)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    multi_state, mean_loss, multi_rec = multi_step(
        multi_state, stacked, rng, multi_rec)

    assert float(mean_loss) == pytest.approx(np.mean(seq_losses), rel=1e-5)
    assert float(multi_rec) == pytest.approx(float(seq_rec), rel=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        multi_state.params, seq_state.params)
    assert int(multi_state.step) == int(seq_state.step) == 4


@pytest.mark.isolated
def test_steps_per_dispatch_trainer_run(tmp_path, synthetic_image_dir):
    """The trainer wires config.steps_per_dispatch end to end: grouped
    loader, grouped sharding, boundary-crossing step logs, finite losses."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path)
    cfg = load_config(_write_config(base, synthetic_image_dir, epoch=[0, 1],
                                    steps_per_dispatch=2), "exp")
    assert cfg.steps_per_dispatch == 2
    # a bound reachable in whole dispatches is accepted and exact (the
    # refusal of one that is not: ..._rejects_indivisible_max_steps)
    result = run(cfg, base, log_every=2, max_steps=4)
    assert np.isfinite(result.best_loss)
    assert result.steps == 4
    log = os.path.join(base, "Saved_Models", cfg.run_name, "train.log")
    text = open(log).read()
    # 10-image folder @ batch 2 → 5 batches → 2 dispatches (tail dropped)
    # → 4 steps; log_every=2 boundaries at steps 2 and 4
    assert "steps:        2 " in text and "steps:        4 " in text


def test_steps_per_dispatch_composes_with_grad_accum_and_ema():
    """spd=2 × grad_accum=2 × ema_decay: the scanned dispatch must equal two
    sequential accumulated steps, EMA shadow included (nested lax.scans plus
    the optimizer-tail EMA update all advance correctly inside the outer
    scan)."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2)
    r = np.random.RandomState(1)
    batches = [
        (jnp.asarray(r.randn(4, 16, 16, 3), jnp.float32),
         jnp.asarray(r.randn(4, 16, 16, 3), jnp.float32),
         jnp.asarray(r.randint(1, 7, size=(4,)), jnp.int32))
        for _ in range(2)
    ]
    mk = lambda: create_train_state(  # noqa: E731
        model, jax.random.PRNGKey(0), lr=1e-3, total_steps=100,
        sample_batch=batches[0], ema_decay=0.9)
    rng = jax.random.PRNGKey(2)

    seq_state = mk()
    one = make_train_step(model, grad_accum=2, ema_decay=0.9)
    rec = jnp.float32(5.0)
    for b in batches:
        seq_state, _, rec = one(seq_state, b, rng, rec)

    multi_state = mk()
    multi = make_train_step(model, grad_accum=2, ema_decay=0.9,
                            steps_per_dispatch=2)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    multi_state, _, mrec = multi(multi_state, stacked, rng, jnp.float32(5.0))

    assert float(mrec) == pytest.approx(float(rec), rel=1e-5)
    for tree_a, tree_b in ((multi_state.params, seq_state.params),
                           (multi_state.ema_params, seq_state.ema_params)):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
            tree_a, tree_b)
    assert int(multi_state.step) == int(seq_state.step) == 2


def test_run_refuses_more_devices_than_visible(tmp_path, synthetic_image_dir):
    """num_gpus above the visible device count is an error, like an explicit
    mesh that does not fit — training on fewer devices than asked for is a
    different run (global batch, lr), not a degraded one."""
    import jax

    from ddim_cold_tpu.train import trainer

    n = len(jax.devices()) + 1
    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    num_gpus=n), "exp")
    with pytest.raises(ValueError, match=f"num_gpus {n} needs {n} devices, "
                                         f"only {n - 1} visible"):
        trainer.run(cfg, str(tmp_path))


def test_flash_config_trains_without_attention_dropout(tmp_path,
                                                        synthetic_image_dir):
    """``use_flash`` is a promise that the kernel runs: build_model zeroes the
    attention-dropout the kernel cannot apply, and a model that still carries
    it refuses to train rather than quietly attending dense."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.trainer import build_model

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    use_flash=True), "exp")
    assert build_model(cfg).attn_drop_rate == 0.0
    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir), "exp")
    assert build_model(cfg).attn_drop_rate == 0.1  # dense keeps the reference's

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2, use_flash=True)
    x, t = jnp.zeros((2, 16, 16, 3)), jnp.zeros((2,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x, t)["params"]
    model.apply({"params": params}, x, t)  # inference: dropout inactive
    with pytest.raises(ValueError, match="cannot apply attention-dropout"):
        model.apply({"params": params}, x, t, deterministic=False,
                    rngs={"dropout": jax.random.PRNGKey(1)})


def test_flash_train_step_splits_over_a_data_mesh():
    """Data-parallel training with the flash kernels: under the trainer's
    ambient mesh every device launches the kernels on its own rows
    (ops/flash_attention.per_device — a jit over a mesh cannot partition a
    Mosaic kernel itself), and the step matches the one-device step."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.parallel import (ambient, make_mesh, shard_batch,
                                        shard_train_state)
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=4, embed_dim=32,
                         depth=1, num_heads=2, use_flash=True,
                         attn_drop_rate=0.0, drop_rate=0.0, drop_path_rate=0.0)
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(8, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randn(8, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randint(1, 7, size=(8,)), jnp.int32))

    def one_step(mesh):
        with ambient(mesh):
            placed = batch if mesh is None else shard_batch(batch, mesh)
            state = create_train_state(model, jax.random.PRNGKey(0), 1e-3, 10,
                                       placed)
            if mesh is not None:
                state = shard_train_state(state, mesh)
            step = make_train_step(model)
            text = step.lower(state, placed, jax.random.PRNGKey(1),
                              jnp.float32(5.0)).as_text()
            state, loss, _ = step(state, placed, jax.random.PRNGKey(1),
                                  jnp.float32(5.0))
            return float(loss), jax.device_get(state.params), text

    loss_1, params_1, _ = one_step(None)
    loss_4, params_4, text = one_step(make_mesh({"data": 4},
                                                devices=jax.devices()[:4]))
    assert "shard_map" in text or "manual" in text.lower()
    assert loss_4 == pytest.approx(loss_1, rel=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4,
                                                         atol=2e-6),
                 params_4, params_1)


def test_steps_per_dispatch_validation(tmp_path, synthetic_image_dir):
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  steps_per_dispatch=0), "exp")
    from ddim_cold_tpu.train.step import make_train_step

    from ddim_cold_tpu.models import DiffusionViT

    with pytest.raises(ValueError, match="steps_per_dispatch"):
        make_train_step(DiffusionViT(img_size=(16, 16), patch_size=8,
                                     embed_dim=32, depth=1, num_heads=2),
                        steps_per_dispatch=0)


def test_checkpoint_converter_roundtrip():
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.utils import checkpoint as ckpt

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
                         num_heads=4)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)),
                        jnp.zeros((1,), jnp.int32))["params"]
    sd = ckpt.torch_state_dict_from_flax(params, patch_size=8)
    # torch-side key surface matches the reference state_dict naming
    assert "blocks.0.attn.qkv.weight" in sd
    assert "patch_embed.proj.weight" in sd and sd["patch_embed.proj.weight"].shape == (32, 3, 8, 8)
    back = ckpt.flax_from_torch_state_dict(sd, patch_size=8)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
                 params, back)


def test_checkpoint_converter_sincos_roundtrip():
    """use_sincos_pos models have no pos_embed param; the converter must
    tolerate its absence in both directions (regression: KeyError on export)."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.utils import checkpoint as ckpt

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32, depth=1,
                         num_heads=2, use_sincos_pos=True)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)),
                        jnp.zeros((1,), jnp.int32))["params"]
    assert "pos_embed" not in params
    sd = ckpt.torch_state_dict_from_flax(params, patch_size=8)
    assert "pos_embed" not in sd
    back = ckpt.flax_from_torch_state_dict(sd, patch_size=8)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
                 params, back)


def test_torch_pkl_file_roundtrip(tmp_path):
    torch = pytest.importorskip("torch")
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.utils import checkpoint as ckpt

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32, depth=1,
                         num_heads=2)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 16, 16, 3), jnp.float32)
    t = jnp.array([5], jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x, t)["params"]
    pkl = str(tmp_path / "w.pkl")
    ckpt.save_torch_pkl(params, pkl, patch_size=8)
    # a torch user can load it...
    sd = torch.load(pkl, weights_only=False)
    assert all(hasattr(v, "numpy") for v in sd.values())
    # ...and we can load it back with identical model behavior
    params2 = ckpt.load_torch_pkl(pkl, patch_size=8)
    out1 = model.apply({"params": params}, x, t)
    out2 = model.apply({"params": params2}, x, t)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_smooth_l1_matches_torch():
    torch = pytest.importorskip("torch")
    import jax.numpy as jnp

    from ddim_cold_tpu.ops.losses import smooth_l1

    rng = np.random.RandomState(0)
    a = rng.randn(4, 8, 8, 3).astype(np.float32) * 2
    b = rng.randn(4, 8, 8, 3).astype(np.float32)
    want = torch.nn.functional.smooth_l1_loss(torch.from_numpy(a), torch.from_numpy(b)).item()
    got = float(smooth_l1(jnp.asarray(a), jnp.asarray(b)))
    assert got == pytest.approx(want, rel=1e-6)


@pytest.mark.isolated
def test_profile_steps_writes_trace(tmp_path, synthetic_image_dir):
    """profile_steps traces the first N steps into <run_dir>/trace and the
    run completes normally (reference had only wall-clock prints)."""
    from ddim_cold_tpu.config import ExperimentConfig
    from ddim_cold_tpu.train.trainer import run

    cfg = ExperimentConfig(
        exp_name="prof", framework="trace", batch_size=2, epoch=(0, 1),
        base_lr=0.005, data_storage=(synthetic_image_dir, synthetic_image_dir),
        image_size=(16, 16), patch_size=8, embed_dim=32, depth=1, head=2,
        profile_steps=2,
    )
    result = run(cfg, str(tmp_path), max_steps=3)
    assert np.isfinite(result.best_loss)
    trace_dir = os.path.join(result.run_dir, "trace")
    assert os.path.isdir(trace_dir)
    assert any(f for _, _, fs in os.walk(trace_dir) for f in fs), "empty trace"
    # beside it, what reduces that timeline by layer: the step's scope map
    with open(os.path.join(result.run_dir, "scopes.json")) as f:
        doc = json.load(f)
    assert [p["name"] for p in doc["programs"]] == ["train/step"]
    assert {"attention", "mlp", "optimizer", "outside"} <= {
        e["layer"] for e in doc["map"].values()}


@pytest.mark.isolated
def test_steps_per_dispatch_rejects_indivisible_max_steps(tmp_path,
                                                          synthetic_image_dir):
    """max_steps not a multiple of steps_per_dispatch fails loud (ADVICE r4):
    the loop advances in whole spd-dispatches, so a non-divisible bound would
    silently run up to spd-1 optimizer steps past max_steps — and the cosine
    schedule/checkpoint counters would include them."""
    from ddim_cold_tpu.config import ExperimentConfig
    from ddim_cold_tpu.train.trainer import run

    cfg = ExperimentConfig(
        exp_name="spd_guard", framework="t", batch_size=2, epoch=(0, 1),
        base_lr=0.005, data_storage=(synthetic_image_dir, synthetic_image_dir),
        image_size=(16, 16), patch_size=8, embed_dim=32, depth=1, head=2,
        steps_per_dispatch=2,
    )
    with pytest.raises(ValueError, match="not reachable in whole dispatches"):
        run(cfg, str(tmp_path), max_steps=3)
    # the divisible bound (max_steps=4 stops at precisely 4) is asserted by
    # test_steps_per_dispatch_trainer_run, which is that run


def test_ema_step_math():
    """ema_decay>0: the shadow follows ema ← d·ema + (1−d)·p exactly, seeded
    from the init params; off (0): ema_params stays None and the step is the
    plain parity path."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=16,
                         depth=1, num_heads=2, total_steps=8)
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray([1, 2], jnp.int32))
    d = 0.5
    state = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10, batch,
                               ema_decay=d)
    p0 = jax.tree.map(np.asarray, state.params)
    step = make_train_step(model, ema_decay=d)
    state, _, _ = step(state, batch, jax.random.PRNGKey(1), jnp.float32(5.0))
    p1 = jax.tree.map(np.asarray, state.params)
    want = jax.tree.map(lambda e, p: d * e + (1 - d) * p, p0, p1)
    got = jax.tree.map(np.asarray, state.ema_params)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(w, g, rtol=1e-6)

    off = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10, batch)
    assert off.ema_params is None
    off2, _, _ = make_train_step(model)(off, batch, jax.random.PRNGKey(1),
                                        jnp.float32(5.0))
    assert off2.ema_params is None


@pytest.mark.isolated
def test_ema_trainer_checkpoints_and_resume(tmp_path, synthetic_image_dir):
    """ema_decay in the yaml: bestloss_ema.ckpt appears, lastepoch carries
    the shadow, resume restores it, and resuming an ema-less checkpoint
    re-seeds instead of crashing."""
    import jax

    from ddim_cold_tpu.train.trainer import run
    from ddim_cold_tpu.utils import checkpoint as ckpt

    base = str(tmp_path)
    cfg = load_config(_write_config(base, synthetic_image_dir,
                                    ema_decay=0.9, snapshot_epochs=1), "exp")
    result = run(cfg, base, log_every=2)
    run_dir = result.run_dir
    # EMA snapshots land beside the raw ones; the FID trend's strict
    # epoch_(\d+) match must keep ignoring them
    snaps = sorted(os.listdir(os.path.join(run_dir, "snapshots")))
    assert snaps == ["epoch_0", "epoch_0_ema", "epoch_1", "epoch_1_ema"]
    assert os.path.isdir(os.path.join(run_dir, "bestloss_ema.ckpt"))
    assert os.path.isfile(os.path.join(run_dir, "bestloss_ema.pkl"))
    best = ckpt.restore_checkpoint(os.path.join(run_dir, "bestloss.ckpt"))
    ema = ckpt.restore_checkpoint(os.path.join(run_dir, "bestloss_ema.ckpt"))
    assert jax.tree.structure(ema) == jax.tree.structure(best)
    # the shadow trails the live params — identical trees would mean the
    # decay never applied (update magnitudes make exact equality impossible)
    diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(jax.tree.leaves(ema), jax.tree.leaves(best))]
    assert max(diffs) > 0

    resume_cfg = load_config(
        _write_config(base, synthetic_image_dir, epoch=[0, 3], ema_decay=0.9,
                      resume=os.path.join(run_dir, "lastepoch.ckpt")), "exp")
    r2 = run(resume_cfg, base, log_every=2)
    assert r2.steps == 15
    assert "re-seeding" not in open(os.path.join(r2.run_dir, "train.log")).read()


@pytest.mark.isolated
def test_ema_resume_from_pre_ema_checkpoint(tmp_path, synthetic_image_dir):
    """Turning ema_decay on mid-run (resume from a checkpoint written without
    it) re-seeds the shadow from the restored params with a log note. Own
    run dir: the shared trained_run fixture's checkpoint is advanced by
    test_resume_continues, which would leave this resume zero epochs."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path)
    r1 = run(load_config(_write_config(base, synthetic_image_dir,
                                       epoch=[0, 1]), "exp"), base, log_every=2)
    resume_cfg = load_config(
        _write_config(base, synthetic_image_dir, epoch=[0, 2], ema_decay=0.9,
                      resume=os.path.join(r1.run_dir, "lastepoch.ckpt")),
        "exp")
    r2 = run(resume_cfg, base, log_every=2)
    assert r2.steps == 10
    log = open(os.path.join(r2.run_dir, "train.log")).read()
    assert "no ema_params" in log and "re-seeding" in log
    # the shadow is carried forward: every lastepoch written after the
    # re-seed includes it
    from ddim_cold_tpu.utils import checkpoint as ckpt2

    last = ckpt2.restore_checkpoint(os.path.join(r2.run_dir, "lastepoch.ckpt"))
    assert "ema_params" in last


@pytest.mark.isolated
def test_ema_off_resume_from_ema_checkpoint(tmp_path, synthetic_image_dir):
    """The reverse toggle: a checkpoint written WITH ema_params resumes
    cleanly under ema_decay=0 (the shadow is dropped with a log note) —
    orbax is strict about the extra on-disk key, so this needs the flipped
    retry."""
    from ddim_cold_tpu.train.trainer import run
    from ddim_cold_tpu.utils import checkpoint as ckpt2

    base = str(tmp_path)
    cfg = load_config(_write_config(base, synthetic_image_dir,
                                    ema_decay=0.9), "exp")
    result = run(cfg, base, log_every=2)
    resume_cfg = load_config(
        _write_config(base, synthetic_image_dir, epoch=[0, 3],
                      resume=os.path.join(result.run_dir, "lastepoch.ckpt")),
        "exp")
    r2 = run(resume_cfg, base, log_every=2)
    assert r2.steps == 15
    log = open(os.path.join(r2.run_dir, "train.log")).read()
    assert "dropping the shadow" in log
    last = ckpt2.restore_checkpoint(os.path.join(r2.run_dir, "lastepoch.ckpt"))
    assert "ema_params" not in last


@pytest.mark.isolated
def test_warm_start_shape_mismatch_fails_loudly(tmp_path, synthetic_image_dir):
    """A stale `initializing` pkl from a different model config must raise a
    clear error naming the mismatched leaves — not surface later as an opaque
    jit shape error (fatal for unattended runs; observed with a leftover
    rehearsal pkl under the real run's warm-start name)."""
    import jax

    from ddim_cold_tpu.train.trainer import run
    from ddim_cold_tpu.utils import checkpoint as ckpt2

    pytest.importorskip("torch")
    base = str(tmp_path)
    # write a WRONG-config pkl under the warm-start name (embed 16 vs 32)
    from ddim_cold_tpu.models import DiffusionViT

    wrong = DiffusionViT(img_size=(64, 64), patch_size=8, embed_dim=16,
                         depth=1, num_heads=2)
    params = wrong.init(jax.random.PRNGKey(0),
                        np.zeros((1, 64, 64, 3), np.float32),
                        np.zeros((1,), np.int32))["params"]
    os.makedirs(os.path.join(base, "Saved_Models"), exist_ok=True)
    ckpt2.save_torch_pkl(params, os.path.join(base, "Saved_Models", "warm.pkl"), 8)
    cfg = load_config(_write_config(base, synthetic_image_dir,
                                    initializing="warm.pkl"), "exp")
    with pytest.raises(ValueError, match="does not match this model config"):
        run(cfg, base, log_every=2)
    # same guard on the checkpoint-DIRECTORY branch (orbax restore returns
    # the on-disk shapes when they differ from the template — measured)
    ckpt2.save_checkpoint(os.path.join(base, "Saved_Models", "warm.ckpt"), params)
    cfg = load_config(_write_config(base, synthetic_image_dir,
                                    initializing="warm.ckpt"), "exp")
    with pytest.raises(ValueError, match="does not match this model config"):
        run(cfg, base, log_every=2)


def test_ema_decay_range_validated(tmp_path, synthetic_image_dir):
    """Out-of-range ema_decay (a 9.99-for-0.999 typo diverges the shadow to
    NaN; 1.0 freezes it at init) fails loudly at config load."""
    for bad in (9.99, 1.0, -0.1):
        path = _write_config(str(tmp_path), synthetic_image_dir, ema_decay=bad)
        with pytest.raises(ValueError, match="ema_decay"):
            load_config(path, "exp")


@pytest.mark.isolated
def test_resume_shape_mismatch_fails_loudly(tmp_path, synthetic_image_dir):
    """`resume:` pointing at a different-config run's lastepoch.ckpt raises
    the clear mismatch error (same guard as warm-start), not an opaque jit
    shape error mid-run."""
    from ddim_cold_tpu.train.trainer import run

    base = str(tmp_path)
    small = load_config(_write_config(base, synthetic_image_dir,
                                      embed_dim=16, epoch=[0, 1]), "exp")
    r1 = run(small, base, log_every=2)
    big = load_config(
        _write_config(base, synthetic_image_dir, embed_dim=32, epoch=[0, 2],
                      resume=os.path.join(r1.run_dir, "lastepoch.ckpt")),
        "exp")
    with pytest.raises(ValueError, match="does not match this model config"):
        run(big, base, log_every=2)


def test_grad_accum_matches_unaccumulated_step():
    """grad_accum=4 with dropout off is the same math as one full-batch step
    (smooth-L1 is a mean; mean of equal-slice grads == full-batch grad), and
    composes with the EMA shadow."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=16,
                         depth=1, num_heads=2, total_steps=8, drop_rate=0.0,
                         attn_drop_rate=0.0, drop_path_rate=0.0)
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(8, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randn(8, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randint(1, 7, size=(8,)), jnp.int32))

    def one(accum):
        st = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10, batch,
                                ema_decay=0.5)
        step = make_train_step(model, ema_decay=0.5, grad_accum=accum)
        st, loss, _ = step(st, batch, jax.random.PRNGKey(1), jnp.float32(5.0))
        return st, float(loss)

    s1, l1 = one(1)
    s4, l4 = one(4)
    # tolerances: mean-of-slice-means vs full mean differ only in float
    # summation order (measured max |Δ| ≈ 1.4e-7 on these shapes)
    assert l1 == pytest.approx(l4, rel=1e-5)
    for tree1, tree4 in ((s1.params, s4.params),
                         (s1.ema_params, s4.ema_params)):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
            tree1, tree4)


def test_grad_accum_config_validation(tmp_path, synthetic_image_dir):
    """grad_accum < 1 fails at config load; grad_accum with a pipe mesh is
    rejected (the pipeline has its own microbatching). Not `isolated`: run()
    refuses with the mesh and the module built, before any loader thread,
    jitted step or checkpoint exists."""
    with pytest.raises(ValueError, match="grad_accum"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  grad_accum=0), "exp")
    from ddim_cold_tpu.train.trainer import run

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    grad_accum=2, batch_size=8,
                                    mesh={"data": 2, "pipe": 2}), "exp")
    with pytest.raises(ValueError, match="grad_accum composes"):
        run(cfg, str(tmp_path), log_every=2)


@pytest.mark.isolated
def test_grad_accum_trainer_end_to_end(tmp_path, synthetic_image_dir):
    """A short run with grad_accum=2 trains, logs, and checkpoints normally."""
    from ddim_cold_tpu.train.trainer import run

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    grad_accum=2, epoch=[0, 1]), "exp")
    result = run(cfg, str(tmp_path), log_every=2)
    assert result.steps == 5 and np.isfinite(result.last_val_loss)
    assert os.path.isdir(os.path.join(result.run_dir, "lastepoch.ckpt"))


def test_make_train_step_validates_ema_inputs():
    """Direct API callers can't bypass the config-layer guards: bad ema_decay
    raises at construction; ema_decay>0 against a shadow-less state raises at
    trace time instead of silently training without EMA."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=16,
                         depth=1, num_heads=2, total_steps=8)
    with pytest.raises(ValueError, match="ema_decay"):
        make_train_step(model, ema_decay=1.0)
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray([1, 2], jnp.int32))
    st = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10, batch)
    with pytest.raises(ValueError, match="no ema_params"):
        make_train_step(model, ema_decay=0.9)(
            st, batch, jax.random.PRNGKey(1), jnp.float32(5.0))
