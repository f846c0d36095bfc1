"""Device-side corruption data path (ops/degrade.make_cold_prepare +
ShardedLoader raw mode + train/step prepare hook + device_prefetch).

The host ships ``(base, t)`` and the jitted step rebuilds the reference
contract ``(D(x,t), target, t)`` on device; these tests pin that the rebuilt
batch is bit-identical to the host/C++ pipeline (diffusion_loader.py:79-97
semantics) and that the trainer trains the same under either path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ddim_cold_tpu.data import ColdDownSampleDataset, DiffusionDataset, ShardedLoader
from ddim_cold_tpu.data.loader import device_prefetch
from ddim_cold_tpu.ops import degrade


@pytest.fixture(scope="module", params=["chain", "direct"])
def cold_sets(request, synthetic_image_dir):
    """(host-path dataset, raw-path dataset) over the same files/seed."""
    mk = lambda: ColdDownSampleDataset(  # noqa: E731
        synthetic_image_dir, imgSize=(64, 64), target_mode=request.param)
    return mk(), mk(), request.param


def test_raw_batch_contract(cold_sets):
    host_ds, raw_ds, _ = cold_sets
    idxs = np.arange(8)
    base, ts = raw_ds.get_raw_batch(idxs, num_threads=2)
    assert base.shape == (8, 64, 64, 3) and base.dtype == np.float32
    assert ts.shape == (8,) and ts.dtype == np.int32
    assert (1 <= ts).all() and (ts <= host_ds.max_step).all()
    # same per-(seed, epoch, index) t stream as the host path
    _, _, host_ts = host_ds.get_batch(idxs, num_threads=2)
    np.testing.assert_array_equal(ts, host_ts)
    # bases are the clean decoded images
    np.testing.assert_array_equal(base[3], raw_ds._base(3))


def test_prepare_rebuilds_host_batch_bitexact(cold_sets):
    host_ds, raw_ds, mode = cold_sets
    idxs = np.arange(10)
    noisy, target, ts = host_ds.get_batch(idxs, num_threads=2)
    base, raw_ts = raw_ds.get_raw_batch(idxs, num_threads=2)
    prepare = degrade.make_cold_prepare(
        size=64, max_step=host_ds.max_step, chain=(mode == "chain"))
    d_noisy, d_target, d_ts = prepare(
        (jnp.asarray(base), jnp.asarray(raw_ts)), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(d_noisy), noisy)
    np.testing.assert_array_equal(np.asarray(d_target), target)
    np.testing.assert_array_equal(np.asarray(d_ts), ts)


def test_cold_prepare_pins_batch_sharding_under_mesh():
    """Under a dp×tp×sp mesh the degrade gathers must stay batch-sharded —
    left to the partitioner they can land W-sharded and trigger XLA's
    "Involuntary full rematerialization" replicate-all fallback on the
    reshard into the attention layout."""
    from ddim_cold_tpu.parallel import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh({"data": 2, "model": 2, "seq": 2})
    prepare = degrade.make_cold_prepare(size=16, max_step=4, chain=True,
                                        mesh=mesh)
    base = jnp.zeros((8, 16, 16, 3), jnp.uint8)
    t = jnp.ones((8,), jnp.int32)
    noisy, target, _ = jax.jit(
        lambda b: prepare(b, jax.random.PRNGKey(0)))((base, t))
    for arr in (noisy, target):
        spec = arr.sharding.spec
        assert spec and spec[0] == "data", spec
        assert all(s is None for s in spec[1:]), spec


def test_uint8_base_normalizes_bitexact(rng):
    """uint8-shipped bases must normalize to the exact host float pipeline
    (÷255 then ·2−1, datasets._load_base order)."""
    u8 = rng.randint(0, 256, size=(4, 16, 16, 3)).astype(np.uint8)
    want = (u8.astype(np.float32) / 255.0) * 2.0 - 1.0
    got = np.asarray(degrade.normalize_base(jnp.asarray(u8)))
    np.testing.assert_array_equal(got, want)
    # float input passes through untouched
    f = want[:2]
    np.testing.assert_array_equal(np.asarray(degrade.normalize_base(jnp.asarray(f))), f)


@pytest.fixture(scope="module")
def exact_size_image_dir(tmp_path_factory):
    """jpgs whose native size IS the dataset img_size (64×64) — the uint8
    ship-raw-bytes fast path (no resize anywhere)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("exact64_jpgs")
    rs = np.random.RandomState(7)
    for i in range(8):
        arr = rs.randint(0, 255, size=(64, 64, 3), dtype=np.uint8)
        Image.fromarray(arr).save(root / f"{i}.jpg")
    return str(root)


def test_raw_batch_ships_uint8_when_exact_size(exact_size_image_dir):
    """Identity-resize datasets ship raw uint8 bytes (4× less transfer), and
    the in-jit normalize+degrade rebuilds the host batch bit-exactly."""
    mk = lambda: ColdDownSampleDataset(  # noqa: E731
        exact_size_image_dir, imgSize=(64, 64), target_mode="chain")
    raw_ds, host_ds = mk(), mk()
    idxs = np.arange(8)
    base, ts = raw_ds.get_raw_batch(idxs, num_threads=2)
    assert base.dtype == np.uint8, "exact-size files must ship as uint8"
    noisy, target, host_ts = host_ds.get_batch(idxs, num_threads=2)
    np.testing.assert_array_equal(ts, host_ts)
    prepare = degrade.make_cold_prepare(size=64, max_step=6, chain=True)
    d_noisy, d_target, _ = prepare(
        (jnp.asarray(base), jnp.asarray(ts)), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(d_noisy), noisy)
    np.testing.assert_array_equal(np.asarray(d_target), target)
    # the float32 view through the same cache matches the PIL pipeline
    from ddim_cold_tpu.data.datasets import _load_base
    import os

    want = _load_base(os.path.join(exact_size_image_dir,
                                   sorted(os.listdir(exact_size_image_dir))[0]),
                      (64, 64), use_native=False)
    np.testing.assert_array_equal(raw_ds._base(0), want)


def test_raw_dtype_stable_for_mixed_size_dataset(tmp_path):
    """One off-size file pins the WHOLE dataset to float32 — batch dtype must
    not flip with batch composition (jit retraces; multi-host SPMD hosts must
    agree on the global array dtype)."""
    from PIL import Image

    rs = np.random.RandomState(3)
    for i in range(6):
        Image.fromarray(rs.randint(0, 255, (64, 64, 3), np.uint8)).save(
            tmp_path / f"exact_{i}.jpg")
    Image.fromarray(rs.randint(0, 255, (65, 64, 3), np.uint8)).save(
        tmp_path / "odd.jpg")
    ds = ColdDownSampleDataset(str(tmp_path), imgSize=(64, 64))
    assert not ds._uniform_u8
    # a batch containing ONLY exact-size files still ships float32
    base, _ = ds.get_raw_batch([0, 1, 2], num_threads=1)
    assert base.dtype == np.float32


def test_raw_dtype_drift_raises_not_silent_flip(tmp_path):
    """A file mutated on disk AFTER the header probe pinned the dataset uint8
    must raise, not silently ship a float32 batch (jit retrace; multi-host
    global-dtype divergence)."""
    from PIL import Image

    from ddim_cold_tpu.data import native

    if not native.available():
        pytest.skip("uint8 pinning requires the native decoder")
    rs = np.random.RandomState(5)
    for i in range(4):
        Image.fromarray(rs.randint(0, 255, (64, 64, 3), np.uint8)).save(
            tmp_path / f"img_{i}.jpg")
    ds = ColdDownSampleDataset(str(tmp_path), imgSize=(64, 64),
                               target_mode="chain")
    assert ds._uniform_u8
    Image.fromarray(rs.randint(0, 255, (80, 80, 3), np.uint8)).save(
        tmp_path / "img_1.jpg")  # now needs a resize → float32 decode path
    with pytest.raises(RuntimeError, match="pinned uint8"):
        ds.get_raw_batch([0, 1, 2], num_threads=1)


def test_native_decode_batch_parity(exact_size_image_dir):
    """Raw C++ u8 decode == PIL bytes; size-mismatched files flag failed."""
    import os

    from PIL import Image

    from ddim_cold_tpu.data import native

    if not native.available():
        pytest.skip("native library unavailable")
    paths = [os.path.join(exact_size_image_dir, n)
             for n in sorted(os.listdir(exact_size_image_dir))]
    res = native.decode_batch(paths, (64, 64), num_threads=2)
    assert res is not None
    u8, failed = res
    assert not failed.any()
    for j, p in enumerate(paths[:3]):
        np.testing.assert_array_equal(u8[j], np.asarray(Image.open(p).convert("RGB")))
    # wrong expected size → failed mask, no crash
    res = native.decode_batch(paths[:2], (32, 32), num_threads=1)
    assert res is not None and res[1].all()


def test_loader_raw_mode_yields_pairs(cold_sets):
    _, raw_ds, _ = cold_sets
    loader = ShardedLoader(raw_ds, 4, shuffle=False, drop_last=True, raw=True)
    batches = list(loader)
    assert len(batches) == len(raw_ds) // 4
    for base, ts in batches:
        assert base.shape == (4, 64, 64, 3) and ts.shape == (4,)


def test_gaussian_raw_batch_and_prepare(synthetic_image_dir):
    """Gaussian raw path: same t stream as the host pipeline, clean x₀ bases,
    and the in-jit forward noising implements √ᾱ·x₀ + √(1−ᾱ)·ε with
    device-drawn unit-normal ε (deterministic per rng)."""
    ds = DiffusionDataset(synthetic_image_dir, imgSize=(32, 32), max_step=2000)
    idxs = np.arange(10)
    base, ts = ds.get_raw_batch(idxs, num_threads=2)
    noisy_h, x0_h, ts_h = ds.get_batch(idxs, num_threads=2)
    np.testing.assert_array_equal(ts, ts_h)
    np.testing.assert_array_equal(base, x0_h)

    prepare = degrade.make_gaussian_prepare(2000)
    rng = jax.random.PRNGKey(5)
    noisy, target, t_out = prepare((jnp.asarray(base), jnp.asarray(ts)), rng)
    np.testing.assert_array_equal(np.asarray(target), base)
    np.testing.assert_array_equal(np.asarray(t_out), ts)
    # recover ε and check it is the exact device-normal draw
    alpha = 1.0 - np.sqrt((ts.astype(np.float32) + 1.0) / 2000.0)
    alpha = alpha[:, None, None, None]
    eps = (np.asarray(noisy) - np.sqrt(alpha) * base) / np.sqrt(1.0 - alpha)
    want_eps = np.asarray(jax.random.normal(rng, base.shape, jnp.float32))
    np.testing.assert_allclose(eps, want_eps, atol=1e-4)
    # deterministic: same rng → same batch
    noisy2, _, _ = prepare((jnp.asarray(base), jnp.asarray(ts)), rng)
    np.testing.assert_array_equal(np.asarray(noisy), np.asarray(noisy2))


@pytest.mark.isolated
def test_trainer_gaussian_device_path_smoke(tmp_path, synthetic_image_dir):
    """Gaussian + device_degrade trains (device-noised train loader) while
    the val loader stays on the deterministic host path."""
    from ddim_cold_tpu.config import ExperimentConfig
    from ddim_cold_tpu.train.trainer import run

    cfg = ExperimentConfig(
        exp_name="g", framework="dd", batch_size=4, epoch=(0, 1),
        base_lr=0.005, data_storage=(synthetic_image_dir, synthetic_image_dir),
        image_size=(32, 32), patch_size=8, embed_dim=32, depth=2, head=2,
        num_devices=1, dataset="gaussian", device_degrade=True,
    )
    result = run(cfg, str(tmp_path), max_steps=3)
    assert np.isfinite(result.best_loss)


def test_loader_raw_requires_capable_dataset(synthetic_image_dir):
    class NoRaw:
        def __len__(self):
            return 4

    with pytest.raises(ValueError, match="get_raw_batch"):
        ShardedLoader(NoRaw(), 4, shuffle=False, raw=True)


def test_train_step_equivalent_under_device_degrade(cold_sets):
    """One optimizer step from identical inits must produce the same loss and
    (numerically) the same params whether corruption ran on host or device."""
    from ddim_cold_tpu.models import DiffusionViT
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    host_ds, raw_ds, mode = cold_sets
    model = DiffusionViT(img_size=(64, 64), patch_size=8, embed_dim=32,
                         depth=2, num_heads=2)
    idxs = np.arange(8)
    host_batch = tuple(map(jnp.asarray, host_ds.get_batch(idxs, num_threads=2)))
    raw_batch = tuple(map(jnp.asarray, raw_ds.get_raw_batch(idxs, num_threads=2)))
    prepare = degrade.make_cold_prepare(
        size=64, max_step=host_ds.max_step, chain=(mode == "chain"))

    def one_step(step_fn, batch):
        state = create_train_state(model, jax.random.PRNGKey(0), lr=1e-3,
                                   total_steps=100, sample_batch=host_batch)
        state, loss, _ = step_fn(state, batch, jax.random.PRNGKey(7),
                                 jnp.float32(5.0))
        return state, float(loss)

    s_host, l_host = one_step(make_train_step(model), host_batch)
    s_dev, l_dev = one_step(make_train_step(model, prepare=prepare), raw_batch)
    np.testing.assert_allclose(l_dev, l_host, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s_host.params), jax.tree.leaves(s_dev.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_device_prefetch_order_and_abandon():
    placed = []

    def place(x):
        placed.append(x)
        return x * 10

    out = list(device_prefetch(range(6), place, depth=2))
    assert out == [0, 10, 20, 30, 40, 50]

    # abandoning the generator stops the producer promptly
    gen = device_prefetch(range(1000), place, depth=2)
    assert next(gen) == 0
    gen.close()
    assert len(placed) < 6 + 20  # bounded work after close


def test_device_prefetch_propagates_errors():
    def place(x):
        if x == 3:
            raise RuntimeError("boom")
        return x

    gen = device_prefetch(range(6), place, depth=2)
    got = [next(gen), next(gen), next(gen)]
    assert got == [0, 1, 2]
    with pytest.raises(RuntimeError, match="boom"):
        list(gen)


@pytest.mark.isolated
def test_trainer_device_path_matches_host_path(tmp_path, synthetic_image_dir):
    """Two 3-step trainer runs — host corruption vs device corruption — land
    on the same loss trajectory, and the async saver leaves both checkpoints."""
    import os

    from ddim_cold_tpu.config import ExperimentConfig
    from ddim_cold_tpu.train.trainer import run

    def go(tag, device_degrade):
        cfg = ExperimentConfig(
            exp_name=tag, framework="dd", batch_size=4, epoch=(0, 1),
            base_lr=0.005, data_storage=(synthetic_image_dir, synthetic_image_dir),
            image_size=(32, 32), patch_size=8, embed_dim=32, depth=2, head=2,
            num_devices=1, device_degrade=device_degrade,
        )
        return run(cfg, str(tmp_path / tag), max_steps=3)

    r_host = go("host", False)
    r_dev = go("dev", True)
    np.testing.assert_allclose(r_dev.last_val_loss, r_host.last_val_loss, rtol=1e-5)
    np.testing.assert_allclose(r_dev.best_loss, r_host.best_loss, rtol=1e-5)
    for name in ("bestloss.ckpt", "lastepoch.ckpt"):
        assert os.path.isdir(os.path.join(r_dev.run_dir, name)), name
