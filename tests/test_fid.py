"""FID subsystem: Fréchet math vs closed forms/scipy, streaming stats vs
numpy, InceptionV3 forward + torch-layout weight conversion."""

import os

import numpy as np
import pytest

from ddim_cold_tpu.eval import fid
from ddim_cold_tpu.eval import inception

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_frechet_identical_is_zero(rng):
    x = rng.randn(500, 8)
    mu, sigma = x.mean(0), np.cov(x, rowvar=False)
    assert abs(fid.frechet_distance(mu, sigma, mu, sigma)) < 1e-8


def test_frechet_diagonal_closed_form():
    """For commuting (diagonal) covariances the distance is
    ‖Δμ‖² + Σᵢ (√s1ᵢ − √s2ᵢ)²."""
    mu1, mu2 = np.array([0.0, 0.0]), np.array([3.0, 4.0])
    s1, s2 = np.diag([1.0, 4.0]), np.diag([9.0, 1.0])
    want = 25.0 + (1 - 3) ** 2 + (2 - 1) ** 2
    assert abs(fid.frechet_distance(mu1, s1, mu2, s2) - want) < 1e-10


def test_trace_sqrt_product_vs_scipy(rng):
    import scipy.linalg

    a = rng.randn(16, 16)
    b = rng.randn(16, 16)
    s1, s2 = a @ a.T + 0.1 * np.eye(16), b @ b.T + 0.1 * np.eye(16)
    want = np.trace(scipy.linalg.sqrtm(s1 @ s2)).real
    assert abs(fid.trace_sqrt_product(s1, s2) - want) < 1e-8


def test_streaming_stats_match_numpy(rng):
    x = rng.randn(333, 12).astype(np.float32)
    stats = fid.ActivationStats(12)
    for chunk in np.array_split(x, 7):
        stats.update(chunk)
    np.testing.assert_allclose(stats.mean, x.mean(0), atol=1e-6)
    np.testing.assert_allclose(stats.cov, np.cov(x, rowvar=False), atol=1e-6)
    # shard merge (per-host accumulators)
    a, b = fid.ActivationStats(12), fid.ActivationStats(12)
    a.update(x[:100])
    b.update(x[100:])
    merged = a.merge(b)
    np.testing.assert_allclose(merged.cov, stats.cov, atol=1e-6)


def test_fid_separates_distributions(rng):
    """Same-distribution FID ≈ small; shifted distribution FID ≫."""
    d = 6
    same1, same2 = rng.randn(2000, d), rng.randn(2000, d)
    far = rng.randn(2000, d) + 5.0
    s = [fid.ActivationStats(d) for _ in range(3)]
    for acc, data in zip(s, (same1, same2, far)):
        acc.update(data)
    near = fid.fid_from_stats(s[0], s[1])
    far_d = fid.fid_from_stats(s[0], s[2])
    assert near < 1.0 < far_d
    assert far_d > 100.0


@pytest.fixture(scope="module")
def small_variables():
    import jax

    return inception.init_variables(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def extractor(small_variables):
    """``(feature_fn, dim)`` of the seeded-random extractor, ONE a module:
    the 94-conv graph is initialised, traced and compiled once for every
    case that pushes (4, 32, 32, 3) batches through it."""
    return fid.make_feature_fn(*small_variables)


def test_inception_forward_shape(small_variables):
    import jax.numpy as jnp

    model, variables = small_variables
    x = jnp.zeros((2, inception.INCEPTION_SIZE, inception.INCEPTION_SIZE, 3))
    feats = model.apply(variables, x)
    assert feats.shape == (2, inception.FEATURE_DIM)
    assert bool(jnp.isfinite(feats).all())


def test_torch_conversion_roundtrip(small_variables):
    """Build a torch-layout state_dict from the flax variables, convert back,
    and check the tree is identical — the layout transform is its own test
    (torchvision itself is not installed)."""
    import jax

    model, variables = small_variables

    # flax tree → torch-key state_dict (inverse of flax_from_torch_inception)
    sd = {}

    def walk(tree, prefix, is_stats):
        for key, value in tree.items():
            path = prefix + [key]
            if isinstance(value, dict):
                walk(value, path, is_stats)
                continue
            v = np.asarray(value)
            mod, leaf = path[:-1], path[-1]
            name = ".".join(mod)
            if leaf == "kernel":
                sd[name + ".weight"] = v.transpose(3, 2, 0, 1)
            elif leaf == "scale":
                sd[name + ".weight"] = v
            elif leaf == "bias":
                sd[name + ".bias"] = v
            elif leaf == "mean":
                sd[name + ".running_mean"] = v
            elif leaf == "var":
                sd[name + ".running_var"] = v
            else:
                raise AssertionError(leaf)

    walk(variables["params"], [], False)
    walk(variables["batch_stats"], [], True)
    sd["fc.weight"] = np.zeros((1000, 2048), np.float32)  # ignored heads
    sd["AuxLogits.conv0.conv.weight"] = np.zeros((1,), np.float32)

    converted = inception.flax_from_torch_inception(sd)
    flat_a = jax.tree_util.tree_leaves_with_path(
        {"params": variables["params"], "batch_stats": variables["batch_stats"]})
    flat_b = jax.tree_util.tree_leaves_with_path(converted)
    assert len(flat_a) == len(flat_b)
    for (pa, va), (pb, vb) in zip(flat_a, flat_b):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_fid_between_images(rng, extractor):
    """End-to-end on tiny images with the random-init extractor: a stream
    compared against itself gives (near-)zero; against noise it does not.
    (Small batches: each 299×299 InceptionV3 forward is ~seconds on CPU —
    8 images over 3 forwards keeps the path covered without dominating the
    suite's wall time.)"""
    imgs = rng.rand(8, 32, 32, 3).astype(np.float32)
    other = rng.rand(4, 32, 32, 3).astype(np.float32) * 0.2
    feature_fn, dim = extractor
    a = fid.stats_for_batches([imgs[:4], imgs[4:]], feature_fn, dim)
    b = fid.stats_for_batches([imgs[:4], imgs[4:]], feature_fn, dim)
    c = fid.stats_for_batches([other], feature_fn, dim)
    assert abs(fid.fid_from_stats(a, b)) < 1e-6
    assert fid.fid_from_stats(a, c) > fid.fid_from_stats(a, b)


def test_fid_trend_collect_points(tmp_path):
    """scripts/fid_trend.py point assembly: random anchor first, snapshot
    epochs sorted + evenly thinned with first/last kept, best last."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "fid_trend", os.path.join(REPO, "scripts", "fid_trend.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    run = tmp_path
    snap = run / "snapshots"
    snap.mkdir()
    for ep in (3, 1, 21, 7, 11, 15, 9):
        (snap / f"epoch_{ep}").mkdir()
    (snap / "epoch_5.tmp").mkdir()  # in-flight copy: must be ignored
    (run / "bestloss.ckpt").mkdir()

    pts = mod.collect_points(str(run), max_points=4)
    labels = [p[0] for p in pts]
    assert labels[0] == "random" and labels[-1] == "best"
    epochs = [p[1] for p in pts[1:-1]]
    assert epochs == sorted(epochs) and len(epochs) <= 4
    assert epochs[0] == 1 and epochs[-1] == 21  # first/last survive thinning
    assert pts[0][2] is None and pts[-1][2].endswith("bestloss.ckpt")

    # no snapshots, no best → still a valid 1-point (random) trend
    empty = tmp_path / "empty_run"
    empty.mkdir()
    assert [p[0] for p in mod.collect_points(str(empty), 4)] == ["random"]


def test_random_extractor_features_do_not_collapse(rng, extractor):
    """Regression: with default lecun conv init the 94-conv stack attenuates
    activations to ~1e-4 std and every FID computes as ≈0; init_variables
    applies the √2 ReLU gain so seeded-random features stay discriminative."""
    import jax.numpy as jnp

    feature_fn, _ = extractor
    imgs = rng.rand(4, 32, 32, 3).astype(np.float32)
    feats = np.asarray(feature_fn(jnp.asarray(imgs)))
    assert feats.std() > 0.05, f"collapsed features: std={feats.std()}"


# ---------------------------------------------------------------------------
# trend-series helpers (scripts/fid_trend.py)
# ---------------------------------------------------------------------------

def test_thin_keeps_first_and_last():
    seq = list(range(25))
    out = fid.thin(seq, 10)
    assert len(out) == 10 and out[0] == 0 and out[-1] == 24
    assert out == sorted(out)
    assert fid.thin(seq, 100) == seq
    assert fid.thin(seq, 1) == [0]
    assert fid.thin([], 5) == []


def test_annotate_deltas_lower_is_better():
    rows = [{"ckpt": "random", "fid": 400.0},
            {"ckpt": "epoch_1", "fid": 120.0},
            {"ckpt": "best", "fid": 118.0},
            {"ckpt": "drift", "fid": 250.0}]
    out = fid.annotate_deltas(rows, "fid", lower_is_better=True)
    assert "delta_rel" not in out[0]  # first point has no predecessor
    assert out[1]["in_band"]  # improvement is always in band
    assert out[2]["in_band"]
    assert not out[3]["in_band"]  # +112% FID: out of band, flagged
    assert rows[1].keys() == {"ckpt", "fid"}  # input rows untouched


def test_run_metadata_stamp(monkeypatch):
    monkeypatch.setenv("DDIM_COLD_RUN_TS", "1754400000")
    monkeypatch.setenv("DDIM_COLD_ROUND", "6")
    meta = fid.run_metadata(chip="TPU v5 lite")
    assert meta["timestamp"] == 1754400000.0
    assert meta["round"] == 6
    assert meta["device_kind"] == "TPU v5 lite"
    assert meta["jax"]  # installed in every supported environment
    monkeypatch.delenv("DDIM_COLD_RUN_TS")
    monkeypatch.delenv("DDIM_COLD_ROUND")
    monkeypatch.delenv("SOURCE_DATE_EPOCH", raising=False)
    meta = fid.run_metadata()
    assert meta["timestamp"] is None  # never the wall clock
    assert meta["round"] is None
