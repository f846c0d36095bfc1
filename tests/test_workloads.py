"""Guided-editing workload tests (ddim_cold_tpu/workloads).

Three contracts, per task:

* **served-vs-direct** — a served ``SamplerConfig(task=…)`` request returns
  what the direct ``workloads.*`` call with the same rng returns, as
  ``tests/promise.py`` states it: bitwise at the bucket's batch size, to a
  tolerance at the request's own n (every init builder is shared code drawn
  at the request's own n);
* **zero compiles after warmup** — the edit configs coalesce into the same
  AOT machinery, so the compile counter is frozen across every submission
  (including preview-enabled variants);
* **mask idempotence** — inpainting preserves the known pixels EXACTLY
  (the final output is the last projected x̂0).

Plus the streaming-preview surface: ``Ticket.previews()`` frames are a
bitwise prefix of the direct trajectory, and at least one frame lands
BEFORE the ticket resolves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import promise
import pytest

from ddim_cold_tpu import serve, workloads
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import degrade, sampling
from ddim_cold_tpu.serve import fleet
from ddim_cold_tpu.serve.router import Router

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500       # 4 reverse steps
T_START = 1200  # 3-step suffix for draft/interp


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


def _configs():
    return {
        "inpaint": serve.SamplerConfig(task="inpaint", k=K),
        "superres": serve.SamplerConfig(task="superres", sampler="cold",
                                        levels=3),
        "draft": serve.SamplerConfig(task="draft", k=K, t_start=T_START),
        "interp": serve.SamplerConfig(task="interp", k=K, t_start=T_START),
        "draft_pv": serve.SamplerConfig(task="draft", k=K, t_start=T_START,
                                        preview_every=1),
    }


@pytest.fixture(scope="module")
def edit_warmed(model_and_params):
    """One engine warmed with every edit config at two buckets."""
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4, 8))
    cfgs = _configs()
    report = serve.warmup(eng, list(cfgs.values()), persistent_cache=False)
    # warm-up compiles one program a distinct fingerprint and aliases the
    # rest: draft and interp differ only in how the start state is made
    keys = [(c, b) for c in cfgs.values() for b in eng.buckets]
    distinct = {eng.program_fingerprint(c, b) for c, b in keys}
    assert report["new_compiles"] == len(distinct) == 8
    assert report["new_compiles"] + report["deduped"] == len(keys) == 10
    return eng, cfgs


@pytest.fixture(scope="module")
def images(model_and_params):
    """Deterministic [-1, 1] reference images + a half-image mask."""
    model, _ = model_and_params
    H, W = model.img_size
    rs = np.random.RandomState(7)
    imgs = rs.uniform(-1.0, 1.0, (5, H, W, 3)).astype(np.float32)
    mask = np.zeros((H, W), np.float32)
    mask[: H // 2] = 1.0
    return imgs, mask


# ----------------------------------------------------------------- registry


def test_task_registry_pinned():
    """serve/batching.py keeps a literal copy of the task tuple (host-only
    module) — it must stay equal to the workloads registry."""
    from ddim_cold_tpu.serve import batching

    assert batching._TASKS == workloads.TASKS
    assert workloads.TASKS == ("sample",) + workloads.EDIT_TASKS


def test_normalize_mask_shapes(model_and_params):
    model, _ = model_and_params
    H, W = model.img_size
    flat = np.ones((H, W), np.float32)
    for shaped in (flat, flat[..., None], flat[None], flat[None, ..., None]):
        m = workloads.normalize_mask(shaped, 3, (H, W))
        assert m.shape == (3, H, W, 1) and m.dtype == np.float32
    with pytest.raises(ValueError, match="binary"):
        workloads.normalize_mask(flat * 0.5, 1, (H, W))
    with pytest.raises(ValueError, match="batch"):
        workloads.normalize_mask(np.ones((2, H, W), np.float32), 3, (H, W))
    with pytest.raises(ValueError, match="mask must be"):
        workloads.normalize_mask(np.ones((H + 1, W), np.float32), 1, (H, W))


# ------------------------------------------------------------------ inpaint


def test_inpaint_mask_idempotence(model_and_params, images):
    """Known pixels of the result are (known+1)/2 bit-exactly; the
    synthesized half actually differs from the reference."""
    model, params = model_and_params
    imgs, mask = images
    known = imgs[:2]
    out = np.asarray(workloads.inpaint(model, params, jax.random.PRNGKey(1),
                                       known, mask, k=K))
    sel = mask.astype(bool)
    assert np.array_equal(out[:, sel], ((known[:, sel] + 1.0) / 2.0))
    assert not np.allclose(out[:, ~sel], (known[:, ~sel] + 1.0) / 2.0)


def _serve_one_by_one(eng, submits):
    """Each request drained alone, so that it is served from the smallest
    bucket that holds it: 3 rows from bucket 4, 5 rows from bucket 8."""
    tickets = []
    for kwargs in submits:
        tickets.append(eng.submit(**kwargs))
        eng.run()
    return tickets


@pytest.mark.parametrize("how", promise.HOWS)
def test_inpaint_engine_bitwise_two_buckets(edit_warmed, images, how):
    eng, cfgs = edit_warmed
    model, params = eng.model, eng.params
    imgs, mask = images
    c0 = eng.stats["compiles"]
    sizes = ((11, 3, 4), (12, 5, 8))  # seed, rows, the bucket that serves
    tickets = _serve_one_by_one(eng, [
        dict(seed=seed, x_init=imgs[:n], mask=mask, config=cfgs["inpaint"])
        for seed, n, _ in sizes])

    def direct(x, known, m):
        return sampling._ddim_scan_inpaint(
            model, params, jnp.asarray(x), jnp.asarray(known),
            jnp.asarray(m), jax.random.PRNGKey(0), k=K, t_start=None,
            eta=0.0, sequence=False)

    for (seed, n, bucket), t in zip(sizes, tickets):
        starts = (jax.random.normal(jax.random.PRNGKey(seed),
                                    (n,) + model.img_size + (3,)),
                  imgs[:n], workloads.normalize_mask(mask, n, model.img_size))
        promise.assert_served(how, t.result(), direct, starts, (bucket,))
    assert eng.stats["compiles"] == c0


# ----------------------------------------------------------------- superres


def test_superres_matches_cold_sample(model_and_params):
    """A 1×1 constant input at the full level count IS cold sampling: the
    upsampled start equals the broadcast constant-color init bitwise."""
    model, params = model_and_params
    color = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, 1, 1, 3),
                                         jnp.float32))
    direct = np.asarray(sampling.cold_sample(model, params,
                                             jax.random.PRNGKey(3), n=2,
                                             levels=4))
    sr = np.asarray(workloads.super_resolve(model, params, color, level=4))
    assert np.array_equal(sr, direct)


@pytest.mark.parametrize("how", promise.HOWS)
def test_superres_engine_bitwise_two_buckets(edit_warmed, images, how):
    eng, cfgs = edit_warmed
    model, params = eng.model, eng.params
    imgs, _ = images
    H = model.img_size[0]
    c0 = eng.stats["compiles"]
    sizes = ((3, 4), (5, 8))  # rows, the bucket that serves them
    starts = [workloads.superres_init(imgs[:n, ::8, ::8], H)  # 2×2 → level 3
              for n, _ in sizes]
    tickets = _serve_one_by_one(eng, [
        dict(x_init=x, config=cfgs["superres"]) for x in starts])

    def direct(x):
        return sampling.cold_sample(model, params, x_init=jnp.asarray(x),
                                    levels=3)

    for (n, bucket), x, t in zip(sizes, starts, tickets):
        promise.assert_served(how, t.result(), direct, (x,), (bucket,))
    assert eng.stats["compiles"] == c0


def test_upsample_nearest_roundtrips_downsample():
    """upsample∘downsample is the cold degradation D(x, level): idempotent
    on already-degraded images (the degradation-operator property the
    superres task leans on)."""
    from ddim_cold_tpu.data import resize

    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (2, 16, 16, 3)).astype(np.float32)
    iy = resize.nearest_indices(4, 16)
    down = x[:, iy][:, :, iy]
    up = np.asarray(degrade.upsample_nearest(down, 16))
    down2 = up[:, iy][:, :, iy]
    assert np.array_equal(down, down2)


# -------------------------------------------------------------------- draft


def _sample_from(model, params, **kwargs):
    """The direct guided sampler on a given start state (draft, interp)."""
    def direct(x):
        return sampling.sample_from(model, params, jnp.asarray(x), T_START,
                                    k=K, **kwargs)
    return direct


@pytest.mark.parametrize("how", promise.HOWS)
def test_draft_engine_bitwise_two_buckets(edit_warmed, images, how):
    eng, cfgs = edit_warmed
    model, params = eng.model, eng.params
    imgs, _ = images
    c0 = eng.stats["compiles"]
    sizes = ((21, 3, 4), (22, 5, 8))  # seed, rows, the bucket that serves
    tickets = _serve_one_by_one(eng, [
        dict(seed=seed, x_init=imgs[:n], config=cfgs["draft"])
        for seed, n, _ in sizes])
    for (seed, n, bucket), t in zip(sizes, tickets):
        x = workloads.draft_init(jax.random.PRNGKey(seed),
                                 jnp.asarray(imgs[:n]), T_START)
        promise.assert_served(how, t.result(), _sample_from(model, params),
                              (x,), (bucket,))
    assert eng.stats["compiles"] == c0


def test_sample_from_forwards_sequence_and_mesh(model_and_params, images):
    """Satellite fix: sample_from used to drop return_sequence/mesh on the
    floor — the trajectory form must come back (steps+1, n, H, W, C)."""
    model, params = model_and_params
    imgs, _ = images
    enc = workloads.draft_init(jax.random.PRNGKey(2),
                               jnp.asarray(imgs[:2]), T_START)
    seq = sampling.sample_from(model, params, enc, T_START, k=K,
                               return_sequence=True, mesh=None)
    steps = T_START // K + 1  # the scan visits t_start down through 0
    assert seq.shape == (steps + 1, 2) + model.img_size + (3,)
    last = sampling.sample_from(model, params, enc, T_START, k=K)
    assert last.shape == (2,) + model.img_size + (3,)


# ------------------------------------------------------------------- interp


def test_interpolate_end_to_end(model_and_params, images):
    model, params = model_and_params
    imgs, _ = images
    out = np.asarray(workloads.interpolate(
        model, params, jax.random.PRNGKey(4), imgs[0], imgs[1],
        n_interp=5, t_start=T_START, k=K))
    assert out.shape == (5,) + model.img_size + (3,)
    assert np.isfinite(out).all()
    assert not np.array_equal(out[0], out[-1])  # path actually moves


@pytest.mark.parametrize("how", promise.HOWS)
def test_interp_engine_bitwise_two_buckets(edit_warmed, images, how):
    eng, cfgs = edit_warmed
    model, params = eng.model, eng.params
    imgs, _ = images
    pair = imgs[:2]
    c0 = eng.stats["compiles"]
    sizes = ((31, 3, 4), (32, 5, 8))  # seed, PATH length, bucket
    tickets = _serve_one_by_one(eng, [
        dict(seed=seed, n=n, x_init=pair, config=cfgs["interp"])
        for seed, n, _ in sizes])
    for (seed, n, bucket), t in zip(sizes, tickets):
        x = workloads.interp_init(jax.random.PRNGKey(seed),
                                  jnp.asarray(pair[0]), jnp.asarray(pair[1]),
                                  n, T_START)
        promise.assert_served(how, t.result(), _sample_from(model, params),
                              (x,), (bucket,))
    assert eng.stats["compiles"] == c0


# ----------------------------------------------------------------- previews


def test_previews_stream_before_completion(edit_warmed, images):
    """preview_every=1 on the 3-step draft config: frames 1 and 2 stream,
    each a bitwise row-slice of the direct trajectory at the bucket's batch
    size, delivered BEFORE the ticket resolves; the final result is the
    trajectory's last frame."""
    eng, cfgs = edit_warmed
    model, params = eng.model, eng.params
    imgs, _ = images
    c0 = eng.stats["compiles"]
    t = eng.submit(seed=41, x_init=imgs[:3], config=cfgs["draft_pv"])
    seen = []
    t.add_preview_callback(lambda step, frames: seen.append((step, t.done)))
    eng.run()
    assert eng.stats["compiles"] == c0
    assert seen and all(not done for _, done in seen)

    x = workloads.draft_init(jax.random.PRNGKey(41), jnp.asarray(imgs[:3]),
                             T_START)
    direct_seq = np.asarray(_sample_from(model, params, return_sequence=True)(
        promise.pad_rows(x, 4)))[:, :3]  # 3 rows are served from bucket 4
    frames = list(t.previews(timeout=5))
    assert [s for s, _ in frames] == [1, 2]
    for step, frame in frames:
        assert np.array_equal(frame, direct_seq[step])
    assert np.array_equal(t.result(), direct_seq[-1])


def test_previews_iterator_empty_without_opt_in(edit_warmed, images):
    eng, cfgs = edit_warmed
    imgs, _ = images
    t = eng.submit(seed=42, x_init=imgs[:3], config=cfgs["draft"])
    eng.run()
    t.result()
    assert list(t.previews(timeout=1)) == []


def test_router_forwards_previews_and_keeps_bitwise(model_and_params,
                                                   images):
    """The fleet path: an edit task routed through Router completes bitwise
    and its preview frames surface on the ROUTER ticket."""
    model, params = model_and_params
    imgs, mask = images
    cfg = serve.SamplerConfig(task="inpaint", k=K, preview_every=2)
    factory = fleet.local_factory(model, params, buckets=(4,))
    router = Router(factory, replicas=1, configs=[cfg],
                    warm_kwargs={"persistent_cache": False})
    try:
        t = router.submit(seed=51, x_init=imgs[:3], mask=mask, config=cfg)
        rows = t.result(timeout=120)
        direct_seq = np.asarray(workloads.inpaint(
            model, params, jax.random.PRNGKey(51), imgs[:3], mask, k=K,
            return_sequence=True))
        assert np.array_equal(rows, direct_seq[-1])
        frames = list(t.previews(timeout=5))
        assert [s for s, _ in frames] == [2]  # 4 steps, every=2
        assert np.array_equal(frames[0][1], direct_seq[2])
    finally:
        router.drain(5.0)


# --------------------------------------------------------------- validation


def test_submit_validation(edit_warmed, images):
    eng, cfgs = edit_warmed
    imgs, mask = images
    with pytest.raises(ValueError, match="mask= is the inpaint"):
        eng.submit(seed=0, x_init=imgs[:2], mask=mask, config=cfgs["draft"])
    with pytest.raises(ValueError, match="needs x_init"):
        eng.submit(seed=0, config=cfgs["draft"])
    with pytest.raises(ValueError, match="needs mask"):
        eng.submit(seed=0, x_init=imgs[:2], config=cfgs["inpaint"])
    with pytest.raises(ValueError, match="keyed"):
        eng.submit(x_init=imgs[:2], mask=mask, config=cfgs["inpaint"])
    with pytest.raises(ValueError, match="endpoint PAIR"):
        eng.submit(seed=0, n=4, x_init=imgs[:3], config=cfgs["interp"])


def test_config_validation():
    with pytest.raises(ValueError, match="task"):
        serve.SamplerConfig(task="sharpen")
    with pytest.raises(ValueError, match="cold"):
        serve.SamplerConfig(task="superres")  # superres is the cold path
    with pytest.raises(ValueError, match="t_start"):
        serve.SamplerConfig(task="draft", k=K)
    # inpaint + step cache became a served product in the adaptive-cache PR
    assert serve.SamplerConfig(task="inpaint", k=K, cache_interval=2).cached
    with pytest.raises(ValueError, match="preview_every"):
        serve.SamplerConfig(k=K, preview_every=-1)


def test_default_edit_configs_cover_every_task():
    cfgs = workloads.default_edit_configs(k=K, t_start=T_START, sr_level=3)
    assert sorted(c.task for c in cfgs) == sorted(workloads.EDIT_TASKS)
