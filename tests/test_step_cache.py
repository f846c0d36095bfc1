"""Step-cache subsystem tests (ops/step_cache.py + the vit.py hooks).

The contract under test, in order of strictness:
* interval=1 routes around the cache machinery entirely — BITWISE equal to
  the plain sampler (the dispatch in sampling.ddim_sample/cold_sample);
* a refresh forward (capture_split) computes the exact plain forward while
  emitting the half-trunk deltas (bitwise on the image output);
* a reuse forward never executes the skipped blocks — proven functionally:
  its output is invariant to arbitrary perturbation of their params;
* the refresh→reuse round trip reproduces the plain forward to float
  round-off (a + (b − a) ≠ b bitwise, so this one is allclose, not equal);
* the schedule is static: one XLA compile per (k, interval, mode);
* SPMD cached sampling over a data mesh matches single-device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import sampling, schedule, step_cache

T = 2000
# depth 4: distinct front (0,1) / rear (2,3) halves, so a delta-mode reuse
# still runs real blocks and param-invariance has something to bite on
TINY4 = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=4,
             num_heads=4, total_steps=T)


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY4)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


# ---------------------------------------------------------------- schedule

def test_branch_sequence_delta_phase_split():
    seq = schedule.cache_branch_sequence(10, 2, "delta")
    assert seq.dtype == np.int32
    # refreshes at every interval-th step, reuse between; early half reuses
    # the REAR delta (branch 1), late half the FRONT (branch 2)
    assert list(seq) == [0, 1, 0, 1, 0, 2, 0, 2, 0, 2]


def test_branch_sequence_full_mode_and_intervals():
    assert list(schedule.cache_branch_sequence(6, 2, "full")) == [0, 1] * 3
    assert list(schedule.cache_branch_sequence(7, 3, "full")) == [
        0, 1, 1, 0, 1, 1, 0]
    # interval <= 1: every step refreshes (the exact sampler)
    assert list(schedule.cache_branch_sequence(4, 1)) == [0] * 4
    assert list(schedule.cache_branch_sequence(4, 0)) == [0] * 4
    with pytest.raises(ValueError):
        schedule.cache_branch_sequence(4, 2, "bogus")


def test_cache_spec_validation():
    spec = step_cache.cache_spec(4, 10, 2, "delta")
    assert spec.split == 2 and spec.n_steps == 10 and spec.interval == 2
    hash(spec)  # must stay hashable — it rides jit static args
    with pytest.raises(ValueError):
        step_cache.cache_spec(1, 10, 2)  # no half to skip
    with pytest.raises(ValueError):
        step_cache.cache_spec(4, 10, 2, split=0)
    with pytest.raises(ValueError):
        step_cache.cache_spec(4, 10, 2, split=4)


def test_flops_saved_fraction():
    # interval=2, 10 steps: 5 reuse steps skipping half the trunk → 25%
    assert step_cache.flops_saved_fraction(
        step_cache.cache_spec(4, 10, 2, "delta")) == pytest.approx(0.25)
    # full mode skips the whole trunk on reuse steps → 50%
    assert step_cache.flops_saved_fraction(
        step_cache.cache_spec(4, 10, 2, "full")) == pytest.approx(0.5)
    assert step_cache.flops_saved_fraction(
        step_cache.cache_spec(4, 10, 1)) == 0.0
    assert not step_cache.enabled(1) and step_cache.enabled(2)


# ---------------------------------------------------------- model-level hooks

def test_capture_split_forward_is_bitwise_plain(model_and_params):
    """A refresh step must cost nothing in exactness: same blocks, same
    order, deltas read off the already-computed token stream."""
    model, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    t = jnp.array([100, 100], jnp.int32)
    plain = model.apply({"params": params}, x, t)
    out, (d_front, d_rear) = model.apply({"params": params}, x, t,
                                         capture_split=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    assert d_front.shape == d_rear.shape == (2, model.num_patches + 1,
                                             model.embed_dim)


def test_skip_with_true_delta_matches_plain(model_and_params):
    """Refresh → reuse round trip: skipping a half and adding its captured
    delta reproduces the plain forward to float round-off."""
    model, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3))
    t = jnp.array([50, 50], jnp.int32)
    plain = np.asarray(model.apply({"params": params}, x, t))
    _, (d_front, d_rear) = model.apply({"params": params}, x, t,
                                       capture_split=2)
    for skip, delta in (((0, 2), d_front), ((2, 4), d_rear),
                        ((0, 4), d_front + d_rear)):
        out = model.apply({"params": params}, x, t, skip_blocks=skip,
                          block_delta=delta)
        np.testing.assert_allclose(np.asarray(out), plain,
                                   rtol=1e-4, atol=1e-5)


def test_reuse_step_never_runs_skipped_blocks(model_and_params):
    """Functional proof that skipped blocks don't execute: a reuse forward is
    BITWISE invariant to arbitrary perturbation of their params, while the
    same perturbation on an executed block changes the output."""
    model, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3))
    t = jnp.array([10, 10], jnp.int32)
    delta = jnp.zeros((2, model.num_patches + 1, model.embed_dim),
                      model.dtype)

    def wreck(p, block_name):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a + 1e3 if any(
                getattr(k, "key", None) == block_name for k in path) else a, p)

    base = np.asarray(model.apply({"params": params}, x, t,
                                  skip_blocks=(2, 4), block_delta=delta))
    for name in ("blocks_2", "blocks_3"):
        out = np.asarray(model.apply({"params": wreck(params, name)}, x, t,
                                     skip_blocks=(2, 4), block_delta=delta))
        np.testing.assert_array_equal(out, base)
    # sanity: the same perturbation on an EXECUTED block must show up
    out = np.asarray(model.apply({"params": wreck(params, "blocks_0")}, x, t,
                                 skip_blocks=(2, 4), block_delta=delta))
    assert np.abs(out - base).max() > 0


def test_hook_validation(model_and_params):
    model, params = model_and_params
    x = jnp.zeros((1, 16, 16, 3))
    t = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="block_delta"):
        model.apply({"params": params}, x, t, skip_blocks=(0, 2))
    with pytest.raises(ValueError, match="capture_split"):
        model.apply({"params": params}, x, t, capture_split=0)
    with pytest.raises(ValueError):
        model.apply({"params": params}, x, t, skip_blocks=(0, 2),
                    block_delta=jnp.zeros(
                        (1, model.num_patches + 1, model.embed_dim)),
                    capture_split=2)
    scan_model = DiffusionViT(scan_blocks=True, **TINY4)
    sp = scan_model.init(jax.random.PRNGKey(0), x, t)["params"]
    with pytest.raises(ValueError, match="scan_blocks"):
        scan_model.apply({"params": sp}, x, t, capture_split=2)


# ------------------------------------------------------------------ samplers

def test_interval_one_is_bitwise_exact(model_and_params):
    model, params = model_and_params
    rng = jax.random.PRNGKey(5)
    plain = sampling.ddim_sample(model, params, rng, k=400, n=2)
    routed = sampling.ddim_sample(model, params, rng, k=400, n=2,
                                  cache_interval=1, cache_mode="full")
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(routed))
    cold_plain = sampling.cold_sample(model, params, rng, n=2, levels=4)
    cold_routed = sampling.cold_sample(model, params, rng, n=2, levels=4,
                                       cache_interval=1)
    np.testing.assert_array_equal(np.asarray(cold_plain),
                                  np.asarray(cold_routed))


@pytest.mark.parametrize("mode", ["delta", "full"])
def test_cached_ddim_close_to_exact(model_and_params, mode):
    """interval=2 on a tiny random-init model: the cached sampler must stay
    in range and near the exact one (the quantitative FID bound is
    ``eval/fid.cached_sampler_guard``; here we pin basic sanity + determinism)."""
    model, params = model_and_params
    rng = jax.random.PRNGKey(6)
    exact = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2))
    cached = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2,
                                             cache_interval=2,
                                             cache_mode=mode))
    assert np.isfinite(cached).all()
    assert cached.min() >= 0.0 and cached.max() <= 1.0
    assert np.abs(cached - exact).max() < 0.25  # near, not equal
    again = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2,
                                            cache_interval=2,
                                            cache_mode=mode))
    np.testing.assert_array_equal(cached, again)  # deterministic


def test_cached_sequence_last_frame_matches_image(model_and_params):
    model, params = model_and_params
    rng = jax.random.PRNGKey(7)
    seq = sampling.ddim_sample(model, params, rng, k=500, n=2,
                               return_sequence=True, cache_interval=2)
    img = sampling.ddim_sample(model, params, rng, k=500, n=2,
                               cache_interval=2)
    assert seq.shape[0] == 5  # init + 4 steps
    np.testing.assert_allclose(np.asarray(seq[-1]), np.asarray(img),
                               rtol=1e-5, atol=1e-6)


def test_cached_cold_and_eta_paths(model_and_params):
    model, params = model_and_params
    rng = jax.random.PRNGKey(8)
    cold = np.asarray(sampling.cold_sample(model, params, rng, n=2, levels=4,
                                           cache_interval=2))
    assert np.isfinite(cold).all() and cold.min() >= 0.0 and cold.max() <= 1.0
    stoch = np.asarray(sampling.ddim_sample(model, params, rng, k=500, n=2,
                                            eta=0.5, cache_interval=2))
    assert np.isfinite(stoch).all()


def test_one_compile_per_schedule(model_and_params):
    """The refresh/reuse pattern is a scanned input, not a trace condition:
    re-sampling with new rngs must not re-trace, and only (k, interval,
    mode) changes may add compilation cache entries."""
    model, params = model_and_params
    fn = sampling._ddim_scan_cached
    fn.clear_cache()
    for seed in (10, 11, 12):
        sampling.ddim_sample(model, params, jax.random.PRNGKey(seed),
                             k=400, n=2, cache_interval=2)
    assert fn._cache_size() == 1
    sampling.ddim_sample(model, params, jax.random.PRNGKey(10), k=400, n=2,
                         cache_interval=3)
    assert fn._cache_size() == 2
    sampling.ddim_sample(model, params, jax.random.PRNGKey(10), k=400, n=2,
                         cache_interval=2, cache_mode="full")
    assert fn._cache_size() == 3


# ------------------------------------------------- adaptive / token: statics

def test_branch_sequence_adaptive_and_token():
    # adaptive reuses the delta pattern verbatim — it is the static
    # worst-case bound the drift gate can only tighten toward refresh
    np.testing.assert_array_equal(
        schedule.cache_branch_sequence(10, 2, "adaptive"),
        schedule.cache_branch_sequence(10, 2, "delta"))
    # token alternates refresh with the single token-reuse branch id
    assert list(schedule.cache_branch_sequence(6, 2, "token")) == [
        schedule.CACHE_REFRESH, schedule.CACHE_REUSE_TOKEN] * 3


def test_adaptive_token_spec_validation():
    spec = step_cache.cache_spec(4, 10, 2, "adaptive", threshold=0.05)
    assert spec.threshold == 0.05
    hash(spec)
    tok = step_cache.cache_spec(4, 10, 2, "token", token_k=2, n_tokens=5)
    assert tok.token_k == 2 and tok.n_tokens == 5
    with pytest.raises(ValueError):  # adaptive needs a threshold
        step_cache.cache_spec(4, 10, 2, "adaptive")
    with pytest.raises(ValueError):  # negative (and NaN) thresholds rejected
        step_cache.cache_spec(4, 10, 2, "adaptive", threshold=-0.1)
    with pytest.raises(ValueError):  # threshold outside its mode
        step_cache.cache_spec(4, 10, 2, "delta", threshold=0.1)
    with pytest.raises(ValueError):  # token needs n_tokens
        step_cache.cache_spec(4, 10, 2, "token", token_k=2)
    with pytest.raises(ValueError):  # k out of range
        step_cache.cache_spec(4, 10, 2, "token", token_k=6, n_tokens=5)
    with pytest.raises(ValueError):  # k=0 is not "unset", it's invalid
        step_cache.cache_spec(4, 10, 2, "token", token_k=0, n_tokens=5)
    with pytest.raises(ValueError):  # token knobs outside their mode
        step_cache.cache_spec(4, 10, 2, "delta", token_k=2, n_tokens=5)


def test_flops_saved_fraction_token_accounting():
    # 10 steps, 5 reuse; each reuse runs 1 of 5 tokens → saves 4/5 per step
    spec = step_cache.cache_spec(4, 10, 2, "token", token_k=1, n_tokens=5)
    assert step_cache.flops_saved_fraction(spec) == pytest.approx(0.4)
    # k = all tokens: the degenerate exact sampler saves nothing
    spec = step_cache.cache_spec(4, 10, 2, "token", token_k=5, n_tokens=5)
    assert step_cache.flops_saved_fraction(spec) == 0.0


def test_adaptive_init_cache_has_xref_leaf():
    cache = step_cache.init_cache(2, 5, 32, jnp.float32, mode="adaptive",
                                  img_shape=(16, 16, 3))
    assert len(cache) == 3 and cache[2].shape == (2, 16, 16, 3)
    assert cache[2].dtype == jnp.float32
    with pytest.raises(ValueError):
        step_cache.init_cache(2, 5, 32, jnp.float32, mode="adaptive")
    assert len(step_cache.init_cache(2, 5, 32, jnp.float32,
                                     mode="token")) == 2


# --------------------------------------------- token hooks: model-level

def test_token_capture_then_k_all_is_bitwise_plain(model_and_params):
    """k = N+1 elides the gather/scatter at trace time: the reuse forward is
    op-for-op the plain trunk (bitwise), and the carry it emits matches a
    capture_tokens refresh bitwise."""
    model, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(20), (2, 16, 16, 3))
    t = jnp.array([100, 100], jnp.int32)
    n_tok = model.num_patches + 1
    plain = np.asarray(model.apply({"params": params}, x, t))
    out_cap, (ref, delta) = model.apply({"params": params}, x, t,
                                        capture_tokens=True)
    np.testing.assert_array_equal(np.asarray(out_cap), plain)
    out_all, (nr, nd) = model.apply({"params": params}, x, t,
                                    token_cache=(ref, delta), token_k=n_tok)
    np.testing.assert_array_equal(np.asarray(out_all), plain)
    np.testing.assert_array_equal(np.asarray(nr), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(delta))


def test_token_gather_scatter_round_trip(model_and_params):
    """Perturb exactly one patch: with token_k=2 the live set is CLS + that
    patch's token; the carry must be updated at EXACTLY those rows (new
    reference stream) and bit-preserved everywhere else."""
    model, params = model_and_params
    x0 = jax.random.normal(jax.random.PRNGKey(21), (2, 16, 16, 3))
    # patch grid is 2×2 (16px/ps8); patch 0 ↔ token 1 (CLS is token 0)
    x1 = x0.at[:, :8, :8, :].add(0.5)
    t = jnp.array([100, 100], jnp.int32)
    _, (ref0, delta0) = model.apply({"params": params}, x0, t,
                                    capture_tokens=True)
    _, (ref1, _) = model.apply({"params": params}, x1, t,
                               capture_tokens=True)
    out, (nr, nd) = model.apply({"params": params}, x1, t,
                                token_cache=(ref0, delta0), token_k=2)
    assert np.isfinite(np.asarray(out)).all()
    # live rows re-referenced from x1's embed stream, dead rows untouched
    np.testing.assert_array_equal(np.asarray(nr[:, :2]),
                                  np.asarray(ref1[:, :2]))
    np.testing.assert_array_equal(np.asarray(nr[:, 2:]),
                                  np.asarray(ref0[:, 2:]))
    np.testing.assert_array_equal(np.asarray(nd[:, 2:]),
                                  np.asarray(delta0[:, 2:]))


def test_token_hook_validation(model_and_params):
    model, params = model_and_params
    x = jnp.zeros((1, 16, 16, 3))
    t = jnp.zeros((1,), jnp.int32)
    cache = (jnp.zeros((1, model.num_patches + 1, model.embed_dim)),) * 2
    with pytest.raises(ValueError, match="token_k"):
        model.apply({"params": params}, x, t, token_cache=cache)
    with pytest.raises(ValueError, match="token_k"):
        model.apply({"params": params}, x, t, token_cache=cache,
                    token_k=model.num_patches + 2)
    with pytest.raises(ValueError, match="token_k"):
        model.apply({"params": params}, x, t, token_k=2)
    with pytest.raises(ValueError):
        model.apply({"params": params}, x, t, capture_tokens=True,
                    token_cache=cache, token_k=2)
    with pytest.raises(ValueError):
        model.apply({"params": params}, x, t, capture_tokens=True,
                    capture_split=2)


# ------------------------------------------- adaptive / token: sampler level

def test_degenerate_settings_are_bitwise_exact(model_and_params):
    """The collapse contracts: threshold=0 forces every step to refresh and
    token_k=n_tokens recomputes every token — both must be BITWISE the
    plain (uncached) sampler, not merely close."""
    model, params = model_and_params
    rng = jax.random.PRNGKey(22)
    exact = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2))
    adapt0 = sampling.ddim_sample(model, params, rng, k=200, n=2,
                                  cache_interval=2, cache_mode="adaptive",
                                  cache_threshold=0.0)
    np.testing.assert_array_equal(np.asarray(adapt0), exact)
    tok_all = sampling.ddim_sample(model, params, rng, k=200, n=2,
                                   cache_interval=2, cache_mode="token",
                                   cache_tokens=model.num_patches + 1)
    np.testing.assert_array_equal(np.asarray(tok_all), exact)


def test_adaptive_inf_threshold_is_bitwise_static_delta(model_and_params):
    """A gate that never fires must follow the static worst-case schedule
    exactly — bitwise the fixed-interval delta sampler."""
    model, params = model_and_params
    rng = jax.random.PRNGKey(23)
    static = sampling.ddim_sample(model, params, rng, k=200, n=2,
                                  cache_interval=2, cache_mode="delta")
    gated = sampling.ddim_sample(model, params, rng, k=200, n=2,
                                 cache_interval=2, cache_mode="adaptive",
                                 cache_threshold=1e30)
    np.testing.assert_array_equal(np.asarray(gated), np.asarray(static))


@pytest.mark.parametrize("kw", [
    dict(cache_mode="adaptive", cache_threshold=0.05),
    dict(cache_mode="token", cache_tokens=3),
])
def test_adaptive_token_midrange_sane_and_deterministic(model_and_params, kw):
    model, params = model_and_params
    rng = jax.random.PRNGKey(24)
    exact = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2))
    out = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2,
                                          cache_interval=2, **kw))
    assert np.isfinite(out).all()
    assert out.min() >= 0.0 and out.max() <= 1.0
    assert np.abs(out - exact).max() < 0.25
    again = np.asarray(sampling.ddim_sample(model, params, rng, k=200, n=2,
                                            cache_interval=2, **kw))
    np.testing.assert_array_equal(out, again)
    cold = np.asarray(sampling.cold_sample(model, params, rng, n=2, levels=4,
                                           cache_interval=2, **kw))
    assert np.isfinite(cold).all()


def test_one_compile_per_adaptive_token_config(model_and_params):
    """The drift gate is a data-dependent branch INDEX inside one program:
    new rngs never retrace, and only the static knobs (threshold, token_k)
    key new cache entries."""
    model, params = model_and_params
    fn = sampling._ddim_scan_cached
    fn.clear_cache()
    for seed in (30, 31, 32):
        sampling.ddim_sample(model, params, jax.random.PRNGKey(seed),
                             k=400, n=2, cache_interval=2,
                             cache_mode="adaptive", cache_threshold=0.05)
    assert fn._cache_size() == 1
    sampling.ddim_sample(model, params, jax.random.PRNGKey(30), k=400, n=2,
                         cache_interval=2, cache_mode="token", cache_tokens=3)
    assert fn._cache_size() == 2
    sampling.ddim_sample(model, params, jax.random.PRNGKey(31), k=400, n=2,
                         cache_interval=2, cache_mode="token", cache_tokens=2)
    assert fn._cache_size() == 3


# --------------------------------------------------- engine composition

def test_engine_adaptive_token_two_buckets_bitwise_zero_compiles():
    """The served form of both adaptive modes at 2 buckets: bitwise equal to
    the direct sampler calls (adaptive padding uses row-0 replicas so the
    batch-max drift gate can't see the pad) with zero compiles after
    warmup. Token mode's bitwise claim is per dispatch SHAPE: an
    exact-bucket token dispatch is bitwise the own-n direct call; a PADDED
    token dispatch is bitwise a direct call at the same padded shape."""
    from ddim_cold_tpu import serve

    model = DiffusionViT(**TINY4)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    adapt = serve.SamplerConfig(k=500, cache_interval=2,
                                cache_mode="adaptive", cache_threshold=0.05)
    tok = serve.SamplerConfig(k=500, cache_interval=2, cache_mode="token",
                              cache_tokens=3)
    assert adapt.batch_coupled and not tok.batch_coupled
    eng = serve.Engine(model, params, buckets=(4, 8))
    report = serve.warmup(eng, [adapt, tok], persistent_cache=False)
    assert report["new_compiles"] == 4
    t1 = eng.submit(seed=7, n=3, config=adapt)   # padded (row-0 replicas)
    t2 = eng.submit(seed=9, n=8, config=adapt)   # exact bucket
    t3 = eng.submit(seed=11, n=4, config=tok)    # exact bucket
    stats = eng.run()
    assert stats["compiles"] == 0
    for task, seed, n, kw in (
            (t1, 7, 3, dict(cache_mode="adaptive", cache_threshold=0.05)),
            (t2, 9, 8, dict(cache_mode="adaptive", cache_threshold=0.05)),
            (t3, 11, 4, dict(cache_mode="token", cache_tokens=3))):
        direct = np.asarray(sampling.ddim_sample(
            model, params, jax.random.PRNGKey(seed), k=500, n=n,
            cache_interval=2, **kw))
        np.testing.assert_array_equal(np.asarray(task.result()), direct)

    # Padded token dispatch (second drain so the two token requests cannot
    # coalesce into one plan): n=5 lands in bucket 8 with 3 zero-pad rows.
    # The guarantee here is bitwise equality with a direct call at the SAME
    # padded shape — identical program on identical inputs. Equality with
    # the own-n direct call is NOT guaranteed for token mode: the reuse
    # step's gathered sub-sequence trunk is a fresh executable per batch
    # shape, and XLA's GEMM tiling at short sequence lengths rounds
    # per-row differently across batch shapes (the full-trunk modes above
    # don't run a shape-k subset, which is why their padded dispatches
    # stay bitwise vs own-n). Own-n agreement is float-level only.
    t4 = eng.submit(seed=13, n=5, config=tok)
    stats = eng.run()
    assert stats["compiles"] == 0
    got = np.asarray(t4.result())
    x5 = jax.random.normal(jax.random.PRNGKey(13), (5, 16, 16, 3),
                           jnp.float32)
    x8 = jnp.concatenate([x5, jnp.zeros((3, 16, 16, 3), jnp.float32)])
    same_shape = np.asarray(sampling.ddim_sample(
        model, params, k=500, x_init=x8, cache_interval=2,
        cache_mode="token", cache_tokens=3))
    np.testing.assert_array_equal(got, same_shape[:5])
    own_n = np.asarray(sampling.ddim_sample(
        model, params, jax.random.PRNGKey(13), k=500, n=5,
        cache_interval=2, cache_mode="token", cache_tokens=3))
    np.testing.assert_allclose(got, own_n, rtol=0, atol=1e-5)


def test_sampler_config_adaptive_token_validation():
    from ddim_cold_tpu import serve

    with pytest.raises(ValueError):  # adaptive needs a threshold
        serve.SamplerConfig(k=500, cache_interval=2, cache_mode="adaptive")
    with pytest.raises(ValueError):  # NaN is not a threshold
        serve.SamplerConfig(k=500, cache_interval=2, cache_mode="adaptive",
                            cache_threshold=float("nan"))
    with pytest.raises(ValueError):  # threshold outside its mode
        serve.SamplerConfig(k=500, cache_interval=2,
                            cache_threshold=0.1)
    with pytest.raises(ValueError):  # token needs cache_tokens
        serve.SamplerConfig(k=500, cache_interval=2, cache_mode="token")
    with pytest.raises(ValueError):  # tokens outside their mode
        serve.SamplerConfig(k=500, cache_interval=2, cache_tokens=3)
    # inpaint + caching is now a served product (the cached inpaint scan)
    cfg = serve.SamplerConfig(task="inpaint", k=500, cache_interval=2)
    assert cfg.cached


def test_plan_batches_adaptive_never_coalesces():
    """Batch-coupled (adaptive) requests get one batch each — the drift
    gate's batch max couples rows, so coalescing or splitting would break
    the bitwise-vs-direct contract."""
    from ddim_cold_tpu import serve
    from ddim_cold_tpu.serve.batching import Request, plan_batches

    cfg = serve.SamplerConfig(k=500, cache_interval=2, cache_mode="adaptive",
                              cache_threshold=0.05)
    reqs = [Request(config=cfg, n=3), Request(config=cfg, n=2)]
    plans = plan_batches(reqs, (4, 8))
    assert [p.bucket for p in plans] == [4, 4]
    assert all(len(p.entries) == 1 for p in plans)
    assert [p.rows for p in plans] == [3, 2]
    with pytest.raises(ValueError, match="bucket"):
        plan_batches([Request(config=cfg, n=9)], (4, 8))


def test_mesh_sharded_cached_sampling_matches_single_device(model_and_params):
    """SPMD cached sampling: the cache shards ride the data axis next to the
    batch (step_cache.shard_cache) and reproduce the single-device result."""
    from ddim_cold_tpu.parallel.mesh import make_mesh

    model, params = model_and_params
    mesh = make_mesh({"data": 8})
    rng = jax.random.PRNGKey(9)
    single = np.asarray(sampling.ddim_sample(model, params, rng, k=500, n=8,
                                             cache_interval=2))
    sharded = sampling.ddim_sample(model, params, rng, k=500, n=8,
                                   cache_interval=2, mesh=mesh)
    assert len(sharded.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(sharded), single,
                               rtol=2e-5, atol=2e-6)
