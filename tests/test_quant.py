"""W8A16 trunk quantization tests (ops/quant.py + the vit/serve wiring).

The contract ladder, strictest first:
* codec round-trip error ≤ scale/2 per output channel (symmetric [−127, 127]
  codes — the −128 code must stay unused);
* ``quant=None`` is a BITWISE no-op — the quant field may not perturb the
  float path it gates;
* the w8a16 forward matches the float forward allclose at the documented
  tolerance (per-channel int8 on a trained-scale random-init trunk);
* the Pallas fused kernel agrees with the XLA dequant form (both accumulate
  f32 and apply scale in the epilogue);
* the step cache COMPOSES: a capture_split refresh over quantized params is
  bitwise the plain quantized forward — block-delta capture is a trunk
  structure hook, independent of how each dense computes;
* the serving engine serves a quant config bitwise-equal to the direct
  quantized sampler, ships int8 trunk buffers, and a warmed engine stays at
  ZERO compiles over mixed quant/non-quant request streams.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu import serve
from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops import quant, sampling

TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500  # 4 reverse steps (tests/test_serve.py's budget)

#: documented w8a16-vs-float forward tolerance on the 16×16 smoke model
#: (observed max |Δ| ≈ 8e-5; PERF.md "Quantization" quotes this bound)
W8A16_ATOL = 1e-3


@pytest.fixture(scope="module")
def model_and_params():
    model = DiffusionViT(**TINY)
    x = jnp.zeros((2, 16, 16, 3))
    params = model.init(jax.random.PRNGKey(0), x,
                        jnp.array([0, 1], jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def quantized(model_and_params):
    model, params = model_and_params
    return model.clone(quant="xla"), quant.quantize_params(params)


def _xt():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    return x, jnp.array([100, 100], jnp.int32)


# ------------------------------------------------------------------- codec

def test_roundtrip_error_within_half_scale():
    """Per-channel symmetric codec: |w − dequant(quant(w))| ≤ scale/2 for
    every entry (round-to-nearest with the max value mapping exactly to
    ±127), codes in [−127, 127] — −128 unused."""
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 48)) * jnp.exp(
        jax.random.normal(jax.random.PRNGKey(3), (48,)))  # ragged col scales
    w_int8, scale = quant.quantize_weight(w)
    assert w_int8.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert int(jnp.min(w_int8)) >= -127 and int(jnp.max(w_int8)) <= 127
    err = np.abs(np.asarray(w, np.float32)
                 - np.asarray(quant.dequantize_weight(w_int8, scale)))
    bound = np.asarray(scale) / 2 + 1e-7
    assert (err <= bound[None, :]).all(), float((err / bound).max())


def test_zero_column_and_calibrate(model_and_params):
    """All-zero output channels get scale 1.0 / zero codes (no 0/0), and
    calibrate's per-layer relative error stays ≤ 0.5 — the codec bound —
    for every trunk dense, keyed by addressable path."""
    w_int8, scale = quant.quantize_weight(jnp.zeros((8, 4)))
    np.testing.assert_array_equal(np.asarray(scale), np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(w_int8), np.zeros((8, 4)))

    _, params = model_and_params
    cal = quant.calibrate(params)
    # depth 2 × (qkv, proj, fc1, fc2) = 8 trunk denses
    assert len(cal) == 8
    assert "blocks_0/attn/qkv" in cal and "blocks_1/mlp/fc2" in cal
    for path, st in cal.items():
        assert st["max_err_over_scale"] <= 0.5 + 1e-6, (path, st)
        assert st["scale_min"] > 0


def test_quantize_params_topology_and_bytes(model_and_params):
    """The tree transform: trunk kernels become {w_int8, scale} IN PLACE
    (same module paths — sharding rules and engine param flow see the same
    structure), biases bitwise-untouched, patch_embed/head/embeds stay
    float, and the trunk itself ships ≈4× fewer bytes."""
    _, params = model_and_params
    qp = quant.quantize_params(params)
    assert not quant.is_quantized(params) and quant.is_quantized(qp)

    for b in ("blocks_0", "blocks_1"):
        for mod, leaves in (("attn", ("qkv", "proj")), ("mlp", ("fc1", "fc2"))):
            for leaf in leaves:
                d = qp[b][mod][leaf]
                assert "kernel" not in d
                assert d["w_int8"].dtype == jnp.int8
                assert d["scale"].dtype == jnp.float32
                assert d["scale"].shape == (d["w_int8"].shape[-1],)
                np.testing.assert_array_equal(
                    np.asarray(d["bias"]),
                    np.asarray(params[b][mod][leaf]["bias"]))
    # the OTHER "proj" — patch_embed's — must stay a float kernel
    assert "kernel" in qp["patch_embed"]["proj"]
    assert "w_int8" not in qp["patch_embed"]["proj"]
    jax.tree_util.tree_map(np.testing.assert_array_equal,
                           qp["head"], params["head"])

    def codec_bytes(tree, leaves):
        return sum(quant.param_bytes(tree[b][m][d][leaf])
                   for b in ("blocks_0", "blocks_1")
                   for m, ds in (("attn", ("qkv", "proj")),
                                 ("mlp", ("fc1", "fc2")))
                   for d in ds for leaf in leaves)

    # f32 kernel → int8 codes + one f32 scale per column: ≈4× on the codec
    # itself (biases are shared by both trees and excluded — at this toy
    # width they'd dilute the ratio, on the real 384-wide trunk they don't)
    ratio = (codec_bytes(params, ("kernel",))
             / codec_bytes(qp, ("w_int8", "scale")))
    assert 3.5 < ratio <= 4.0, ratio
    assert quant.param_bytes(qp) < quant.param_bytes(params)


# ----------------------------------------------------------------- matmuls

@pytest.mark.parametrize("shape", [(7, 33, 50), (16, 128, 256)])
def test_pallas_matches_xla(shape):
    """The fused kernel (padding paths included: odd M/K/N) reproduces the
    XLA dequant matmul to f32 round-off — either mode can stand in for the
    other."""
    M, Kd, N = shape
    x = jax.random.normal(jax.random.PRNGKey(4), (M, Kd))
    w_int8, scale = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(5), (Kd, N)))
    a = np.asarray(quant.dequant_matmul(x, w_int8, scale, mode="xla"))
    b = np.asarray(quant.dequant_matmul(x, w_int8, scale, mode="pallas"))
    assert a.dtype == b.dtype == np.float32
    np.testing.assert_allclose(b, a, rtol=1e-6, atol=1e-6)


def test_pallas_multichunk_k_accumulation():
    """K streamed through the VMEM accumulator in several chunks (the TPU
    schedule for real trunk shapes) must match a single-pass dot."""
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 300))
    w_int8, scale = quant.quantize_weight(
        jax.random.normal(jax.random.PRNGKey(7), (300, 64)))
    got = np.asarray(quant._dequant_matmul_pallas(
        x, w_int8, scale, block_m=8, block_n=128, block_k=128))  # 3 k-chunks
    want = np.asarray(quant._dequant_matmul_xla(x, w_int8, scale))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_odd_requested_blocks_legalized_at_200px(monkeypatch):
    """Regression for the 200px tile-legality bug, quant edition: odd
    hand-tuned (block_m, block_n, block_k) used to reach the BlockSpecs via
    ``min(block, dim)`` — fine under CPU interpret, a Mosaic reject on chip.
    K is the hardest dim: it is the activation's LANE dim and the int8
    weight's SUBLANE dim (unit 32) at the same time. Shapes are the exact
    200px trunk matmuls: p8 tokens (626, 384) @ fc1, p4 tokens 2501."""
    from test_flash_attention import _tile_rule_spy

    calls = _tile_rule_spy(monkeypatch, quant)  # only uses the shared pl
    cases = [((626, 384, 1536), jnp.bfloat16, (100, 300, 100)),
             ((2501, 384, 384), jnp.float32, (300, 100, 384))]
    for (M, Kd, N), dtype, (bm, bn, bk) in cases:
        x = jax.random.normal(jax.random.PRNGKey(8), (M, Kd), dtype)
        w_int8, scale = quant.quantize_weight(
            jax.random.normal(jax.random.PRNGKey(9), (Kd, N)))
        got = np.asarray(quant._dequant_matmul_pallas(
            x, w_int8, scale, block_m=bm, block_n=bn, block_k=bk),
            np.float32)
        want = np.asarray(quant._dequant_matmul_xla(
            x.astype(jnp.float32), w_int8, scale))
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    assert len(calls) == len(cases), calls


def test_dequant_matmul_validation():
    x = jnp.zeros((2, 4))
    w_int8, scale = quant.quantize_weight(jnp.ones((4, 3)))
    with pytest.raises(ValueError, match="mode"):
        quant.dequant_matmul(x, w_int8, scale, mode="int4")
    with pytest.raises(ValueError, match="int8"):
        quant.dequant_matmul(x, jnp.ones((4, 3)), scale)


# ------------------------------------------------------------- model level

def test_quant_none_is_bitwise_noop(model_and_params):
    """The quant field gates, never perturbs: quant=None runs the identical
    float program."""
    model, params = model_and_params
    x, t = _xt()
    base = np.asarray(model.apply({"params": params}, x, t))
    routed = np.asarray(model.clone(quant=None).apply({"params": params}, x, t))
    np.testing.assert_array_equal(routed, base)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_w8a16_forward_close_to_float(model_and_params, mode):
    """The headline numerics contract: the quantized forward matches the
    float forward at the documented tolerance, for both matmul modes."""
    model, params = model_and_params
    x, t = _xt()
    want = np.asarray(model.apply({"params": params}, x, t))
    got = np.asarray(model.clone(quant=mode).apply(
        {"params": quant.quantize_params(params)}, x, t))
    np.testing.assert_allclose(got, want, atol=W8A16_ATOL, rtol=0)


def test_quant_model_validation(model_and_params):
    model, params = model_and_params
    x, t = _xt()
    with pytest.raises(ValueError, match="quant"):
        model.clone(quant="int4").apply({"params": params}, x, t)
    scan = DiffusionViT(scan_blocks=True, **TINY)
    sp = scan.init(jax.random.PRNGKey(0), x, t)["params"]
    with pytest.raises(ValueError, match="scan_blocks"):
        scan.clone(quant="xla").apply({"params": sp}, x, t)
    moe = DiffusionViT(num_experts=2, **TINY)
    mp = moe.init(jax.random.PRNGKey(0), x, t)["params"]
    with pytest.raises(ValueError, match="dense trunk"):
        moe.clone(quant="xla").apply({"params": mp}, x, t)


# ----------------------------------------------------- step-cache composition

def test_capture_split_refresh_is_bitwise_plain_quantized(quantized):
    """Composition with the step cache: a refresh forward (capture_split)
    over QUANTIZED params is bitwise the plain quantized forward — the
    delta-capture hook reads the token stream the w8a16 trunk already
    computed, exactly as on the float path."""
    qmodel, qparams = quantized
    x, t = _xt()
    plain = np.asarray(qmodel.apply({"params": qparams}, x, t))
    out, (d_front, d_rear) = qmodel.apply({"params": qparams}, x, t,
                                          capture_split=1)
    np.testing.assert_array_equal(np.asarray(out), plain)
    assert d_front.shape == d_rear.shape


def test_cached_quantized_sampler_paired_drift(model_and_params, quantized):
    """interval=2 full-mode quantized sampling stays paired-close to the
    exact float sampler (the composed shift the PERF.md table reports), and
    the composed path is deterministic."""
    model, params = model_and_params
    qmodel, qparams = quantized
    rng = jax.random.PRNGKey(8)
    exact = np.asarray(sampling.ddim_sample(model, params, rng, k=K, n=2))
    composed = np.asarray(sampling.ddim_sample(
        qmodel, qparams, rng, k=K, n=2, cache_interval=2, cache_mode="full"))
    assert np.isfinite(composed).all()
    assert np.abs(composed - exact).max() < 0.25
    again = np.asarray(sampling.ddim_sample(
        qmodel, qparams, rng, k=K, n=2, cache_interval=2, cache_mode="full"))
    np.testing.assert_array_equal(composed, again)


def test_quantized_sampler_guard_smoke(model_and_params):
    """The paired Fréchet guard runs end to end (proxy extractor) and its
    pixel delta obeys the sampler tolerance; composed cache_interval rides
    the same call."""
    from ddim_cold_tpu.eval import fid

    model, params = model_and_params
    rep = fid.quantized_sampler_guard(model, params,
                                      rng=jax.random.PRNGKey(9),
                                      n_samples=2, sample_batch=2, k=K)
    assert rep["quant_rev"] == quant.QUANT_REV
    assert np.isfinite(rep["fid_exact_vs_quant"])
    assert rep["max_abs_pixel_delta"] < 5e-3  # 4-step drift of an 8e-5 eps gap
    assert rep["calibration_worst_layer"] is not None


# ----------------------------------------------------------------- serving

@pytest.fixture(scope="module")
def warmed_quant(model_and_params):
    model, params = model_and_params
    eng = serve.Engine(model, params, buckets=(4,))
    cfg_f = serve.SamplerConfig(k=K)
    cfg_q = serve.SamplerConfig(k=K, quant="xla")
    report = serve.warmup(eng, [cfg_f, cfg_q], persistent_cache=False)
    assert report["new_compiles"] == 2  # one program per (config, bucket)
    return eng, cfg_f, cfg_q


def test_engine_quant_bitwise_vs_direct(model_and_params, quantized,
                                        warmed_quant):
    """Acceptance: the engine serves a quant config bitwise-equal to the
    direct quantized sampler, ships int8 trunk buffers (device dtype, not a
    dequantized copy), and reports the ≈4×-smaller param-byte footprint."""
    qmodel, qparams = quantized
    eng, _, cfg_q = warmed_quant
    compiles = eng.stats["compiles"]
    t = eng.submit(seed=101, n=3, config=cfg_q)
    eng.run()
    assert eng.stats["compiles"] == compiles
    want = np.asarray(sampling.ddim_sample(
        qmodel, qparams, jax.random.PRNGKey(101), k=K, n=3))
    np.testing.assert_array_equal(t.result(timeout=5), want)
    # the engine's own tree carries int8 leaves — H2D shipped int8, once
    assert eng._qparams["blocks_0"]["attn"]["qkv"]["w_int8"].dtype == jnp.int8
    assert eng.stats["param_bytes_quant"] < eng.stats["param_bytes"]


def test_zero_compiles_mixed_quant_streams(model_and_params, warmed_quant):
    """After warmup over BOTH configs, interleaved quant and float requests
    at many sizes — across several drains — trigger zero program builds, and
    the two streams never coalesce into one batch."""
    from ddim_cold_tpu.serve.batching import Request, plan_batches

    eng, cfg_f, cfg_q = warmed_quant
    compiles = eng.stats["compiles"]
    for sizes in ([1, 2], [3, 4], [2, 1, 3]):
        tickets = [eng.submit(seed=110 + n, n=n,
                              config=(cfg_q if i % 2 else cfg_f))
                   for i, n in enumerate(sizes)]
        eng.run()
        for t in tickets:
            assert t.done
    assert eng.stats["compiles"] == compiles

    plans = plan_batches([Request(config=cfg_f, n=2),
                          Request(config=cfg_q, n=2)], (4,))
    assert len(plans) == 2  # quant and float programs differ — no sharing


# ---------------------------------------------------------------- exact GELU

@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 2e-2)])
def test_gelu_exact_is_the_erf_gelu(dtype, atol):
    """``gelu_exact`` spells erf from mul/add/div (the Pallas TPU lowering has
    neither erf nor erfc); it must still BE the exact GELU — against math.erf
    in float64, and within f32 round-off of ``jax.nn.gelu(approximate=False)``
    — nowhere near the tanh approximation's 1e-3 — in the input's dtype."""
    import math

    x = np.linspace(-6.0, 6.0, 4801, dtype=np.float32)
    want = np.array([0.5 * v * (1.0 + math.erf(v / math.sqrt(2.0)))
                     for v in x.astype(np.float64)])
    got = quant.gelu_exact(jnp.asarray(x, dtype))
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=atol)
    if dtype == jnp.float32:
        np.testing.assert_allclose(
            got, jax.nn.gelu(jnp.asarray(x), approximate=False), atol=2e-6)
