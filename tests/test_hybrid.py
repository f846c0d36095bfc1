"""models/hybrid.py at toy size (width 64, d_inner 128, 16 states, dt_rank 8,
4 layers with attention at ``i % 4 == 2``, 2 query heads on 1 K/V head,
16x16 px, patch 4) on seeded weights, against the plain reference
(``benchmark/reference/hybrid.py``, which imports nothing of the program):
the forward, the DDIM trajectory, gradients, causality of every layer kind,
serving, building from a yaml, and each option the trunk refuses by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_hybrid
from benchmark.reference import hybrid as ref
from benchmark.reference import lowprec
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import hybrid
from ddim_cold_tpu.ops import sampling

TRUNK = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=4,
    num_attention_heads=2, num_key_value_heads=1, attn_layer_period=4,
    attn_layer_offset=2, mamba_expand=2, mamba_d_state=16, mamba_d_conv=4,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    rms_norm_eps=1e-6, hidden_act="silu", num_experts=1, sliding_window=None)
SIZES = dict(img_size=[16, 16], patch_size=4, in_chans=3, total_steps=2000)


def config(precision):
    return dict(TRUNK, **SIZES, precision=precision)


def model_and_params(precision, seed=7):
    dtype = weights_hybrid.DTYPES[precision]
    model = hybrid.HybridDenoiser(
        trunk=TRUNK, img_size=(16, 16), patch_size=4, total_steps=2000,
        dtype=dtype, param_dtype=dtype)
    return model, weights_hybrid.make(config(precision), seed)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT):
    return ref.forward(params, x, t, trunk=TRUNK, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_weights_are_the_tree_the_model_declares():
    """Names, shapes and dtypes of ``model.init`` — at both storage types."""
    for precision in ("bfloat16", "float32"):
        model, params = model_and_params(precision)
        x, t = inputs()
        declared = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), x, t)["params"])
        spec = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
        assert spec(params) == spec(declared)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(
        model_and_params("bfloat16")[1]))


def test_forward_matches_the_reference_in_float32():
    model, params = model_and_params("float32")
    x, t = inputs()
    got = model.apply({"params": params}, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree: bfloat16 rounding through 4 layers reads 1.5e-3 to 3e-3 on
#: outputs of rms 0.17; the float8 control reads above 2e-2
BF16_FORWARD_RMS = 8e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = model.apply({"params": params}, x, t)
    control = reference_forward(params, x, t, ops=lowprec.FP8)
    assert rms(got, want) < BF16_FORWARD_RMS < rms(control, want), (
        rms(got, want), rms(control, want))


def test_ddim_sample_follows_the_reference_trajectory():
    """k = 500: the four reverse steps from the same start noise."""
    model, params = model_and_params("float32")
    key = jax.random.PRNGKey(11)
    got = sampling.ddim_sample(model, params, key, k=500, n=2)
    x_init = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
    want = ref.sample(params, x_init, k=500, total_steps=2000, trunk=TRUNK,
                      patch_size=4)
    assert got.shape == (2, 16, 16, 3)
    assert rms(got, want) < 2e-5, rms(got, want)


def test_a_trace_counts_one_convolution_and_one_scan_a_mamba_layer():
    """Three of the toy's four layers are Mamba mixers: three traces of the
    short convolution and three of the scan, off the TPU on the XLA forms."""
    from ddim_cold_tpu.obs import metrics

    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(params)
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.causal_conv_schedule",
                     "kernels.ssm_scan_schedule", "kernels.ssd_schedule",
                     "kernels.kda_schedule"):
            for key, count in series.get(name + "/by_key", {}).items():
                by_key[name, key] = by_key.get((name, key), 0) + count
    assert by_key == {("kernels.causal_conv_schedule", "xla"): 3,
                      ("kernels.ssm_scan_schedule", "xla"): 3}
    metrics.reset()


def test_gradient_matches_the_references():
    """What a training step differentiates: the scan's XLA path."""
    model, params = model_and_params("float32")
    x, t = inputs()
    target = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    loss = lambda fwd: lambda p: jnp.mean((fwd(p) - target) ** 2)
    got = jax.grad(loss(lambda p: model.apply({"params": p}, x, t, False)))(params)
    want = jax.grad(loss(lambda p: reference_forward(p, x, t)))(params)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert np.isfinite(np.asarray(g)).all(), path
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        assert float(jnp.abs(g - w).max()) <= 2e-3 * scale + 1e-9, path


def test_a_training_step_runs_and_lowers_the_loss():
    from ddim_cold_tpu.ops.losses import smooth_l1
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = hybrid.HybridDenoiser(trunk=TRUNK, img_size=(16, 16), patch_size=4)
    x, t = inputs()
    clean = jnp.tanh(jax.random.normal(jax.random.PRNGKey(3), x.shape))
    batch = (x, clean, t)
    state = create_train_state(model, jax.random.PRNGKey(0), lr=2e-3,
                               total_steps=50, sample_batch=batch)
    loss_of = lambda p: float(smooth_l1(model.apply({"params": p}, x, t), clean))
    before = loss_of(state.params)
    step = make_train_step(model)
    rec = jnp.float32(5.0)
    for _ in range(12):
        state, _, rec = step(state, batch, jax.random.PRNGKey(1), rec)
    assert loss_of(state.params) < before


@pytest.mark.parametrize("kind", ["mamba", "attention", "mlp", "layer_mamba",
                                  "layer_attention", "denoiser_trunk"])
def test_every_layer_kind_is_causal(kind):
    """The output at token t does not move when tokens after t change."""
    kw = dict(trunk=hybrid.flax.core.FrozenDict(TRUNK))
    module = {
        "mamba": lambda: hybrid.MambaMixer(**kw),
        "attention": lambda: hybrid.CausalAttention(**kw),
        "mlp": lambda: hybrid.GatedMlp(**kw),
        "layer_mamba": lambda: hybrid.HybridLayer(attention=False, **kw),
        "layer_attention": lambda: hybrid.HybridLayer(attention=True, **kw),
    }.get(kind)
    tokens = jax.random.normal(jax.random.PRNGKey(2), (2, 17, 64))
    t = 8
    later = tokens.at[:, t + 1:].add(
        jax.random.normal(jax.random.PRNGKey(4), (2, 17 - t - 1, 64)))
    if module is None:  # the whole trunk: patches after t of the image
        model, params = model_and_params("float32")
        x, steps = inputs(2)
        run = lambda x: model.apply({"params": params}, x, steps)
        # tokens run in raster order over 4x4 patches: rows of pixels 8.. are
        # patches 8..15 = tokens 9..16; pixel rows 0..7 come from tokens <= 8
        moved = run(x.at[:, 8:].add(1.0))
        base = run(x)
        np.testing.assert_allclose(moved[:, :8], base[:, :8], atol=1e-6)
        assert float(jnp.abs(moved[:, 8:] - base[:, 8:]).max()) > 1e-3
        return
    m = module()
    variables = m.init(jax.random.PRNGKey(0), tokens)
    # seeded, non-degenerate leaves: scales near 1, kernels drawn
    variables = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(9), a.shape),
        variables)
    base, moved = m.apply(variables, tokens), m.apply(variables, later)
    np.testing.assert_allclose(moved[:, :t + 1], base[:, :t + 1], atol=1e-6)
    assert float(jnp.abs(moved[:, t + 1:] - base[:, t + 1:]).max()) > 1e-3


def test_requests_through_engine_and_router_match_the_direct_call():
    """Within 1e-6, not bitwise: the engine's bitwise contract is red by one
    unit in the last place on this backend for every model (ROADMAP Design
    5); a padded bucket (n = 3 in 4) is in the comparison."""
    model, params = model_and_params("float32")
    cfg = serve.SamplerConfig(k=500)
    direct = lambda seed, n: np.asarray(sampling.ddim_sample(
        model, params, jax.random.PRNGKey(seed), k=500, n=n))
    eng = serve.Engine(model, params, buckets=(4,))
    serve.warmup(eng, [cfg], persistent_cache=False)
    tickets = [(seed, n, eng.submit(seed=seed, n=n, config=cfg))
               for seed, n in ((21, 4), (22, 3))]
    eng.run()
    for seed, n, ticket in tickets:
        got = np.asarray(ticket.result(timeout=120))
        assert np.abs(got - direct(seed, n)).max() <= 1e-6
    router = serve.Router(
        serve.local_factory(model, params, buckets=(4,)), replicas=1,
        configs=[cfg], warm_kwargs=dict(persistent_cache=False),
        drain_timeout_s=10.0)
    try:
        got = np.asarray(router.submit(seed=23, n=2, config=cfg).result(timeout=120))
        assert np.abs(got - direct(23, 2)).max() <= 1e-6
    finally:
        router.close()


# ---------------------------------------------------------------- refusals

def _yaml(tmp_path, **extra):
    import yaml

    from ddim_cold_tpu.config import load_config

    raw = dict(image_size=[16, 16], patch_size=4, trunk=TRUNK, **extra)
    path = tmp_path / "hybrid.yaml"
    path.write_text(yaml.safe_dump(raw))
    return load_config(str(path))


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    from ddim_cold_tpu.train.trainer import build_model

    model = build_model(_yaml(tmp_path, AMP=True))
    assert isinstance(model, hybrid.HybridDenoiser)
    assert (model.embed_dim, model.depth, model.num_heads) == (64, 4, 2)
    assert model.dtype == jnp.bfloat16 and model.num_patches == 16
    assert model.total_steps == 2000 and tuple(model.img_size) == (16, 16)


@pytest.mark.parametrize("option,how", [
    ("quant", lambda m, tmp: m.clone(quant="xla")),
    ("fused", lambda m, tmp: m.clone(fused=True)),
    ("cache_mode", lambda m, tmp: m.apply(
        {"params": {}}, *inputs(), capture_split=1)),
    ("scan_blocks", lambda m, tmp: _build(tmp, scan_blocks=True)),
    ("num_experts", lambda m, tmp: _build(tmp, num_experts=4)),
    ("sp_mode", lambda m, tmp: m.clone(sp_mode="ulysses")),
    ("sp_mode", lambda m, tmp: m.clone(seq_mesh=object(), seq_axis="seq")),
    ("use_flash", lambda m, tmp: _build(tmp, use_flash=True)),
])
def test_options_that_assume_blocks_internals_are_refused_by_name(
        option, how, tmp_path):
    model, _ = model_and_params("float32")
    with pytest.raises(ValueError, match=f"has no '{option}'"):
        how(model, tmp_path)


def _build(tmp_path, **extra):
    from ddim_cold_tpu.train.trainer import build_model

    return build_model(_yaml(tmp_path, **extra))


def test_a_trunk_with_experts_is_refused_at_construction():
    with pytest.raises(ValueError, match="has no 'num_experts'"):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, num_experts=8))


@pytest.mark.parametrize("option,kwargs", [
    ("quant", dict(quant="xla")),
    ("fused", dict(fused=True)),
    ("cache_mode", dict(cache_interval=2)),
    ("sp_mode", dict(sp_mode="ring", sp_degree=2)),
])
def test_the_engine_refuses_sampler_configs_by_name(option, kwargs):
    model, params = model_and_params("float32")
    eng = serve.Engine(model, params, buckets=(4,))
    cfg = serve.SamplerConfig(k=500, **kwargs)
    with pytest.raises(ValueError, match=f"has no '{option}'"):
        eng.submit(seed=1, n=1, config=cfg)
    with pytest.raises(ValueError, match=f"has no '{option}'"):
        eng.ensure_program(cfg, 4)


def test_x004_the_trunk_adds_no_program_class(monkeypatch):
    from ddim_cold_tpu.analysis import config_checks

    assert config_checks.check_hybrid_refusals() == []
    monkeypatch.setattr(hybrid, "sampler_config_refusal", lambda cfg: None)
    found = config_checks.check_hybrid_refusals()
    assert found and all(f.rule == "GRAFT-X004" for f in found)
