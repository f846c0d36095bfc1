"""models/nemotron.py at toy size (the benchmark's own toy configuration,
``benchmark/tests/fixtures_nemotron``: hidden 64, pattern ``ME*E`` — every
layer kind — 4 Mamba-2 heads of 8 x 16 state in 2 groups scanned over chunks
of 8, 4 query heads on 2 K/V heads of 16, 16 router outputs top-3 under a
sigmoid with a selection bias, experts 0-7 held, ungated, 32 -> 24 -> 32 in a
latent; 16x16 px patch 4 = 17 tokens: two chunks and one token) on seeded
weights, against the plain reference (``benchmark/reference/nemotron.py``,
which imports nothing of the program): the forward, the pattern, the gate and
the grouped norm, attention without a position term, the DDIM trajectory,
causality, serving, refusals, scopes and counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_nemotron
from benchmark.reference import lowprec
from benchmark.reference import nemotron as ref
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import hybrid, nemotron
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import sampling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "fixtures_nemotron",
                       "benchmark", "configs", "toy_nemotron.json")) as f:
    TOY = json.load(f)


def config(precision, **changes):
    return {**TOY, "precision": precision, **changes}


TRUNK = weights_nemotron.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_nemotron.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_nemotron.trunk_of(cfg), img_size=(16, 16), patch_size=4,
        total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_nemotron.make(cfg, seed)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    """To 2e-4 relative: both sides are float32 with float32 products (the
    suite pins the matmul precision), and differ in the order of their sums —
    the program's scan over chunks of 8 (17 tokens: the state crosses two
    chunk edges and the last chunk holds one token), blockwise attention and
    sorted expert rows against the reference's token-by-token recurrence,
    per-block softmax and per-expert loops."""
    model, params = model_and_params("float32")
    x, t = inputs()
    got = model.apply({"params": params}, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree, an expert that flips at a near-tie included; the float8
#: control reads several times that
BF16_FORWARD_RMS = 6e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = model.apply({"params": params}, x, t)
    control = reference_forward(params, x, t, ops=lowprec.FP8)
    assert rms(got, want) < BF16_FORWARD_RMS < rms(control, want), (
        rms(got, want), rms(control, want))


def test_the_layer_kind_is_read_from_the_pattern_by_index():
    """Layer i is letter i: the leaves a layer declares are its letter's, and
    another pattern over the same sizes is another stack."""
    assert [nemotron.layer_kind(TRUNK, i) for i in range(4)] == list("ME*E")
    model, params = model_and_params("float32")
    leaf = {"M": "A_log", "*": "q_proj", "E": "router"}
    for i, kind in enumerate("ME*E"):
        mixer = params[f"layers_{i}"]["mixer"]
        assert [k in mixer for k in leaf.values()] == [
            k == leaf[kind] for k in leaf.values()]
    x, t = inputs(1)
    other, other_params = model_and_params("float32",
                                           hybrid_override_pattern="E*MM")
    assert "router" in other_params["layers_0"]["mixer"]
    assert "A_log" in other_params["layers_3"]["mixer"]
    np.testing.assert_allclose(
        other.apply({"params": other_params}, x, t),
        reference_forward(other_params, x, t,
                          trunk=dict(TRUNK, hybrid_override_pattern="E*MM")),
        rtol=2e-4, atol=2e-5)
    # a longer pattern than the stack is deep is read as far as the depth
    hybrid.HybridDenoiser(trunk=dict(TRUNK, hybrid_override_pattern="ME*EMM-"))


def test_the_gate_comes_before_the_grouped_norm_and_the_groups_are_apart():
    """``RMSNorm(y * SiLU(z))`` over each of the G = 2 runs of 16 channels by
    itself: scaling one run of y scales nothing (its own norm undoes it, the
    other run never sees it); norm-then-gate is another function."""
    norm = nemotron.GatedGroupRMSNorm(groups=2, eps=1e-5)
    y, z = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 9, 32))
    p = {"params": {"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(6), (32,))}}
    got = norm.apply(p, y, z)
    gated = y * jax.nn.silu(z)
    runs = gated.reshape(3, 9, 2, 16)
    want = (runs / jnp.sqrt((runs ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 9, 32) * p["params"]["scale"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    louder = norm.apply(p, y.at[..., :16].multiply(10.0), z)
    np.testing.assert_allclose(louder, got, rtol=1e-3, atol=1e-4)
    whole = gated / jnp.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    assert rms(whole * p["params"]["scale"], got) > 0.05  # one group is not two
    flat = y / jnp.sqrt((y.reshape(3, 9, 2, 16) ** 2).mean(-1, keepdims=True)
                        + 1e-5).repeat(16, -1).reshape(3, 9, 32)
    assert rms(flat * p["params"]["scale"] * jax.nn.silu(z), got) > 0.05


def test_attention_has_no_position_term():
    """A token's context is a set: the last token's output does not change
    when the tokens before it change places (a rotary or any other position
    term inside the layer would move it), and it is the reference's."""
    _, params = model_and_params("float32")
    p = params["layers_2"]["mixer"]
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 11, 64))
    layer = nemotron.CausalSharedKVAttention(TRUNK)
    got = layer.apply({"params": p}, y)
    np.testing.assert_allclose(got, ref.attention(p, y, TRUNK, ref.vit.EXACT),
                               rtol=2e-4, atol=2e-6)
    moved = jnp.concatenate([y[:, 9:0:-1], y[:, :1], y[:, 10:]], axis=1)
    np.testing.assert_allclose(layer.apply({"params": p}, moved)[:, -1],
                               got[:, -1], rtol=1e-4, atol=1e-6)
    # 4 query heads on 2 K/V heads: query head h reads K/V head h // 2
    q = (y @ p["q_proj"]["kernel"]).reshape(1, 11, 4, 16)
    k = (y @ p["k_proj"]["kernel"]).reshape(1, 11, 2, 16)
    v = (y @ p["v_proj"]["kernel"]).reshape(1, 11, 2, 16)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, jnp.repeat(k, 2, 2)) / 4.0
    attn = jax.nn.softmax(jnp.where(np.tril(np.ones((11, 11), bool)), logits,
                                    -jnp.inf), -1)
    want = jnp.einsum("bhnm,bmhd->bnhd", attn, jnp.repeat(v, 2, 2)).reshape(
        1, 11, 64) @ p["o_proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_the_mixers_columns_lie_in_the_published_order():
    """``in_proj``'s columns are ``[z, xBC, dt]`` and the convolution's ``[x,
    B, C]``, as published and as the configuration file says: a mixer whose
    dt columns are zeroed runs at Delta = softplus(dt_bias) for every token,
    and one whose z columns are zeroed gates everything to zero."""
    _, params = model_and_params("float32")
    p = params["layers_0"]["mixer"]
    y = jax.random.normal(jax.random.PRNGKey(8), (1, 17, 64))
    mixer = nemotron.Mamba2Mixer(TRUNK)
    d, shared, heads = 32, 2 * 2 * 16, 4
    assert p["in_proj"]["kernel"].shape == (64, 2 * d + shared + heads)
    no_z = dict(p, in_proj={"kernel": p["in_proj"]["kernel"].at[:, :d].set(0.0)})
    assert float(jnp.abs(mixer.apply({"params": no_z}, y)).max()) == 0.0
    no_dt = dict(p, in_proj={"kernel": p["in_proj"]["kernel"].at[:, -heads:].set(0.0)})
    got = mixer.apply({"params": no_dt}, y)
    np.testing.assert_allclose(got, ref.mamba2(no_dt, y, TRUNK, ref.vit.EXACT),
                               rtol=2e-4, atol=2e-6)
    assert float(jnp.abs(got - mixer.apply({"params": p}, y)).max()) > 1e-5


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


def test_the_whole_trunk_is_causal_in_raster_order():
    model, params = model_and_params("float32")
    x, steps = inputs(2)
    run = lambda x: model.apply({"params": params}, x, steps)
    # rows of pixels 8.. are patches 8..15 = tokens 9..16: past a chunk's edge
    moved, base = run(x.at[:, 8:].add(1.0)), run(x)
    np.testing.assert_allclose(moved[:, :8], base[:, :8], atol=1e-6)
    assert float(jnp.abs(moved[:, 8:] - base[:, 8:]).max()) > 1e-3


def test_a_request_through_the_engine_matches_the_direct_call():
    """Within 1e-6, a padded bucket (n = 3 in 4) in the comparison."""
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
    with pytest.raises(ValueError, match="quant"):
        eng.submit(seed=1, n=1, config=serve.SamplerConfig(k=500, quant="w8a16"))


def test_gradients_flow_off_the_chip():
    """Every path is plain JAX off the TPU."""
    model, params = model_and_params("float32")
    x, t = inputs(1)
    grads = jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x, t) ** 2))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert norms["layers_0"]["mixer"]["A_log"] > 0
    assert norms["layers_0"]["mixer"]["dt_bias"] > 0
    assert norms["layers_0"]["mixer"]["conv1d_kernel"] > 0
    assert norms["layers_1"]["mixer"]["fc1_latent_proj"]["kernel"] > 0
    assert norms["layers_1"]["mixer"]["router"] > 0
    assert norms["layers_2"]["mixer"]["k_proj"]["kernel"] > 0
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))


@pytest.mark.parametrize("change,match", [
    (dict(hybrid_override_pattern="ME-E"), "'-', the family's dense MLP"),
    (dict(hybrid_override_pattern="MEXE"), "'X' is no layer kind"),
    (dict(hybrid_override_pattern="ME*"), "has no layer 3"),
    (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(n_group=2), "n_group 2"),
    (dict(time_step_limit=(0.0, 1.0)), "time_step_limit"),
    (dict(sliding_window=512), "sliding_window"),
    (dict(mamba_num_heads=3), "mamba_num_heads 3"),
    (dict(experts_held_from=9), "held of 16 routed"),
    (dict(model_type="llama"), "'nemotron_h', 'kimi_linear'"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_and_refuses_blocks_options():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (nemotron.check_trunk, nemotron.layer)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})
    # the norms' epsilon is read under the published key
    assert "rms_norm_eps" not in model.trunk
    assert hybrid.norm_eps(model.trunk) == model.trunk["layer_norm_epsilon"]


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/mamba2 | attn | moe | route`` in the lowered text; one count a traced
    scan, two products an expert layer (up under its squared ReLU, down) and
    no gated first half."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/mamba2", "trunk/attn", "trunk/moe", "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.ssd_schedule", "kernels.ssm_scan_schedule",
                     "kernels.moe_gmm_schedule",
                     "kernels.moe_gate_up_schedule",
                     "kernels.causal_conv_schedule"):
            for key, count in series.get(name + "/by_key", {}).items():
                by_key[name, key] = by_key.get((name, key), 0) + count
    assert by_key == {("kernels.ssd_schedule", "xla"): 1,
                      ("kernels.causal_conv_schedule", "xla"): 1,
                      ("kernels.moe_gmm_schedule", "xla"): 4}
    metrics.reset()


def test_the_convolution_is_one_piece_of_code_for_both_mamba_mixers():
    """``hybrid.MambaMixer`` and ``nemotron.Mamba2Mixer`` call
    ``hybrid.causal_conv_silu``: the same two leaves under the same names, and
    the written-out taps."""
    import flax.linen as nn

    class Conv(nn.Module):
        dtype = jnp.float32
        param_dtype = jnp.float32

        @nn.compact
        def __call__(self, u):
            return hybrid.causal_conv_silu(self, u, 4, True)

    u = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(5), (6,))
    got = Conv().apply({"params": {"conv1d_kernel": w, "conv1d_bias": b}}, u)
    past = np.concatenate([np.zeros((2, 3, 6)), np.asarray(u)], axis=1)
    want = sum(np.asarray(w)[j] * past[:, j:j + 9] for j in range(4)) + np.asarray(b)
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    _, params = model_and_params("float32")
    assert {"conv1d_kernel", "conv1d_bias"} <= set(params["layers_0"]["mixer"])
    import inspect
    assert "causal_conv_silu(" in inspect.getsource(hybrid.MambaMixer)
    assert "causal_conv_silu(" in inspect.getsource(nemotron.Mamba2Mixer)


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    """The trainer's ``build_model`` on a yaml whose ``trunk:`` carries the
    published keys: the same stack, and ``use_flash`` refused by name."""
    import yaml

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model

    def build(**extra):
        raw = dict(image_size=[16, 16], patch_size=4, trunk=TRUNK, **extra)
        path = tmp_path / "nemotron.yaml"
        path.write_text(yaml.safe_dump(raw))
        return build_model(load_config(str(path)))

    model = build()
    assert isinstance(model, hybrid.HybridDenoiser) and model.depth == 4
    assert hybrid.stack_of(model.trunk) == (nemotron.check_trunk, nemotron.layer)
    with pytest.raises(ValueError, match="use_flash"):
        build(use_flash=True)
