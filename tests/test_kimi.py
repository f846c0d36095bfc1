"""models/kimi.py at toy size (the benchmark's own toy configuration,
``benchmark/tests/fixtures_kimi``: hidden 64, four layers — delta, delta,
latent, delta attention by the two lists, the first with the dense MLP, the
others with experts — 4 delta heads of a 16 x 16 state, 2 latent heads of 128
+ 64 / 128, the smallest the attention launch addresses, 16 router outputs
top-3 under a sigmoid with a selection bias, experts 0-7 held; 32x64 px patch
4 = 129 tokens: one chunk of the scan and one token) on seeded weights,
against the plain reference (``benchmark/reference/kimi.py``, which imports
nothing of the program and scans token by token): the forward, the two lists,
the unrotated latent attention and its column order, the head-wise gated
norm, the DDIM trajectory, causality, serving, refusals, scopes and
counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_kimi
from benchmark.reference import kimi as ref
from benchmark.reference import lowprec
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import hybrid, kimi, pangu
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import sampling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "fixtures_kimi",
                       "benchmark", "configs", "toy_kimi.json")) as f:
    TOY = json.load(f)


def config(precision, **changes):
    return {**TOY, "precision": precision, **changes}


def lists(kda, full):
    return dict(TOY["linear_attn_config"], kda_layers=kda,
                full_attn_layers=full)


TRUNK = weights_kimi.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_kimi.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_kimi.trunk_of(cfg), img_size=tuple(cfg["img_size"]),
        patch_size=4, total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_kimi.make(cfg, seed)


def forward(model, params, x, t):
    return jax.jit(model.apply)({"params": params}, x, t)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 32, 64, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    """To 2e-4 relative: both sides are float32 with float32 products (the
    suite pins the matmul precision), and differ in the order of their sums —
    the program's scan over chunks of 128 through a triangular solve (129
    tokens: the state crosses a chunk's edge and the last chunk holds one
    token), whole-matrix attention and sorted expert rows against the
    reference's token-by-token delta rule, per-block softmax and per-expert
    loops. A bfloat16 state or a clamped decay is a hundred times that
    (``tests/test_kda.py`` shows both)."""
    model, params = model_and_params("float32")
    x, t = inputs()
    got = forward(model, params, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree, an expert that flips at a near-tie included; the float8
#: control reads several times that
BF16_FORWARD_RMS = 3e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = forward(model, params, x, t)
    control = reference_forward(params, x, t, ops=lowprec.FP8)
    assert rms(got, want) < BF16_FORWARD_RMS < rms(control, want), (
        rms(got, want), rms(control, want))


def test_the_mixer_kind_is_read_from_the_two_lists_by_the_published_number():
    """Layer i of a slice is published layer ``layers_from + i``, number
    ``layers_from + i + 1`` in the lists, which count from 1: the leaves a
    layer declares are its kind's, and a slice that starts further down reads
    further down the lists."""
    assert [kimi.layer_kind(TRUNK, i) for i in range(4)] == [
        "kda", "kda", "mla", "kda"]
    _, params = model_and_params("float32")
    for i, kind in enumerate(["kda", "kda", "mla", "kda"]):
        mixer = params[f"layers_{i}"]["self_attn"]
        assert ("A_log" in mixer, "kv_b_proj" in mixer) == (
            kind == "kda", kind == "mla")
    # the leading layer alone is dense (first_k_dense_replace 1)
    assert ["router" in params[f"layers_{i}"]["mlp"] for i in range(4)] == [
        False, True, True, True]
    # layers 2 and 3 of the published stack, numbers 3 and 4: latent, delta
    cut = dict(layers_from=2, num_hidden_layers=2)
    model, cut_params = model_and_params("float32", **cut)
    trunk = dict(TRUNK, **cut)
    assert [kimi.layer_kind(trunk, i) for i in range(2)] == ["mla", "kda"]
    assert "kv_b_proj" in cut_params["layers_0"]["self_attn"]
    assert "A_log" in cut_params["layers_1"]["self_attn"]
    assert all("router" in cut_params[f"layers_{i}"]["mlp"] for i in range(2))
    x, t = inputs(1)
    np.testing.assert_allclose(
        forward(model, cut_params, x, t),
        reference_forward(cut_params, x, t, trunk=trunk), rtol=2e-4, atol=2e-5)


def test_latent_attention_is_the_published_formula_without_a_rotation():
    """``q_proj`` and ``kv_b_proj`` hold all the heads' first parts, then all
    their second parts; published weights have a head's parts side by side.
    ``pangu.published_columns`` is the permutation: the layer fed the permuted
    published weight computes what the published formula does head by head —
    q straight from the normed input (no query latent), the 64 second dims of
    q and the one shared ``k_r`` as the projections wrote them (nothing is
    turned), the scale 192^-1/2 — and so does the reference."""
    H, nope, rot, vd, rank = 2, 128, 64, 128, 32
    _, params = model_and_params("float32")
    p = params["layers_2"]["self_attn"]
    assert sorted(p) == ["kv_a_layernorm", "kv_a_proj_with_mqa", "kv_b_proj",
                         "o_proj", "q_proj"]
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 9, 64))
    # a "published" tree: undo the permutation, then read it head by head
    undo = lambda w, a, b: w[:, np.argsort(pangu.published_columns(H, a, b))]
    q = (y @ undo(p["q_proj"]["kernel"], nope, rot)).reshape(
        1, 9, H, nope + rot)
    kv_a = y @ p["kv_a_proj_with_mqa"]["kernel"]
    kv = (ref.rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], 1e-5)
          @ undo(p["kv_b_proj"]["kernel"], nope, vd)).reshape(
        1, 9, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        kv_a[:, :, None, rank:], (1, 9, H, rot))], -1)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * 192 ** -0.5
    attn = jax.nn.softmax(jnp.where(np.tril(np.ones((9, 9), bool)), logits,
                                    -jnp.inf), -1)
    want = jnp.einsum("bhnm,bmhd->bnhd", attn, kv[..., nope:]).reshape(
        1, 9, H * vd) @ p["o_proj"]["kernel"]
    got = kimi.NopeLatentAttention(TRUNK).apply({"params": p}, y)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(ref.mla(p, y, TRUNK, ref.vit.EXACT), want,
                               rtol=2e-4, atol=2e-6)
    # and the permutation matters: the tree read as if it were published
    as_is = (y @ p["q_proj"]["kernel"]).reshape(1, 9, H, nope + rot)
    assert float(jnp.abs(as_is - q).max()) > 0.1


def test_delta_attention_is_the_reference_and_its_norm_is_a_heads_own():
    """One delta-attention mixer against the reference's token-by-token one;
    the output norm takes its variance over each head's 16 channels with ONE
    gain of 16 for all four heads, and the gate multiplies AFTER it."""
    _, params = model_and_params("float32")
    p = params["layers_1"]["self_attn"]
    assert p["o_norm"]["scale"].shape == (16,)
    assert p["A_log"].shape == (4,) and p["dt_bias"].shape == (64,)
    assert all(p[f"{part}_conv1d"]["conv1d_kernel"].shape == (4, 64)
               for part in "qkv")
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 70, 64))
    got = kimi.DeltaAttention(TRUNK).apply({"params": p}, y)
    np.testing.assert_allclose(got, ref.kda(p, y, TRUNK, ref.vit.EXACT),
                               rtol=2e-4, atol=2e-6)
    o = jax.random.normal(jax.random.PRNGKey(6), (2, 5, 64))
    gate = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 64))
    heads = np.asarray(o).reshape(2, 5, 4, 16)
    want = (heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-5)
            * np.asarray(p["o_norm"]["scale"])).reshape(2, 5, 64) / (
        1 + np.exp(-np.asarray(gate)))
    np.testing.assert_allclose(
        kimi.HeadwiseGatedRMSNorm(16, 1e-5).apply(
            {"params": p["o_norm"]}, o, gate), want, rtol=1e-5, atol=1e-6)


def test_ddim_sample_follows_the_reference_trajectory():
    """k = 500: the four reverse steps from the same start noise."""
    model, params = model_and_params("float32")
    key = jax.random.PRNGKey(11)
    got = sampling.ddim_sample(model, params, key, k=500, n=2)
    x_init = jax.random.normal(key, (2, 32, 64, 3), jnp.float32)
    want = ref.sample(params, x_init, k=500, total_steps=2000, trunk=TRUNK,
                      patch_size=4)
    assert got.shape == (2, 32, 64, 3)
    assert rms(got, want) < 2e-5, rms(got, want)


def test_the_whole_trunk_is_causal_in_raster_order():
    model, params = model_and_params("float32")
    x, steps = inputs(2)
    run = lambda x: forward(model, params, x, steps)
    # rows of pixels 16.. are patches 64..127 = tokens 65..128
    moved, base = run(x.at[:, 16:].add(1.0)), run(x)
    np.testing.assert_allclose(moved[:, :16], base[:, :16], atol=1e-6)
    assert float(jnp.abs(moved[:, 16:] - base[:, 16:]).max()) > 1e-3


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
    """Every path is plain JAX off the TPU (published layers 1 and 2, a delta
    and a latent layer with experts, on 17 tokens)."""
    model, params = model_and_params("float32", layers_from=1,
                                     num_hidden_layers=2, img_size=[16, 16])
    x, t = inputs(1)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x[:, :16, :16], t) ** 2)))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    delta = norms["layers_0"]["self_attn"]
    latent = norms["layers_1"]["self_attn"]
    assert delta["A_log"] > 0 and delta["dt_bias"] > 0
    assert delta["k_conv1d"]["conv1d_kernel"] > 0
    assert delta["b_proj"]["kernel"] > 0 and delta["f_a_proj"]["kernel"] > 0
    assert latent["q_proj"]["kernel"] > 0 and latent["kv_b_proj"]["kernel"] > 0
    assert norms["layers_0"]["mlp"]["router"] > 0
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))


@pytest.mark.parametrize("change,match", [
    (dict(linear_attn_config=lists([1, 2], [3])),
     "layer 4 .* is in neither of linear_attn_config.kda_layers"),
    (dict(linear_attn_config=lists([1, 2, 3, 4], [3])), "layer 3 .* in both"),
    (dict(mla_use_nope=False), "mla_use_nope False"),
    (dict(q_lora_rank=1536), "q_lora_rank 1536"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(moe_router_activation_func="softmax"), "moe_router_activation_func"),
    (dict(num_expert_group=2), "num_expert_group 2"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(qk_rope_head_dim=32), "rot 32"),
    (dict(num_attention_heads=3), "an even number of heads"),
    (dict(experts_held_from=9), "held of 16 routed"),
    (dict(model_type="llama"), "'nemotron_h', 'kimi_linear'"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_and_refuses_blocks_options():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (kimi.check_trunk, kimi.layer)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})
    # the published lists reach the stack as they are, nested group and all
    assert model.trunk["linear_attn_config"]["kda_layers"] == (1, 2, 4)
    assert hash(model) is not None  # jit's static argument


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/kda | mla | mlp | moe | route`` in the lowered text; one count a traced
    scan and a traced latent attention, three convolutions a delta layer,
    three products an expert layer."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/kda", "trunk/mla", "trunk/mlp", "trunk/moe",
                  "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.kda_schedule", "kernels.flash_latent_schedule",
                     "kernels.moe_gmm_schedule", "kernels.ssd_schedule",
                     "kernels.causal_conv_schedule"):
            for key, count in series.get(name + "/by_key", {}).items():
                by_key[name, key] = by_key.get((name, key), 0) + count
    assert by_key == {("kernels.kda_schedule", "xla"): 3,
                      ("kernels.causal_conv_schedule", "xla"): 9,
                      ("kernels.flash_latent_schedule", "xla"): 1,
                      ("kernels.moe_gmm_schedule", "xla"): 9}
    metrics.reset()


def test_the_three_convolutions_are_the_other_mixers_piece_of_code():
    """``DeltaAttention``'s three short convolutions call
    ``hybrid.causal_conv_silu`` without a bias: one ``conv1d_kernel`` each and
    the written-out taps; q's and k's hand it their head size, and what comes
    back is the plain convolution's result L2-normed a head as the reference
    norms it."""
    import inspect

    u = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 6))
    got = kimi._ShortConv(4).apply({"params": {"conv1d_kernel": w}}, u)
    past = np.concatenate([np.zeros((2, 3, 6)), np.asarray(u)], axis=1)
    want = sum(np.asarray(w)[j] * past[:, j:j + 9] for j in range(4))
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(ref.conv_silu(w, u), got, rtol=1e-5, atol=1e-6)
    assert ("causal_conv_silu(self, u, self.taps, False, self.l2_head_dim)"
            in inspect.getsource(kimi._ShortConv))
    normed = kimi._ShortConv(4, 3).apply({"params": {"conv1d_kernel": w}}, u)
    heads = np.asarray(got).reshape(2, 9, 2, 3)
    np.testing.assert_allclose(
        normed, (heads / np.sqrt((heads * heads).sum(-1, keepdims=True) + 1e-6)
                 ).reshape(2, 9, 6), rtol=1e-5, atol=1e-6)


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    """The trainer's ``build_model`` on a yaml whose ``trunk:`` carries the
    published keys: the same stack, and ``use_flash`` refused by name."""
    import yaml

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model

    def build(**extra):
        raw = dict(image_size=[32, 64], patch_size=4, trunk=TRUNK, **extra)
        path = tmp_path / "kimi.yaml"
        path.write_text(yaml.safe_dump(raw))
        return build_model(load_config(str(path)))

    model = build()
    assert isinstance(model, hybrid.HybridDenoiser) and model.depth == 4
    assert hybrid.stack_of(model.trunk) == (kimi.check_trunk, kimi.layer)
    with pytest.raises(ValueError, match="use_flash"):
        build(use_flash=True)
