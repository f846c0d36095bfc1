"""models/pangu.py at toy size (hidden 64, 2 heads of 128 + 64 query/key dims
and 128 value dims — the smallest the attention launch addresses — on a
16-dim key/value latent, published layers 2-4 with ``first_k_dense_replace``
3: one dense layer and two expert layers; 16 router outputs top-3 under a
sigmoid without a bias, experts 0-7 held; 16x16 px patch 4) on seeded
weights, against the plain reference (``benchmark/reference/pangu.py``, which
imports nothing of the program): the forward, the sandwich norms, the column
order of the two up-projections, the DDIM trajectory, causality, serving,
refusals, scopes and counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_pangu
from benchmark.reference import lowprec
from benchmark.reference import pangu as ref
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import glm, hybrid, pangu
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import sampling
from ddim_cold_tpu.ops.rotary import apply_rotary

PUBLISHED = dict(
    model_type="pangu_ultra_moe", hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=2, num_key_value_heads=2,
    hidden_act="silu", attention_bias=False, rms_norm_eps=1e-5,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=25600000,
    sandwich_norm=True, first_k_dense_replace=3, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=3, moe_intermediate_size=32,
    norm_topk_prob=True, routed_scaling_factor=2.5)
SIZES = dict(img_size=[16, 16], patch_size=4, in_chans=3, total_steps=2000)


def config(precision, **changes):
    return {**PUBLISHED, **SIZES, "precision": precision, "layers_from": 2,
            "source_values": {"n_routed_experts": 16}, "experts_held_from": 0,
            **changes}


TRUNK = weights_pangu.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_pangu.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_pangu.trunk_of(cfg), img_size=(16, 16), patch_size=4,
        total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_pangu.make(cfg, seed)


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
    the program's blockwise XLA attention and sorted expert rows against the
    reference's per-block softmax and per-expert loops."""
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


def test_every_sub_layers_result_is_normed_before_it_is_added():
    """The sandwich: a layer's change to x has the rms its second norm's gain
    gives it, whatever the size of the sub-layer's weights — ten times
    ``o_proj`` changes nothing once eps is out of sight — and the four norms
    are four leaves."""
    model, params = model_and_params("float32")
    layer = pangu.PanguLayer(model.trunk, 0)  # published layer 2: dense
    p = params["layers_0"]
    assert {k for k in p if k.endswith("layernorm")} == {
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm"}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 17, 64))
    got = layer.apply({"params": p}, x)
    np.testing.assert_allclose(got, ref.layer(p, x, TRUNK, 0), rtol=2e-4,
                               atol=2e-5)
    louder = lambda by: layer.apply({"params": dict(p, self_attn=dict(
        p["self_attn"], o_proj={
            "kernel": by * p["self_attn"]["o_proj"]["kernel"]}))}, x)
    # (from 100 on: at the toy's 0.008 of rms eps still shows at o_proj x 1)
    np.testing.assert_allclose(louder(1000.0), louder(100.0), rtol=1e-2,
                               atol=1e-3)
    # the pre-norm layer of the same weights is another function
    plain = x + ref.attention(
        p["self_attn"], ref.rms_norm(x, p["input_layernorm"], 1e-5), TRUNK,
        ref.vit.EXACT)
    assert rms(plain, got) > 0.02  # sub-layers of a twentieth of a unit


def test_a_published_order_weight_maps_onto_the_trees_columns():
    """``q_b_proj`` and ``kv_b_proj`` hold all the heads' first parts, then
    all their second parts; published weights have a head's parts side by
    side. ``published_columns`` is the permutation: a layer fed the permuted
    published weight computes what the published formula does head by head."""
    H, nope, rot, vd = 2, 128, 64, 128
    cols = pangu.published_columns(H, nope, rot)
    assert sorted(cols) == list(range(H * (nope + rot)))
    # head 1's nope part: published columns 192..319, here 128..255
    assert cols[nope:2 * nope].tolist() == list(range(192, 320))
    assert cols[H * nope:H * nope + rot].tolist() == list(range(128, 192))
    _, params = model_and_params("float32")
    p = params["layers_1"]["self_attn"]
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 9, 64))
    # a "published" tree: undo the permutation, then read it head by head
    undo = lambda w, a, b: w[:, np.argsort(pangu.published_columns(H, a, b))]
    w_q = undo(p["q_b_proj"]["kernel"], nope, rot)
    w_kv = undo(p["kv_b_proj"]["kernel"], nope, vd)
    c_q = ref.rms_norm(y @ p["q_a_proj"]["kernel"], p["q_a_layernorm"], 1e-5)
    q = (c_q @ w_q).reshape(1, 9, H, nope + rot)
    kv_a = y @ p["kv_a_proj_with_mqa"]["kernel"]
    kv = (ref.rms_norm(kv_a[..., :16], p["kv_a_layernorm"], 1e-5)
          @ w_kv).reshape(1, 9, H, nope + vd)
    q_r = ref.rotary(q[..., nope:], 25600000.0, 0, rot, False)
    k_r = ref.rotary(kv_a[:, :, None, 16:], 25600000.0, 0, rot, False)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, q_r.shape)], -1)
    logits = jnp.einsum("bnhd,bmhd->bhnm",
                        jnp.concatenate([q[..., :nope], q_r], -1), k) * 192 ** -0.5
    attn = jax.nn.softmax(jnp.where(np.tril(np.ones((9, 9), bool)), logits,
                                    -jnp.inf), -1)
    want = jnp.einsum("bhnm,bmhd->bnhd", attn, kv[..., nope:]).reshape(
        1, 9, H * vd) @ p["o_proj"]["kernel"]
    got = pangu.DenseLatentAttention(TRUNK).apply({"params": p}, y)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(ref.attention(p, y, TRUNK, ref.vit.EXACT), want,
                               rtol=2e-4, atol=2e-6)


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
    # rows of pixels 8.. are patches 8..15 = tokens 9..16
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
    x, t = inputs(2)
    grads = jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x, t) ** 2))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert norms["layers_1"]["self_attn"]["kv_b_proj"]["kernel"] > 0
    assert norms["layers_1"]["self_attn"]["q_b_proj"]["kernel"] > 0
    assert norms["layers_2"]["mlp"]["router"] > 0
    assert norms["layers_0"]["post_attention_layernorm"]["scale"] > 0
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))


@pytest.mark.parametrize("change,match", [
    (dict(sandwich_norm=False), "sandwich_norm False"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(qk_nope_head_dim=192), "nope 192, rot 64, vd 128"),
    (dict(qk_rope_head_dim=32), "rot 32"),
    (dict(v_head_dim=64), "vd 64"),
    (dict(num_attention_heads=3), "an even number of heads"),
    (dict(experts_held_from=9), "held of 16 routed"),
    (dict(model_type="llama"), "'nemotron_h', 'kimi_linear'"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_and_refuses_blocks_options():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (pangu.check_trunk, pangu.layer)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})
    # a null rope_scaling and value heads as wide as two lane groups pass
    hybrid.HybridDenoiser(trunk=dict(TRUNK, rope_scaling=None, v_head_dim=256))


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/mla | moe | route | mlp`` in the lowered text; one count a traced
    attention by path and by mask, three products an expert layer."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/mla", "trunk/moe", "trunk/mlp", "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.flash_latent_schedule", "kernels.flash_fwd_mask",
                     "kernels.moe_gmm_schedule",
                     "kernels.moe_gate_up_schedule"):
            for key, count in series.get(name + "/by_key", {}).items():
                by_key[name, key] = by_key.get((name, key), 0) + count
    assert by_key == {("kernels.flash_latent_schedule", "xla"): 3,
                      ("kernels.moe_gmm_schedule", "xla"): 6,
                      ("kernels.moe_gate_up_schedule", "xla"): 2}
    metrics.reset()


def test_the_latent_projections_are_one_piece_of_code_for_both_stacks():
    """``pangu.DenseLatentAttention`` calls ``glm.latent_projections``, GLM's
    ``LatentAttention`` ``glm.latent_paths`` and its own in-place
    ``kv_b_proj``: the same six leaves, and from one latent the two column
    orders give the same heads."""
    import flax.linen as nn

    rope = pangu._rope(TRUNK)
    kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)

    class Apart(nn.Module):  # what DenseLatentAttention runs
        @nn.compact
        def __call__(self, y):
            return glm.latent_projections(TRUNK, y, rope, "rotate_half", **kw)

    class Published(nn.Module):  # what glm.LatentAttention runs
        @nn.compact
        def __call__(self, y):
            c_q, q, k_r, c_kv = glm.latent_paths(TRUNK, y, rope, "rotate_half",
                                                 **kw)
            return c_q, q, k_r, glm._KeysAndValuesInPlace(
                2, 128, 128, name="kv_b_proj", **kw)(c_kv, k_r)

    _, params = model_and_params("float32")
    p = params["layers_0"]["self_attn"]
    six = {k: v for k, v in p.items() if k != "o_proj"}
    y = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 64))
    c_q, (q_nope, q_r), k_r, (k_nope, v) = Apart().apply({"params": six}, y)
    # the same weights in the published order, through the published path
    undo = lambda w, a, b: w[:, np.argsort(pangu.published_columns(2, a, b))]
    published = dict(six,
                     q_b_proj={"kernel": undo(p["q_b_proj"]["kernel"], 128, 64)},
                     kv_b_proj={"kernel": undo(p["kv_b_proj"]["kernel"], 128, 128)})
    c_q2, q, k_r2, (k, v2) = Published().apply({"params": published}, y)
    np.testing.assert_array_equal(c_q, c_q2)
    np.testing.assert_array_equal(k_r, k_r2)
    # the published path hands q on unturned, for its reader's launch to turn
    q = apply_rotary(q, 2, *rope, first=128)
    q, k = q.reshape(1, 5, 2, 192), k.reshape(1, 5, 2, 192)
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    close(q_nope.reshape(1, 5, 2, 128), q[..., :128])
    close(q_r.reshape(1, 5, 2, 64), q[..., 128:])
    close(k_nope.reshape(1, 5, 2, 128), k[..., :128])
    # every key head ends in the ONE shared k_r, placed there bit for bit
    np.testing.assert_array_equal(
        k[..., 128:], np.broadcast_to(np.asarray(k_r)[:, :, None], (1, 5, 2, 64)))
    close(v, v2)


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    """The trainer's ``build_model`` on a yaml whose ``trunk:`` carries the
    published keys: the same stack, and ``use_flash`` refused by name."""
    import yaml

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model

    def build(**extra):
        raw = dict(image_size=[16, 16], patch_size=4, trunk=TRUNK, **extra)
        path = tmp_path / "pangu.yaml"
        path.write_text(yaml.safe_dump(raw))
        return build_model(load_config(str(path)))

    model = build()
    assert isinstance(model, hybrid.HybridDenoiser) and model.depth == 3
    assert hybrid.stack_of(model.trunk) == (pangu.check_trunk, pangu.layer)
    with pytest.raises(ValueError, match="use_flash"):
        build(use_flash=True)
