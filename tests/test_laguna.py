"""models/laguna.py at toy size (hidden 64, head size 16, 2 K/V heads, heads
[4, 6, 6, 6, 4] by layer, window 8, 16 router outputs top-3 with experts 0-7
held, expert and shared width 32, dense width 128, 16x16 px patch 4 -> 17
tokens, YaRN with ``original_max_position_embeddings`` 8) on seeded weights,
against the plain reference (``benchmark/reference/laguna.py``, which imports
nothing of the program): the forward, the shares of the expert layer, the
rotary frequencies, the DDIM trajectory, gradients, causality and the
window's reach, serving, building from a yaml, and each option the trunk
refuses by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_laguna
from benchmark.reference import laguna as ref
from benchmark.reference import lowprec
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import hybrid, laguna, moe
from ddim_cold_tpu.ops import sampling

ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8, "beta_slow": 1, "beta_fast": 32,
        "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
PUBLISHED = dict(
    model_type="laguna", hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, attention_bias=False, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], gating="per-head",
    sliding_window=8, rope_parameters=ROPE,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3,  # longer than the depth
    moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense"] + ["sparse"] * 7, gating_types=["per_head"] * 8,
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6, 6, 6],
    moe_router_logit_softcapping=0)
SIZES = dict(img_size=[16, 16], patch_size=4, in_chans=3, total_steps=2000)


def config(precision, **changes):
    return {**PUBLISHED, **SIZES, "precision": precision,
            "source_values": {"num_experts": 16}, "experts_held_from": 0,
            **changes}


TRUNK = weights_laguna.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_laguna.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_laguna.trunk_of(cfg), img_size=(16, 16), patch_size=4,
        total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_laguna.make(cfg, seed)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    model, params = model_and_params("float32")
    x, t = inputs()
    got = model.apply({"params": params}, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_forward_through_the_launch_that_turns_q(precision, monkeypatch):
    """What the TPU's path does to q, interpreted: every layer hands
    ``fwd_masked`` the q that ``q_proj`` wrote with its rotation beside it
    (``kernels.flash_fwd_rotary`` = ``kernel``, once a layer) and the launch
    turns it, in the padded lanes of a 16-dim head. Against the reference, as
    the forward that turns q with ``apply_rotary`` is held above."""
    from ddim_cold_tpu.obs import metrics
    from ddim_cold_tpu.ops import flash_attention as fa

    monkeypatch.setattr(laguna, "masked_attention", fa.flash_attention_masked)
    model, params = model_and_params(precision)
    x, t = inputs()
    metrics.reset()
    got = model.apply({"params": params}, x, t)
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"kernel": 5}
    metrics.reset()
    want = reference_forward(params, x, t)
    if precision == "float32":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
    else:
        assert rms(got, want) < BF16_FORWARD_RMS, rms(got, want)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree: bfloat16 rounding through 5 layers reads 1e-3 on outputs of
#: rms 0.16 (a row whose top-3 flips to another expert included); the float8
#: control reads 8e-3
BF16_FORWARD_RMS = 3e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = model.apply({"params": params}, x, t)
    control = reference_forward(params, x, t, ops=lowprec.FP8)
    assert rms(got, want) < BF16_FORWARD_RMS < rms(control, want), (
        rms(got, want), rms(control, want))


# ------------------------------------------------------- the expert layer

def _expert_layer(first, held, dtype=jnp.float32, **router):
    return moe.HeldExpertsMlp(
        num_routed=16, top_k=3, first_held=first, num_held=held,
        hidden_features=32, shared_features=32, scaling=2.5, dtype=dtype,
        param_dtype=dtype, **router)


def _uncut_tree(seed=3):
    """One sparse layer's ``mlp`` tree with all 16 experts held."""
    cfg = config("float32", num_experts=16)
    return weights_laguna.make(cfg, seed)["layers_1"]["mlp"]


def _share(tree, first, held):
    banks = {k: tree[k][first:first + held] for k in ref.BANKS if k in tree}
    return dict(tree, **banks)


def _softmax_router():
    """(uncut tree, the reference's layer given a share, the program's)."""
    tree = _uncut_tree()
    whole = lambda share, y: ref.sparse_mlp(
        _share(tree, *share), y,
        dict(TRUNK, experts_held_from=share[0], num_experts=share[1]))
    return tree, whole, _expert_layer


def _sigmoid_router_with_a_selection_bias():
    """GLM-5.2's router on the same banks: a sigmoid a router output, the top
    3 of score + bias chosen, weighed by the score alone."""
    from benchmark.reference import glm as glm_ref

    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    tree = dict(_uncut_tree(), e_score_correction_bias=bias)
    cfg = dict(num_experts_per_tok=3, norm_topk_prob=True,
               routed_scaling_factor=2.5)
    whole = lambda share, y: glm_ref.sparse_mlp(
        _share(tree, *share), y,
        dict(cfg, experts_held_from=share[0], n_routed_experts=share[1]))
    layer = lambda first, held: _expert_layer(
        first, held, score="sigmoid", selection_bias=True)
    # the bias does choose: without it other experts are taken
    y = jax.random.normal(jax.random.PRNGKey(2), (34, 64))
    unbiased = glm_ref.sparse_mlp(
        dict(tree, e_score_correction_bias=0 * bias), y,
        dict(cfg, n_routed_experts=16))
    assert float(jnp.abs(unbiased - whole((0, 16), y)).max()) > 1e-3
    return tree, whole, layer


def _sigmoid_router_without_a_bias():
    """openPangu-Ultra-MoE's router on the same banks: a sigmoid a router
    output, the top 3 of the scores chosen and weighed by them, x 2.5; the
    uncut layer is ``reference/pangu.py``'s."""
    from benchmark.reference import pangu as pangu_ref

    tree = _uncut_tree()
    cfg = dict(num_experts_per_tok=3, norm_topk_prob=True,
               routed_scaling_factor=2.5)
    whole = lambda share, y: pangu_ref.sparse_mlp(
        _share(tree, *share), y,
        dict(cfg, experts_held_from=share[0], n_routed_experts=share[1]))
    layer = lambda first, held: _expert_layer(first, held, score="sigmoid")
    # and it is not the softmax router under another name: the same experts
    # are chosen, and weighed a tenth otherwise (outputs of 2e-3)
    y = jax.random.normal(jax.random.PRNGKey(2), (34, 64))
    softmax = ref.sparse_mlp(tree, y, dict(TRUNK, num_experts=16))
    assert float(jnp.abs(softmax - whole((0, 16), y)).max()) > 1e-4
    return tree, whole, layer


def _ungated_experts_in_a_latent():
    """Nemotron-3-Super's ``E`` layer (the benchmark's toy configuration with
    all 16 experts held): a sigmoid a router output, the top 3 of score + bias
    chosen, ungated squared-ReLU experts 32 -> 24 -> 32 in a latent between
    one projection in and one out, the shared expert ungated on the full
    width; the uncut layer is ``reference/nemotron.py``'s."""
    import json
    import os

    from benchmark import weights_nemotron
    from benchmark.reference import nemotron as nemotron_ref

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "tests", "fixtures_nemotron", "benchmark",
                           "configs", "toy_nemotron.json")) as f:
        toy = dict(json.load(f), precision="float32", n_routed_experts=16)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    tree = dict(weights_nemotron.make(toy, 3)["layers_1"]["mixer"],
                e_score_correction_bias=bias)
    # at widths of 24 and 32 weights of std 0.02 leave the routed part at
    # 1e-5 beside a shared expert of 1e-3: louder, so that the sum is tested
    tree = dict(tree, up_proj=4 * tree["up_proj"],
                down_proj=4 * tree["down_proj"], fc1_latent_proj={
                    "kernel": 2 * tree["fc1_latent_proj"]["kernel"]})
    cfg = weights_nemotron.trunk_of(toy)
    whole = lambda share, y: nemotron_ref.latent_experts(
        _share(tree, *share), y,
        dict(cfg, experts_held_from=share[0], n_routed_experts=share[1]))
    layer = lambda first, held: moe.HeldExpertsMlp(
        num_routed=16, top_k=3, first_held=first, num_held=held,
        hidden_features=24, shared_features=48, scaling=5, score="sigmoid",
        selection_bias=True, hidden_act="relu2", latent_features=32)
    # the latent's way out is linear and has no bias: what it maps is the sum
    y = jax.random.normal(jax.random.PRNGKey(2), (34, 64))
    assert tree["up_proj"].shape == (16, 32, 24) and "gate_proj" not in tree
    assert float(jnp.abs(whole((0, 16), y) - whole((0, 4), y)).max()) > 1e-3
    return tree, whole, layer


def _kimi_experts_over_two_chips():
    """Kimi-Linear's expert layer (the benchmark's toy configuration with all
    16 experts held): a sigmoid a router output, the top 3 of score + bias
    chosen, weighed by the renormalised scores x 2.446, gated experts and one
    shared expert at 24; the uncut layer is ``reference/kimi.py``'s, under
    that configuration's own key names."""
    import json
    import os

    from benchmark import weights_kimi
    from benchmark.reference import kimi as kimi_ref

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "tests", "fixtures_kimi", "benchmark", "configs",
                           "toy_kimi.json")) as f:
        toy = dict(json.load(f), precision="float32", num_experts=16)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    tree = dict(weights_kimi.make(toy, 3)["layers_1"]["mlp"],
                e_score_correction_bias=bias)
    # at a width of 24 weights of std 0.02 leave the routed part at 1e-5
    # beside a shared expert of 1e-4: louder, so that the sum is tested
    tree.update({k: 6 * tree[k] for k in ref.BANKS})
    cfg = weights_kimi.trunk_of(toy)
    whole = lambda share, y: kimi_ref.sparse_mlp(
        _share(tree, *share), y,
        dict(cfg, experts_held_from=share[0], num_experts=share[1]))
    layer = lambda first, held: moe.HeldExpertsMlp(
        num_routed=16, top_k=3, first_held=first, num_held=held,
        hidden_features=24, shared_features=24, scaling=2.446,
        score="sigmoid", selection_bias=True)
    y = jax.random.normal(jax.random.PRNGKey(2), (34, 64))
    assert tree["gate_proj"].shape == (16, 64, 24)
    assert float(jnp.abs(whole((0, 16), y) - whole((0, 8), y)).max()) > 1e-3
    return tree, whole, layer


@pytest.mark.parametrize("router,chips", [
    (_softmax_router, 2), (_sigmoid_router_with_a_selection_bias, 16),
    (_sigmoid_router_without_a_bias, 4), (_ungated_experts_in_a_latent, 4),
    (_kimi_experts_over_two_chips, 2)])
def test_the_shares_add_up_to_the_uncut_layer(router, chips):
    """The 16 experts over ``chips`` chips (0-7 and 8-15; one each; or four
    each), the shared expert — and nothing else — computed by all and counted
    once, against the reference's uncut layer."""
    tree, reference, layer = router()
    held = 16 // chips
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 17, 64))
    want = reference((0, 16), y.reshape(-1, 64)).reshape(y.shape)
    shares = [layer(first, held).apply(
        {"params": _share(tree, first, held)}, y)
        for first in range(0, 16, held)]
    gated = "gate_proj" in tree["shared_expert"]
    shared = (hybrid.GatedMlp if gated else hybrid.SquaredReluMlp)(
        {"hidden_size": 64, "intermediate_size":
         tree["shared_expert"]["up_proj"]["kernel"].shape[1]}).apply(
        {"params": tree["shared_expert"]}, y)
    np.testing.assert_allclose(sum(shares) - (chips - 1) * shared, want,
                               rtol=1e-4, atol=2e-6)
    # each share alone is the reference's share, and none is the whole
    for first, got in zip(range(0, 16, held), shares):
        np.testing.assert_allclose(
            got, reference((first, held), y.reshape(-1, 64)).reshape(y.shape),
            rtol=1e-4, atol=1e-6)
        assert float(jnp.abs(got - want).max()) > 1e-3


def test_no_assignment_is_dropped_when_every_row_routes_to_one_expert():
    """A router that sends every row to experts 5, 6, 7 (in that order of
    weight): 3 x rows assignments to held experts, none to the other five, and
    the layer's output is exactly those three experts' weighted sum."""
    tree = _uncut_tree()
    router = jnp.zeros((64, 16)).at[:, 5].set(0.03).at[:, 6].set(0.02).at[
        :, 7].set(0.01)
    tree = dict(_share(tree, 0, 8), router=router)
    y = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (40, 64))) + 0.1
    got = _expert_layer(0, 8).apply({"params": tree}, y)
    r = jax.nn.softmax(y @ router, axis=-1)[:, 5:8]
    w = 2.5 * r / r.sum(-1, keepdims=True)
    one = lambda e: ref.mlp(jax.tree.map(
        lambda bank: {"kernel": bank[e]}, {k: tree[k] for k in ref.BANKS}),
        y, ref.vit.EXACT)
    want = ref.mlp(tree["shared_expert"], y, ref.vit.EXACT) + sum(
        w[:, j, None] * one(5 + j) for j in range(3))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_a_share_that_does_not_lie_among_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="held"):
        _expert_layer(12, 8).init(jax.random.PRNGKey(0), jnp.zeros((4, 64)))
    with pytest.raises(ValueError, match="held of 16 routed"):
        model_and_params("float32", experts_held_from=9)


# ----------------------------------------------------------------- rotary

def test_yarn_frequencies_against_values_worked_by_hand():
    """The published full-attention entry at head size 128: rot = 64, 32
    pairs; d(32) = 64 ln(8192 / 64 pi) / (2 ln 5e5) = 9.04, d(1) = 17.49, so
    low = 9, high = 18: pairs 0-9 keep theta^(-2j/64), pairs 18-31 are divided
    by 128, pair 12 is a third of the way."""
    rope = dict(ROPE["full_attention"], original_max_position_embeddings=8192)
    inv, scale = laguna.rotary_frequencies(rope, 128)
    plain = 500000.0 ** (-np.arange(32) / 32)
    assert inv.shape == (32,) and scale == 1.4852030263919618
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-12)
    third = plain[12] / 128 / 3 + plain[12] * 2 / 3
    np.testing.assert_allclose(inv[12], third, rtol=1e-12)
    assert inv[12] == pytest.approx(0.0048808, rel=1e-4)
    # no attention_factor in the config: 0.1 ln(factor) + 1
    del rope["attention_factor"]
    assert laguna.rotary_frequencies(rope, 128)[1] == pytest.approx(
        1.4852030263919618, rel=1e-12)
    # the window layers' entry: all 128 dims, plain
    inv, scale = laguna.rotary_frequencies(ROPE["sliding_attention"], 128)
    np.testing.assert_allclose(inv, 10000.0 ** (-np.arange(64) / 64))
    assert scale == 1.0
    # and the reference's own arithmetic agrees
    for kind in ROPE:
        np.testing.assert_array_equal(
            ref.rotary(ROPE[kind], 16)[0],
            laguna.rotary_frequencies(ROPE[kind], 16)[0])


def test_rotary_on_the_token_major_array_is_the_per_head_rotation():
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 3 * 16))
    for kind in ROPE:
        rope = laguna.rotary_frequencies(ROPE[kind], 16)
        got = laguna.apply_rotary(x, 3, *rope)
        want = ref.rotate(x.reshape(2, 9, 3, 16), *rope).reshape(x.shape)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        assert float(jnp.abs(got - x)[:, 1:].max()) > 0.1
    # position 0 is not turned: the plain kind leaves the class token as it is
    plain = laguna.rotary_frequencies(ROPE["sliding_attention"], 16)
    np.testing.assert_allclose(laguna.apply_rotary(x, 3, *plain)[:, 0], x[:, 0],
                               rtol=1e-6)


# ------------------------------------------------- trajectory, gradients

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


def _reference_forward_traceable(params, x, t):
    """``ref.forward`` takes its row lists on the host; under ``jax.grad``
    the same equations with every held expert applied to every row and the
    rows not routed to it weighted 0."""
    ops = ref.vit.EXACT
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok = ref._embed(outer, x, t, patch_size=4, ops=ops)
    eps = TRUNK["rms_norm_eps"]
    for i in range(TRUNK["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        tok = tok + ref.attention(
            p["self_attn"], ref.rms_norm(tok, p["input_layernorm"], eps),
            TRUNK, i, ops)
        y = ref.rms_norm(tok, p["post_attention_layernorm"], eps)
        if TRUNK["mlp_layer_types"][i] == "dense":
            tok = tok + ref.mlp(p["mlp"], y, ops)
            continue
        top_e, weight = ref.route(p["mlp"]["router"], y, TRUNK, ops)
        out = ref.mlp(p["mlp"]["shared_expert"], y, ops)
        for e in range(TRUNK["num_experts"]):
            w_e = jnp.sum(jnp.where(top_e == e, weight, 0.0), -1)
            bank = {k: {"kernel": p["mlp"][k][e]} for k in ref.BANKS}
            out = out + w_e[..., None] * ref.mlp(bank, y, ops)
        tok = tok + out
    return ref._head(outer, tok, patch_size=4, shape=x.shape[1:], eps=eps,
                     ops=ops)


def test_gradient_matches_the_references():
    """What a training step differentiates off the TPU: blockwise XLA
    attention and ``ragged_dot``."""
    model, params = model_and_params("float32")
    x, t = inputs()
    np.testing.assert_allclose(  # the traceable form is the reference
        _reference_forward_traceable(params, x, t),
        reference_forward(params, x, t), rtol=1e-5, atol=1e-6)
    target = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    loss = lambda fwd: lambda p: jnp.mean((fwd(p) - target) ** 2)
    got = jax.grad(loss(lambda p: model.apply({"params": p}, x, t, False)))(params)
    want = jax.grad(loss(lambda p: _reference_forward_traceable(p, x, t)))(params)
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

    model, _ = model_and_params("float32")
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


# -------------------------------------------------------------- causality

def _one_layer(index):
    """A trunk of layer ``index`` alone: its own residual stream."""
    kw = dict(trunk=hybrid._frozen(TRUNK))
    m = laguna.LagunaLayer(index=index, **kw)
    tokens = jax.random.normal(jax.random.PRNGKey(2), (2, 17, 64))
    variables = m.init(jax.random.PRNGKey(0), tokens)
    variables = jax.tree.map(  # seeded, non-degenerate leaves
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(9), a.shape),
        variables)
    return m, variables, tokens


@pytest.mark.parametrize("index,kind", [(0, "full attention, dense MLP"),
                                        (1, "window attention, experts"),
                                        (4, "full attention, experts")])
def test_every_layer_kind_is_causal(index, kind):
    """The output at token t does not move when tokens after t change."""
    m, variables, tokens = _one_layer(index)
    t = 8
    later = tokens.at[:, t + 1:].add(
        jax.random.normal(jax.random.PRNGKey(4), (2, 17 - t - 1, 64)))
    base, moved = m.apply(variables, tokens), m.apply(variables, later)
    np.testing.assert_allclose(moved[:, :t + 1], base[:, :t + 1], atol=1e-6)
    assert float(jnp.abs(moved[:, t + 1:] - base[:, t + 1:]).max()) > 1e-3


def test_the_windows_reach_in_a_one_layer_trunk():
    """Window 8: token 15 sees tokens 8..15. Moving token 7 leaves its output
    where it was, moving token 8 does not; the full layer sees both."""
    for index, sees_7 in ((1, False), (4, True)):
        m, variables, tokens = _one_layer(index)
        base = m.apply(variables, tokens)[:, 15]
        at = lambda j: m.apply(variables, tokens.at[:, j].add(1.0))[:, 15]
        assert float(jnp.abs(at(8) - base).max()) > 1e-4
        moved = float(jnp.abs(at(7) - base).max())
        assert (moved > 1e-4) if sees_7 else (moved <= 1e-6), (index, moved)


def test_the_whole_trunk_is_causal_in_raster_order():
    model, params = model_and_params("float32")
    x, steps = inputs(2)
    run = lambda x: model.apply({"params": params}, x, steps)
    # rows of pixels 8.. are patches 8..15 = tokens 9..16
    moved, base = run(x.at[:, 8:].add(1.0)), run(x)
    np.testing.assert_allclose(moved[:, :8], base[:, :8], atol=1e-6)
    assert float(jnp.abs(moved[:, 8:] - base[:, 8:]).max()) > 1e-3


# ---------------------------------------------------------------- serving

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


# ---------------------------------------------------- building, refusals

def _yaml(tmp_path, **extra):
    import yaml

    from ddim_cold_tpu.config import load_config

    raw = dict(image_size=[16, 16], patch_size=4, trunk=TRUNK, **extra)
    path = tmp_path / "laguna.yaml"
    path.write_text(yaml.safe_dump(raw))
    return load_config(str(path))


def _build(tmp_path, **extra):
    from ddim_cold_tpu.train.trainer import build_model

    return build_model(_yaml(tmp_path, **extra))


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    model = _build(tmp_path, AMP=True)
    assert isinstance(model, hybrid.HybridDenoiser)
    assert hybrid.stack_of(model.trunk) == (laguna.check_trunk, laguna.layer)
    assert (model.embed_dim, model.depth, model.num_heads) == (64, 5, 4)
    assert model.dtype == jnp.bfloat16 and model.num_patches == 16
    x, t = inputs()
    tree = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x, t))
    mlp = tree["params"]["layers_2"]["mlp"]
    assert mlp["router"].shape == (64, 16)          # the published width
    assert mlp["gate_proj"].shape == (8, 64, 32)    # the experts held
    assert "router" not in tree["params"]["layers_0"]["mlp"]  # dense


@pytest.mark.parametrize("option,how", [
    ("quant", lambda m, tmp: m.clone(quant="xla")),
    ("fused", lambda m, tmp: m.clone(fused=True)),
    ("cache_mode", lambda m, tmp: m.apply(
        {"params": {}}, *inputs(), capture_split=1)),
    ("scan_blocks", lambda m, tmp: _build(tmp, scan_blocks=True)),
    ("num_experts", lambda m, tmp: _build(tmp, num_experts=4)),
    ("num_experts", lambda m, tmp: _build(tmp, moe_dispatch="index")),
    ("sp_mode", lambda m, tmp: m.clone(sp_mode="ulysses")),
    ("sp_mode", lambda m, tmp: m.clone(seq_mesh=object(), seq_axis="seq")),
    ("use_flash", lambda m, tmp: _build(tmp, use_flash=True)),
])
def test_options_that_assume_blocks_internals_are_refused_by_name(
        option, how, tmp_path):
    model, _ = model_and_params("float32")
    with pytest.raises(ValueError, match=f"has no '{option}'"):
        how(model, tmp_path)


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


@pytest.mark.parametrize("change,match", [
    (dict(attention_bias=True), "attention_bias"),
    (dict(moe_router_logit_softcapping=30), "softcapping"),
    (dict(gating_types=["per_element"] * 8), "per_element"),
    (dict(layer_types=["full_attention"] * 3), "3 entries for 5 layers"),
    (dict(num_attention_heads_per_layer=[4, 5, 6, 6, 4]), "divide"),
    (dict(model_type="llama"), "no layer stack"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_x004_every_spelling_is_refused_whatever_the_stack():
    from ddim_cold_tpu.analysis import config_checks

    assert config_checks.check_hybrid_refusals() == []
    for name in config_checks.HYBRID_MUST_REFUSE_SPELLINGS:
        assert hybrid._ALIASES.get(name, name) in hybrid.REFUSED, name


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/attn_full | attn_window | moe | route | mlp`` in the lowered text, and
    one count a trace on each of the two kernels' counters and on the one
    that says where q was turned."""
    from ddim_cold_tpu.obs import metrics
    from ddim_cold_tpu.ops import flash_attention as fa

    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/attn_full", "trunk/attn_window", "trunk/moe",
                  "trunk/mlp", "trunk/route"):
        assert scope in text, scope
    by_key, first_half = {}, {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.moe_gmm_schedule/by_key", {}))
        first_half.update(series.get("kernels.moe_gate_up_schedule/by_key", {}))
    assert by_key == {"xla": 12}  # 4 sparse layers x gate, up, down
    assert first_half == {"xla": 4}  # gate and up: one call a layer
    # off the TPU every layer's q is turned by apply_rotary, before the oracle
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"xla": 5}
    metrics.reset()
