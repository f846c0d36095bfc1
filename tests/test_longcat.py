"""models/longcat.py at toy size (hidden 64, two published layers, each two
latent attentions of 2 heads of 128 + 64 query/key dims and 128 value dims —
the smallest the attention launch addresses — on latents of 32 and 16, BOTH
rescaled (a_q = sqrt(2), a_kv = 2), two dense MLPs of 96 and one expert layer
as the shortcut: a router of 8 + 4 outputs top-3 under a softmax with a
selection bias, not renormalised, times 6, experts 0-3 of 8 held at width 32,
outputs 8-11 identities; 16x16 px patch 4 = 17 tokens) on seeded weights,
against the plain reference (``benchmark/reference/longcat.py``, which imports
nothing of the program): the forward whole and sub-layer by sub-layer, what
each departure from the equations costs, the shares of the experts and the
identity term counted once, the router, the rescaled latents, the DDIM
trajectory, causality, refusals, scopes and counters — and that the stacks
that were here trace to the programs they had."""

import hashlib
import importlib
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_longcat
from benchmark.reference import longcat as ref
from benchmark.reference import lowprec
from ddim_cold_tpu.models import glm, hybrid, longcat, moe, pangu
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import sampling

PUBLISHED = dict(
    model_type="longcat_flash", hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=2,
    attention_bias=False, rms_norm_eps=1e-5, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000000, attention_method="MLA", mla_scale_q_lora=True,
    mla_scale_kv_lora=True, n_routed_experts=4, zero_expert_num=4,
    zero_expert_type="identity", moe_topk=3, routed_scaling_factor=6)
SIZES = dict(img_size=[16, 16], patch_size=4, in_chans=3, total_steps=2000)
EXACT = ref.vit.EXACT


def config(precision, **changes):
    return {**PUBLISHED, **SIZES, "precision": precision, "layers_from": 0,
            "source_values": {"n_routed_experts": 8}, "experts_held_from": 0,
            **changes}


TRUNK = weights_longcat.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_longcat.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_longcat.trunk_of(cfg), img_size=(16, 16), patch_size=4,
        total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_longcat.make(cfg, seed)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def forward(model, params, x, t):
    return model.apply({"params": params}, x, t)


def reference_forward(params, x, t, ops=EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    """To 1e-5 relative: both sides are float32 with float32 products (the
    suite pins the matmul precision) and differ in the order of their sums —
    the program's blockwise softmax, its latents rescaled inside their norms
    and its sorted expert rows against the reference's per-block softmax
    under an explicit mask, its multiplications where the published code has
    them and its loop over the experts."""
    model, params = model_and_params("float32")
    x, t = inputs()
    got = forward(model, params, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree (two double layers' operands and stream rounded to 8 bits of
#: mantissa, a pick that flips at a near-tie of the top-3 included: 1.0e-3 to
#: 1.6e-3 over seeds); the float8 control reads 6e-3 and more
BF16_FORWARD_RMS = 3e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = forward(model, params, x, t)
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
    assert rms(got, want) < 1e-5, rms(got, want)


def _stream(seed=5, n=2, tokens=17):
    return 0.7 * jax.random.normal(jax.random.PRNGKey(seed), (n, tokens, 64))


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _expert_layer(trunk=TRUNK, **changes):
    """The expert layer as ``LongcatLayer`` builds it."""
    kw = dict(num_routed=trunk["n_experts_routed"], top_k=trunk["moe_topk"],
              first_held=trunk["experts_held_from"],
              num_held=trunk["n_routed_experts"],
              hidden_features=trunk["expert_ffn_hidden_size"],
              shared_features=0, scaling=trunk["routed_scaling_factor"],
              norm_topk=False, selection_bias=True,
              zero_experts=trunk["zero_expert_num"])
    return HeldExpertsMlp(**{**kw, **changes})


@pytest.mark.parametrize("part", ["self_attn_0", "self_attn_1", "mlps_0",
                                  "mlps_1", "mlp", "layer"])
def test_every_sub_layer_is_the_references(part):
    """Each of a published layer's five sub-layers on a stream of its own,
    then the layer whole: 1e-5 relative, float32 sums in another order."""
    _, params = model_and_params("float32")
    p, x = params["layers_1"], _stream()
    if part.startswith("self_attn"):
        got = pangu.DenseLatentAttention(TRUNK, pairing="interleave").apply(
            {"params": p[part]}, x)
        want = ref.attention(_f32(p[part]), x, TRUNK, EXACT)
    elif part.startswith("mlps"):
        got = hybrid.GatedMlp({"hidden_size": 64, "intermediate_size": 96}
                              ).apply({"params": p[part]}, x)
        want = ref.mlp(_f32(p[part]), x, EXACT)
    elif part == "mlp":
        got = _expert_layer().apply({"params": p[part]}, x)
        want = ref.sparse_mlp(p[part], x.reshape(-1, 64), TRUNK).reshape(
            x.shape)
    else:
        got = longcat.LongcatLayer(TRUNK).apply({"params": p}, x)
        want = ref.layer(p, x, TRUNK)
    scale = float(jnp.abs(want).max())
    assert scale > 1e-3  # a dense MLP of 96 behind a down_proj of std 0.007
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * scale)


def _reference_layer(p, x, cfg, *, identity=True, shortcut_last=True,
                     held=True):
    """``reference/longcat.layer`` written out from its own pieces, with the
    departures a test can ask for: the identity term left out, the held
    experts' term left out (a grouped product that returns zeros), and the
    shortcut added where a plain layer would add it (at ``h2``, so that the
    second attention and the second MLP read it)."""
    import json

    static = json.dumps(cfg, sort_keys=True)
    eps = cfg["rms_norm_eps"]
    h1 = ref._attend(p["input_layernorm_0"], p["self_attn_0"], x, cfg=static,
                     ops=EXACT)
    rest = {k: v for k, v in p["mlp"].items() if k not in ref.BANKS}
    y, top_e, weight = ref._route(p["post_attention_layernorm_0"], rest, h1,
                                  cfg=static, ops=EXACT)
    s = ref.experts({k: p["mlp"][k] for k in ref.BANKS}, y, top_e, weight,
                    ref._held(cfg), EXACT)
    if not held:
        s = jnp.zeros_like(s)
    if identity:
        s = s + ref.passed(top_e, weight, cfg) * y
    s = s.reshape(x.shape)
    h2 = ref._dense(p["post_attention_layernorm_0"], p["mlps_0"], h1, eps=eps,
                    ops=EXACT)
    if not shortcut_last:
        h2 = h2 + s
    h3 = ref._attend(p["input_layernorm_1"], p["self_attn_1"], h2, cfg=static,
                     ops=EXACT)
    h4 = ref._dense(p["post_attention_layernorm_1"], p["mlps_1"], h3, eps=eps,
                    ops=EXACT)
    return h4 + s if shortcut_last else h4


def _stronger(p):
    """A layer's tree with branches and attention logits of order one, as a
    trained layer's are: the seeded ``o_proj`` and ``down_proj`` (std 0.007;
    the held experts' bank too) times 12 and ``q_b_proj`` times 30, so that
    what the second attention and the second MLP READ matters to what they
    add, a query's scale to its softmax, and a held expert's product to the
    shortcut."""
    p = jax.tree.map(lambda w: w, p)
    for half in (0, 1):
        attn, dense = p[f"self_attn_{half}"], p[f"mlps_{half}"]
        attn["o_proj"]["kernel"] = 12 * attn["o_proj"]["kernel"]
        attn["q_b_proj"]["kernel"] = 30 * attn["q_b_proj"]["kernel"]
        dense["down_proj"]["kernel"] = 12 * dense["down_proj"]["kernel"]
    p["mlp"]["down_proj"] = 12 * p["mlp"]["down_proj"]
    return p


@pytest.mark.parametrize("departure", [
    "none", "no_identity_term", "held_experts_zeroed", "shortcut_added_at_h2",
    "no_a_q", "no_a_kv"])
def test_each_departure_from_the_equations_fails_the_comparison(departure):
    """The comparison that passes at 1e-5 sees every one of the four things
    this stack adds, and the held experts' grouped product: against a
    reference without the identity term, without the held experts' term,
    with the shortcut added before the second attention, or with a latent
    left unscaled, the program's layer is off by more than a hundred times
    the tolerance (relative to the largest value). At a toy size this float32
    comparison is the one that sees a wrong grouped product: the toy cell's
    ``sample_rms_vs_reference`` in bfloat16 does not, the real cell's does
    by 13 % of its limit (PERF.md section 7)."""
    _, params = model_and_params("float32")
    p, x = _stronger(params["layers_0"]), _stream(seed=9)
    got = longcat.LongcatLayer(TRUNK).apply({"params": p}, x)
    cfg = dict(TRUNK)
    kw = {}
    if departure == "no_identity_term":
        kw["identity"] = False
    elif departure == "held_experts_zeroed":
        kw["held"] = False
    elif departure == "shortcut_added_at_h2":
        kw["shortcut_last"] = False
    elif departure == "no_a_q":
        cfg["mla_scale_q_lora"] = False
    elif departure == "no_a_kv":
        cfg["mla_scale_kv_lora"] = False
    want = _reference_layer(p, x, cfg, **kw)
    off = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    if departure == "none":
        assert off < 1e-5, off
        np.testing.assert_allclose(np.asarray(want),
                                   np.asarray(ref.layer(p, x, TRUNK)),
                                   rtol=1e-6, atol=1e-7)
    else:
        assert off > 1e-3, (departure, off)


def _experts_tree(outputs=12, seed=3, bias=0.0):
    """One expert layer's tree with all 8 experts with weights, the router
    ``outputs`` wide."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, shape, std: std * jax.random.normal(k, shape)
    return {"router": normal(keys[0], (64, outputs), 0.3),
            "e_score_correction_bias": bias * jax.random.normal(
                keys[4], (outputs,)),
            "gate_proj": normal(keys[1], (8, 64, 32), 0.2),
            "up_proj": normal(keys[2], (8, 64, 32), 0.2),
            "down_proj": normal(keys[3], (8, 32, 64), 0.2)}


def _loop(tree, z, *, first=0, held=8, top_k=3, scaling=6.0, identity=True):
    """A dense oracle, expert by expert: ``Σ_{e ∈ S ∩ held} w_e E_e(z) +
    (Σ_{e ∈ S, e ≥ 8} w_e) z`` with ``w`` the softmax's own numbers times
    ``scaling``, chosen by ``r + b``."""
    z2 = np.asarray(z, np.float64).reshape(-1, 64)
    logits = z2 @ np.asarray(tree["router"], np.float64)
    r = np.exp(logits - logits.max(-1, keepdims=True))
    r /= r.sum(-1, keepdims=True)
    chosen = np.argsort(-(r + np.asarray(tree["e_score_correction_bias"],
                                         np.float64)), -1, kind="stable")
    out = np.zeros_like(z2)
    for row, picks in enumerate(chosen[:, :top_k]):
        for e in picks:
            w = scaling * r[row, e]
            if e >= 8:
                out[row] += w * z2[row] if identity else 0.0
            elif first <= e < first + held:
                g = z2[row] @ np.asarray(tree["gate_proj"][e], np.float64)
                u = z2[row] @ np.asarray(tree["up_proj"][e], np.float64)
                out[row] += w * ((g / (1 + np.exp(-g)) * u)
                                 @ np.asarray(tree["down_proj"][e], np.float64))
    return out.reshape(z.shape), chosen[:, :top_k], r


def _share(tree, first, held):
    return dict(tree, **{k: tree[k][first:first + held] for k in ref.BANKS})


def test_four_shares_and_the_identity_term_once_add_up_to_the_uncut_layer():
    """8 experts with weights + 4 zero-compute ones, top-3, four chips that
    hold 2 experts each: every share is its held experts' part PLUS the
    identity term, which every chip computes alike for its own rows; the four
    held parts and the identity term ONCE are the uncut reference's ``E(y)``,
    and no share is idle."""
    tree = _experts_tree(bias=0.02)
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 64))
    cfg = dict(TRUNK, n_routed_experts=8, n_experts_routed=8)
    want = ref.sparse_mlp(tree, z.reshape(-1, 64), cfg).reshape(z.shape)
    top_e, weight = ref.route(_f32({k: tree[k] for k in (
        "router", "e_score_correction_bias")}), z.reshape(-1, 64), cfg, EXACT)
    once = (ref.passed(top_e, weight, cfg) * z.reshape(-1, 64)).reshape(z.shape)
    assert float(jnp.abs(once).max()) > 1e-2  # identities are picked
    shares = [_expert_layer(first_held=first, num_held=2).apply(
        {"params": _share(tree, first, 2)}, z) for first in range(0, 8, 2)]
    parts = [share - once for share in shares]
    assert all(float(jnp.abs(part).max()) > 1e-2 for part in parts)
    # four float32 partial sums added in another order than the reference's
    # one running sum
    np.testing.assert_allclose(sum(parts) + once, want, rtol=1e-5, atol=5e-6)
    # counted four times it is not the layer
    assert float(jnp.abs(sum(shares) - want).max()) > 1e-2
    # the reference given one share is that share, identity term and all
    one = ref.sparse_mlp(_share(tree, 2, 2), z.reshape(-1, 64), dict(
        cfg, n_routed_experts=2, experts_held_from=2)).reshape(z.shape)
    np.testing.assert_allclose(shares[1], one, rtol=1e-5, atol=2e-6)
    # and the dense oracle agrees with both
    np.testing.assert_allclose(want, _loop(tree, z)[0], rtol=1e-4, atol=1e-5)


def test_a_token_that_picks_identities_alone_costs_no_expert_row(monkeypatch):
    """A bias that lifts the four zero-compute outputs over every expert:
    each token's three picks are identities, no row reaches a group
    (``group_sizes`` all zero: the launches skip every tile) and the layer
    returns ``(Σ w) · y``, w the softmax's own numbers times 6."""
    tree = _experts_tree()
    tree["e_score_correction_bias"] = jnp.where(jnp.arange(12) >= 8, 1.0, 0.0)
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 17, 64))
    sizes = []
    real = moe.grouped_mlp
    monkeypatch.setattr(moe, "grouped_mlp", lambda rows, gate, up, down, s,
                        **kw: sizes.append(np.asarray(s)) or real(
                            rows, gate, up, down, s, **kw))
    got = _expert_layer(num_held=8).apply({"params": tree}, z)
    assert len(sizes) == 1 and sizes[0].shape == (8,) and sizes[0].sum() == 0
    _, chosen, r = _loop(tree, z)
    assert (chosen >= 8).all()
    weights = 6 * np.take_along_axis(r, chosen, -1).sum(-1)
    assert 0.0 < weights.min() < weights.max() < 6.0
    np.testing.assert_allclose(
        np.asarray(got).reshape(-1, 64),
        weights[:, None] * np.asarray(z, np.float64).reshape(-1, 64),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("bias", [0.0, 0.05])
def test_the_bias_chooses_and_never_weighs_and_nothing_is_renormalised(bias):
    """Against the dense oracle: S is the top-3 of ``r + b``; a chosen
    expert's weight is 6 r_e whatever b is; the weights of a token's picks
    sum to whatever they sum to (0.4 to 3 here, never a fixed number)."""
    tree = _experts_tree(bias=bias)
    z = jax.random.normal(jax.random.PRNGKey(4), (2, 17, 64))
    got = _expert_layer(num_held=8).apply({"params": tree}, z)
    want, chosen, r = _loop(tree, z)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    plain = np.argsort(-r, -1, kind="stable")[:, :3]
    assert (chosen != plain).any() == bool(bias)  # the bias changes S
    sums = 6 * np.take_along_axis(r, chosen, -1).sum(-1)
    assert sums.max() - sums.min() > 0.5
    # renormalised, or weighted by r + b, the layer would be another
    normed = _expert_layer(num_held=8, norm_topk=True).apply(
        {"params": tree}, z)
    assert float(jnp.abs(normed - got).max()) > 1e-2
    # without its identity term too
    assert np.abs(_loop(tree, z, identity=False)[0] - want).max() > 1e-2


def test_without_zero_experts_the_layer_is_the_one_it_was():
    """``zero_experts=0`` traces the same equations as a layer built without
    the field, and a router 12 wide over 12 experts WITH weights is another
    layer than 8 + 4 identities."""
    z = jnp.ones((2, 5, 64))
    kw = dict(num_routed=8, top_k=3, first_held=0, num_held=8,
              hidden_features=32, shared_features=0)
    text = lambda layer: str(jax.make_jaxpr(
        lambda p: layer.apply({"params": p}, z))(jax.eval_shape(
            layer.init, jax.random.PRNGKey(0), z)["params"]))
    assert text(HeldExpertsMlp(**kw)) == text(HeldExpertsMlp(**kw,
                                                             zero_experts=0))
    assert text(HeldExpertsMlp(**kw)) != text(HeldExpertsMlp(**kw,
                                                             zero_experts=4))
    with pytest.raises(ValueError, match="zero_experts 4 with latent_features"):
        HeldExpertsMlp(**kw, zero_experts=4, latent_features=16,
                       hidden_act="relu2").init(jax.random.PRNGKey(0), z)
    # 11 picks of 8 + 4 outputs are fine, 13 are not
    HeldExpertsMlp(**dict(kw, top_k=11), zero_experts=4).init(
        jax.random.PRNGKey(0), z)
    with pytest.raises(ValueError, match="13 a token, of 8 routed and 4 "
                                         "zero-compute"):
        HeldExpertsMlp(**dict(kw, top_k=13), zero_experts=4).init(
            jax.random.PRNGKey(0), z)


#: sha256 (16 hex) of the printed jaxpr of each stack's toy forward in float32
#: (its own test file's ``model_and_params``; 2 images), AS THE PARENT OF PR 53
#: TRACED IT, under this suite's pinned matmul precision. PR 53 gave
#: ``HeldExpertsMlp`` its ``zero_experts`` and ``glm.latent_paths`` its
#: rescalings, both nothing at their defaults: every stack's program is the
#: one it had. A PR that changes a stack's program re-pins that stack's line.
PARENT_JAXPRS = {
    "hybrid": "9e6e819b81e324f2", "laguna": "7888101d2f12b3f1",
    "glm": "5a4ba28d7fe037c8", "pangu": "6788ceaf83b0eb90",
    "nemotron": "c60319473c69737a", "kimi": "0ea41acb88d0394d",
    "smallthinker": "7d8f5c3200889519"}


def _toy_jaxpr_hash(name):
    mod = importlib.import_module("test_" + name)
    if name == "hybrid":
        model = hybrid.HybridDenoiser(trunk=mod.TRUNK, img_size=(16, 16),
                                      patch_size=4, total_steps=2000)
    else:
        model, _ = mod.model_and_params("float32")
    x = jax.ShapeDtypeStruct((2, *model.img_size, 3), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, t)
    text = str(jax.make_jaxpr(lambda p, x, t: model.apply(p, x, t))(
        params, x, t))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS))
def test_the_stacks_that_were_here_trace_to_the_programs_they_had(name):
    assert _toy_jaxpr_hash(name) == PARENT_JAXPRS[name]


def test_the_rescaled_latents_are_multiplied_behind_their_norms():
    """``a = sqrt(hidden_size / rank)`` where the trunk says so, else 1 and
    then no operation; ``latent_paths`` multiplies the norm's rounded result,
    the published order."""
    assert glm.latent_multipliers(TRUNK) == (math.sqrt(2.0), 2.0)
    assert glm.latent_multipliers({"hidden_size": 6144, "q_lora_rank": 1536,
                                   "kv_lora_rank": 512,
                                   "mla_scale_q_lora": True,
                                   "mla_scale_kv_lora": True}) == (
        2.0, math.sqrt(12.0))
    assert glm.latent_multipliers({"hidden_size": 64, "q_lora_rank": 32,
                                   "kv_lora_rank": 16}) == (1.0, 1.0)
    assert glm.latent_multipliers(dict(TRUNK, mla_scale_q_lora=False)) == (
        1.0, 2.0)

    class Paths(nn.Module):
        trunk: dict
        dtype: object = jnp.float32

        @nn.compact
        def __call__(self, y):
            return glm.latent_paths(self.trunk, y, pangu._rope(self.trunk),
                                    "interleave",
                                    self.dtype, jnp.float32)

    y = jax.random.normal(jax.random.PRNGKey(0), (1, 5, TRUNK["hidden_size"]))
    plain_trunk = dict(TRUNK, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    params = Paths(plain_trunk).init(jax.random.PRNGKey(1), y)
    a_q, a_kv = glm.latent_multipliers(TRUNK)
    for dtype, exact in ((jnp.float32, False), (jnp.bfloat16, True)):
        c_q0, _, k_r0, c_kv0 = Paths(plain_trunk, dtype).apply(params, y)
        c_q, _, k_r, c_kv = Paths(TRUNK, dtype).apply(params, y)
        assert c_kv.dtype == c_q.dtype == dtype
        np.testing.assert_array_equal(np.asarray(k_r, np.float32),
                                      np.asarray(k_r0, np.float32))
        if exact:  # the rounded norm times a, rounded again
            np.testing.assert_array_equal(
                np.asarray(c_kv, np.float32),
                np.asarray(c_kv0 * a_kv, np.float32))
        np.testing.assert_allclose(np.asarray(c_q, np.float32),
                                   a_q * np.asarray(c_q0, np.float32),
                                   rtol=1e-2 if exact else 1e-6)
        np.testing.assert_allclose(np.asarray(c_kv, np.float32),
                                   a_kv * np.asarray(c_kv0, np.float32),
                                   rtol=1e-2 if exact else 1e-6)
    # absent or false: the flags' absence emits what their falsehood emits
    bare = {k: v for k, v in plain_trunk.items()
            if k not in ("mla_scale_q_lora", "mla_scale_kv_lora")}
    text = lambda trunk: str(jax.make_jaxpr(
        lambda y: Paths(trunk).apply(params, y))(y))
    assert text(bare) == text(plain_trunk) != text(TRUNK)
    assert text(plain_trunk).count(" mul ") + 2 == text(TRUNK).count(" mul ")


def test_a_q_behind_the_norm_is_a_q_behind_the_projection_bit_for_bit():
    """``(c_q · 2) W_qb`` and ``(c_q W_qb) · 2`` in bfloat16: the same bits,
    which is why the program may rescale the query latent before
    ``q_b_proj`` (a_q is 2 at the published 6,144 / 1,536)."""
    key = jax.random.PRNGKey(0)
    c_q = jax.random.normal(key, (17, 32)).astype(jnp.bfloat16)
    w = (0.05 * jax.random.normal(jax.random.fold_in(key, 1), (32, 384))
         ).astype(jnp.bfloat16)
    before = jnp.dot(c_q * jnp.bfloat16(2), w)
    after = jnp.dot(c_q, w) * jnp.bfloat16(2)
    np.testing.assert_array_equal(np.asarray(before, np.float32),
                                  np.asarray(after, np.float32))


def test_the_whole_trunk_is_causal_in_raster_order():
    """A change to the last patch moves no earlier patch's output."""
    model, params = model_and_params("float32")
    x, t = inputs(1)
    moved = x.at[:, 12:, 12:].add(1.0)
    a, b = forward(model, params, x, t), forward(model, params, moved, t)
    assert float(jnp.abs(a - b)[:, 12:, 12:].max()) > 1e-3
    same = jnp.abs(a - b).at[:, 12:, 12:].set(0.0)
    assert float(same.max()) < 1e-6


def test_gradients_flow_off_the_chip():
    """Every path is plain JAX off the TPU: into both attentions, both dense
    MLPs, the router (through the weights of the picks and the identity term)
    and the held experts."""
    model, params = model_and_params("float32", num_layers=1)
    x, t = inputs(1)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x, t) ** 2)))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    layer = norms["layers_0"]
    for half in (0, 1):
        assert layer[f"self_attn_{half}"]["q_b_proj"]["kernel"] > 0
        assert layer[f"self_attn_{half}"]["kv_a_layernorm"]["scale"] > 0
        assert layer[f"mlps_{half}"]["gate_proj"]["kernel"] > 0
    assert layer["mlp"]["router"] > 0 and layer["mlp"]["gate_proj"] > 0
    assert layer["mlp"]["e_score_correction_bias"] == 0  # chooses, never weighs
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))


@pytest.mark.parametrize("change,match", [
    (dict(zero_expert_type="copy"), "zero_expert_type 'copy'"),
    (dict(attention_method="GQA"), "attention_method 'GQA'"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(qk_rope_head_dim=32), "lane"),
    (dict(experts_held_from=5), "experts 5..8 held of 8 routed"),
    (dict(n_experts_routed=2), "held of 2 routed"),
    (dict(zero_expert_num=-1), "zero_expert_num -1"),
    (dict(model_type="llama"), "'smallthinker', 'longcat_flash' are written"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_and_counts_its_own_layers():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (longcat.check_trunk, longcat.layer)
    assert list(hybrid.STACKS)[-1] == "longcat_flash" and len(
        hybrid.STACKS) == 8
    # the published key is num_layers: no num_hidden_layers in this trunk
    assert "num_hidden_layers" not in model.trunk and model.depth == 2
    assert hybrid.depth_of({"num_hidden_layers": 5, "num_layers": 9}) == 5
    assert (model.embed_dim, model.num_heads) == (64, 2)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})
    assert hash(model) is not None  # jit's static argument


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/mla``, ``trunk/mlp``, ``trunk/moe`` and inside the last the
    routing's own ``trunk/route`` in the lowered text; two attentions and one
    expert layer a published layer counted: three products, one gated first
    half, the router's source and its zero-compute outputs."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/mla", "trunk/mlp", "trunk/moe", "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name, counts in series.items():
            if name.startswith("kernels.") and name.endswith("/by_key"):
                for key, count in counts.items():
                    at = name[:-len("/by_key")], key
                    by_key[at] = by_key.get(at, 0) + count
    assert by_key == {("kernels.flash_latent_schedule", "xla"): 4,
                      ("kernels.moe_gmm_schedule", "xla"): 6,
                      ("kernels.moe_gate_up_schedule", "xla"): 2,
                      ("kernels.moe_route_source", "expert_input"): 2,
                      ("kernels.moe_zero_experts", "identity"): 2}
    metrics.reset()
    # a router as wide as its experts counts itself so
    _expert_layer(zero_experts=0).init(jax.random.PRNGKey(0), _stream())
    assert moe._kernels.by_key("kernels.moe_zero_experts") == {"none": 1}
    metrics.reset()
