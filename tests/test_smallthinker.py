"""models/smallthinker.py at toy size (the benchmark's own toy configuration,
``benchmark/tests/fixtures_smallthinker``: hidden 64, five layers — full,
window, window, window, full, so both kinds and a second period's first layer
run — 14 query heads on 2 K/V heads of 16, seven a K/V head as published, 8
router outputs top-3 all held, ReLU-gated experts of width 32, window 8;
12x52 px patch 4 = 40 tokens) on seeded weights, against the plain reference
(``benchmark/reference/smallthinker.py``, which imports nothing of the
program): the forward, the DDIM trajectory, where the router reads, the
position-free full layers, the share, causality, refusals, scopes and
counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_smallthinker
from benchmark.reference import lowprec
from benchmark.reference import smallthinker as ref
from ddim_cold_tpu.models import hybrid, smallthinker
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import flash_attention, rotary, sampling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "fixtures_smallthinker",
                       "benchmark", "configs", "toy_smallthinker.json")) as f:
    TOY = json.load(f)
SHAPE = (12, 52, 3)  # 3 x 13 patches of 4 + the class token = 40 tokens


def config(precision, **changes):
    return {**TOY, "precision": precision, **changes}


TRUNK = weights_smallthinker.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_smallthinker.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_smallthinker.trunk_of(cfg),
        img_size=tuple(cfg["img_size"]), patch_size=4, total_steps=2000,
        dtype=dtype, param_dtype=dtype)
    return model, weights_smallthinker.make(cfg, seed)


def forward(model, params, x, t):
    return jax.jit(model.apply)({"params": params}, x, t)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, *SHAPE))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    """To 1e-5 relative: both sides are float32 with float32 products (the
    suite pins the matmul precision) and differ in the order of their sums —
    the program's blockwise softmax and sorted expert rows against the
    reference's per-block softmax under an explicit mask and its loop over
    the experts."""
    model, params = model_and_params("float32")
    x, t = inputs()
    got = forward(model, params, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree (five layers' operands and stream rounded to 8 bits of
#: mantissa, an expert that flips at a near-tie of the top-3 included: 8e-4
#: to 1.2e-3 over seeds); the float8 control reads about ten times the
#: program
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
    x_init = jax.random.normal(key, (2, *SHAPE), jnp.float32)
    want = ref.sample(params, x_init, k=500, total_steps=2000, trunk=TRUNK,
                      patch_size=4)
    assert got.shape == (2, *SHAPE)
    assert rms(got, want) < 1e-5, rms(got, want)


def _layer(i, trunk=TRUNK):
    return smallthinker.SmallThinkerLayer(trunk, i)


def _stream(seed=5, n=2, tokens=40):
    return 0.7 * jax.random.normal(jax.random.PRNGKey(seed), (n, tokens, 64))


@pytest.mark.parametrize("i", [0, 1])
def test_one_layer_is_the_reference_layer(i):
    """A full layer (0) and a window layer (1), each against the reference's
    layer on a stream of its own."""
    _, params = model_and_params("float32")
    x = _stream()
    got = _layer(i).apply({"params": params[f"layers_{i}"]}, x)
    want = ref.layer(params[f"layers_{i}"], x, TRUNK, i)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _named(router, x):
    """The experts the reference's router names for each row of ``x``, as a
    ``(rows, 8)`` mask."""
    top_e, _ = ref.route(router.astype(jnp.float32), x.reshape(-1, 64), TRUNK,
                         ref.vit.EXACT)
    return np.asarray(jax.nn.one_hot(top_e, 8).sum(-2) > 0)


def _telltale(mlp):
    """``mlp``'s tree with experts that say who ran: expert e squares the
    ReLU of its input's first 32 dims and writes their sum to output dim e
    alone, so a row's output dim e is above 0 exactly where e was chosen for
    it (its weight is a softmax's: never 0)."""
    eye = jnp.eye(64, 32)
    down = jnp.zeros((8, 32, 64)).at[jnp.arange(8), :, jnp.arange(8)].set(1.0)
    return dict(mlp, gate_proj=jnp.tile(eye, (8, 1, 1)),
                up_proj=jnp.tile(eye, (8, 1, 1)), down_proj=down)


def _parts(p, i, x):
    """(x', z, the expert layer's own addition) of the program's layer i."""
    norm = lambda name, v: hybrid.RMSNorm(1e-6).apply({"params": p[name]}, v)
    after = x + smallthinker.Attention(
        TRUNK, bool(TRUNK["rope_layout"][i]),
        bool(TRUNK["sliding_window_layout"][i])).apply(
        {"params": p["self_attn"]}, norm("input_layernorm", x))
    out = smallthinker.SmallThinkerLayer(TRUNK, i).apply({"params": p}, x)
    return after, norm("post_attention_layernorm", after), out - after


@pytest.mark.parametrize("i", [0, 1])
def test_the_router_reads_the_layers_input_and_not_what_attention_adds(i):
    """With experts that say who ran (``_telltale``), the experts the PROGRAM
    chose are those the reference's router names from x, the layer's input.
    Perturb only what attention adds — another ``o_proj``, so x' and z change
    and x does not — and what the experts write changes while who was chosen
    does not. Route from the normed post-attention stream instead, as every
    other stack's expert layer does, and other experts are chosen and the
    layer's result leaves the float32 tolerance."""
    _, params = model_and_params("float32")
    p = dict(params[f"layers_{i}"])
    x = _stream()
    tell = dict(p, mlp=_telltale(p["mlp"]))
    names = _named(p["mlp"]["router"], x)
    assert names.sum(-1).tolist() == [3] * 80
    _, z, wrote = _parts(tell, i, x)
    np.testing.assert_array_equal(
        np.asarray(wrote).reshape(80, 64)[:, :8] > 1e-7, names)

    other = dict(tell, self_attn=dict(tell["self_attn"], o_proj={
        "kernel": jax.random.normal(jax.random.PRNGKey(9), (14 * 16, 64))}))
    _, z_other, wrote_other = _parts(other, i, x)
    assert float(jnp.abs(z - z_other).max()) > 0.5   # the experts' input moved
    assert float(jnp.abs(wrote - wrote_other).max()) > 0.5
    np.testing.assert_array_equal(
        np.asarray(wrote_other).reshape(80, 64)[:, :8] > 1e-7, names)

    # the router behind attention: HeldExpertsMlp without route_from
    behind = HeldExpertsMlp(
        num_routed=8, top_k=3, first_held=0, num_held=8, hidden_features=32,
        shared_features=0, hidden_act="relu")
    chose = np.asarray(behind.apply({"params": tell["mlp"]}, z_other)
                       ).reshape(80, 64)[:, :8] > 1e-7
    np.testing.assert_array_equal(chose, _named(p["mlp"]["router"], z_other))
    assert (chose != names).any(axis=-1).mean() > 0.5  # most rows differ
    loud = dict(p, self_attn=other["self_attn"])  # the layer's own experts
    after, z_loud, _ = _parts(loud, i, x)
    moved = after + behind.apply({"params": p["mlp"]}, z_loud)
    want = ref.layer(loud, x, TRUNK, i)
    np.testing.assert_allclose(
        smallthinker.SmallThinkerLayer(TRUNK, i).apply({"params": loud}, x),
        want, rtol=1e-5, atol=2e-6)
    assert float(jnp.abs(moved - want).max()) > 1e-3


def test_a_full_layer_ignores_the_rotary_table_and_a_window_layer_does_not():
    """Another ``rope_theta`` is another table: the full layers (0 and 4:
    ``rope_layout`` 0, ``masked_attention(rotary=None)``) give the same bits,
    a window layer another result. And no table is built for a full layer:
    its trace counts no rotation."""
    _, params = model_and_params("float32")
    x = _stream()
    other = dict(TRUNK, rope_theta=100.0)
    for i, moved in ((0, False), (1, True), (4, False)):
        p = {"params": params[f"layers_{i}"]}
        same = _layer(i).apply(p, x)
        turned = _layer(i, other).apply(p, x)
        assert (float(jnp.abs(same - turned).max()) > 1e-4) == moved, i
        if not moved:
            np.testing.assert_array_equal(same, turned)
    metrics.reset()
    jax.eval_shape(_layer(0).apply, {"params": params["layers_0"]}, x)
    assert flash_attention._kernels.by_key("kernels.flash_fwd_rotary") == {}
    jax.eval_shape(_layer(1).apply, {"params": params["layers_1"]}, x)
    assert flash_attention._kernels.by_key("kernels.flash_fwd_rotary") == {
        "xla": 1}
    metrics.reset()


def test_a_window_layer_turns_q_and_k_by_the_default_rotary_over_every_dim():
    """Layer 1's attention written out: q and k through ``apply_rotary`` with
    θ = 1.5 M over all 16 dims (``rotate_half``: dim j with dim j + 8), the
    window of 8, seven query heads a K/V head, against the module."""
    _, params = model_and_params("float32")
    p = params["layers_1"]["self_attn"]
    y = _stream(6)
    inv = 1.5e6 ** (-np.arange(0, 16, 2) / 16)
    q = rotary.apply_rotary(y @ p["q_proj"]["kernel"], 14, inv, 1.0)
    k = rotary.apply_rotary(y @ p["k_proj"]["kernel"], 2, inv, 1.0)
    v = y @ p["v_proj"]["kernel"]
    k, v = (jnp.repeat(a.reshape(2, 40, 2, 16), 7, axis=2) for a in (k, v))
    logits = jnp.einsum("bnhd,bmhd->bhnm", q.reshape(2, 40, 14, 16), k) / 4.0
    t, j = jnp.arange(40)[:, None], jnp.arange(40)[None]
    attn = jax.nn.softmax(jnp.where((j <= t) & (j > t - 8), logits, -jnp.inf))
    want = jnp.einsum("bhnm,bmhd->bnhd", attn, v).reshape(2, 40, 224) @ p[
        "o_proj"]["kernel"]
    got = smallthinker.Attention(TRUNK, True, True).apply({"params": p}, y)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rope,window", [([1, 0, 0, 0, 1], [0, 1, 1, 1, 0]),
                                         ([0, 0, 1, 1, 1], [1, 1, 0, 0, 1])])
def test_the_two_layouts_are_read_apart_entry_by_entry(rope, window):
    """``rope_layout`` and ``sliding_window_layout`` agree in the published
    config; the stack reads each by the layer's index on its own, so a rotary
    full layer and a position-free window layer run too, as the reference
    has them."""
    cut = dict(rope_layout=rope, sliding_window_layout=window)
    model, params = model_and_params("float32", **cut)
    x, t = inputs(2)
    np.testing.assert_allclose(
        forward(model, params, x, t),
        reference_forward(params, x, t, trunk=dict(TRUNK, **cut)),
        rtol=1e-5, atol=1e-6)


def test_the_whole_trunk_is_causal_in_raster_order():
    model, params = model_and_params("float32")
    x, steps = inputs(2)
    run = lambda x: forward(model, params, x, steps)
    # rows of pixels 8.. are patches 26..38 = tokens 27..39
    moved, base = run(x.at[:, 8:].add(1.0)), run(x)
    np.testing.assert_allclose(moved[:, :8], base[:, :8], atol=1e-6)
    assert float(jnp.abs(moved[:, 8:] - base[:, 8:]).max()) > 1e-3


@pytest.mark.parametrize("first,held", [(0, 4), (4, 4), (6, 2)])
def test_a_share_of_the_experts_is_the_references_same_share(first, held):
    """``moe_num_primary_experts`` held from ``experts_held_from`` of the 8
    the router still scores: the forward against the reference given the same
    share, and not the uncut model's."""
    cut = dict(moe_num_primary_experts=held, experts_held_from=first,
               source_values={"moe_num_primary_experts": 8})
    model, params = model_and_params("float32", **cut)
    trunk = weights_smallthinker.trunk_of(config("float32", **cut))
    assert (trunk["moe_num_primary_experts_routed"],
            params["layers_0"]["mlp"]["router"].shape,
            params["layers_0"]["mlp"]["up_proj"].shape) == (
        8, (64, 8), (held, 64, 32))
    x, t = inputs(2)
    got = forward(model, params, x, t)
    np.testing.assert_allclose(got, reference_forward(params, x, t,
                                                      trunk=trunk),
                               rtol=1e-5, atol=1e-6)
    # (a share's distance from the whole model, 1.7e-4 to 2.7e-4 under the
    # seeded down_proj's small scale, is a hundred times the tolerance above)
    whole, whole_params = model_and_params("float32")
    assert rms(got, forward(whole, whole_params, x, t)) > 1e-4


def test_gradients_flow_off_the_chip():
    """Every path is plain JAX off the TPU: two layers, one of each kind."""
    model, params = model_and_params("float32", num_hidden_layers=2)
    x, t = inputs(1)
    grads = jax.jit(jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x, t) ** 2)))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    for i in range(2):
        layer = norms[f"layers_{i}"]
        assert layer["self_attn"]["q_proj"]["kernel"] > 0
        assert layer["mlp"]["router"] > 0 and layer["mlp"]["gate_proj"] > 0
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(moe_primary_router_apply_softmax=False),
     "moe_primary_router_apply_softmax false"),
    (dict(rope_layout=[0, 1, 2, 1, 0]), r"rope_layout entries \[2\]"),
    (dict(sliding_window_layout=[0, 1, 1, -1, 0]),
     r"sliding_window_layout entries \[-1\]"),
    (dict(rope_layout=[0, 1, 1]), "rope_layout has 3 entries for 5 layers"),
    (dict(sliding_window_layout=[0, 1, 1, 1]),
     "sliding_window_layout has 4 entries for 5 layers"),
    (dict(num_attention_heads=15), "num_attention_heads 15 must divide"),
    (dict(num_key_value_heads=4), "num_key_value_heads 4"),
    (dict(experts_held_from=1), "experts 1..8 held of 8 routed"),
    (dict(moe_num_primary_experts_routed=4), "held of 4 routed"),
    (dict(model_type="llama"), "'kimi_linear', 'smallthinker', "),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_from_one_table():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (smallthinker.check_trunk,
                                            smallthinker.layer)
    assert list(hybrid.STACKS)[:7] == [
        "jamba", "laguna", "glm_moe_dsa", "pangu_ultra_moe", "nemotron_h",
        "kimi_linear", "smallthinker"]
    # every entry of the table is a module with the two names, the default
    # (no model_type) this module's own
    for model_type in hybrid.STACKS:
        check, layer = hybrid.stack_of({"model_type": model_type})
        assert callable(check) and callable(layer)
    assert hybrid.stack_of({}) == (hybrid.check_trunk, hybrid.layer)
    with pytest.raises(ValueError) as refused:
        hybrid.stack_of({"model_type": "llama"})
    assert all(repr(name) in str(refused.value) for name in hybrid.STACKS)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})
    # the published lists reach the stack as they are, longer than the depth
    assert model.trunk["rope_layout"] == (0, 1, 1, 1, 0, 1, 1, 1)
    assert hash(model) is not None  # jit's static argument


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/attn_full | attn_window | moe`` and, inside the last, the
    routing's own ``trunk/route`` in the lowered text; a turn counted a traced
    window layer's attention (its mask is counted by the launch, on the TPU
    only, as Laguna's), three products, one gated first half, its activation
    and the router's source a traced expert layer."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/attn_full", "trunk/attn_window", "trunk/moe",
                  "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name, counts in series.items():
            if name.startswith("kernels.") and name.endswith("/by_key"):
                for key, count in counts.items():
                    at = name[:-len("/by_key")], key
                    by_key[at] = by_key.get(at, 0) + count
    assert by_key == {("kernels.flash_fwd_rotary", "xla"): 3,
                      ("kernels.moe_gmm_schedule", "xla"): 15,
                      ("kernels.moe_gate_up_schedule", "xla"): 5,
                      ("kernels.moe_route_source", "layer_input"): 5,
                      ("kernels.moe_zero_experts", "none"): 5}
    metrics.reset()


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    """The trainer's ``build_model`` on a yaml whose ``trunk:`` carries the
    published keys: the same stack."""
    import yaml

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model

    path = tmp_path / "smallthinker.yaml"
    path.write_text(yaml.safe_dump({
        "image_size": [12, 52], "patch_size": 4, "trunk": dict(TRUNK)}))
    model = build_model(load_config(str(path)))
    assert isinstance(model, hybrid.HybridDenoiser)
    assert hybrid.stack_of(model.trunk)[1] is smallthinker.layer
    assert model.num_patches == 39


# ------------------------------------------- the expert layer's new options

def _experts_tree(seed=3, routed_on=64, shared=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, *shape: 0.2 * jax.random.normal(k, shape)
    tree = {"router": normal(ks[0], routed_on, 8),
            "gate_proj": normal(ks[1], 8, 64, 32),
            "up_proj": normal(ks[2], 8, 64, 32),
            "down_proj": normal(ks[3], 8, 32, 64)}
    if shared:
        tree["shared_expert"] = {
            "gate_proj": {"kernel": normal(ks[4], 64, shared)},
            "up_proj": {"kernel": normal(ks[5], 64, shared)},
            "down_proj": {"kernel": normal(ks[6], shared, 64)}}
    return tree


def _loop_over_experts(tree, z, read, act, first=0, held=8, top_k=3):
    """The layer in plain words: softmax over what the router is given to
    ``read``, the 3 largest renormalised, and a loop over the experts held on
    the rows ``z``; the shared expert where the tree has one."""
    hp = jax.lax.Precision.HIGHEST
    mm = lambda a, b: jnp.matmul(a, b, precision=hp)
    p = jax.nn.softmax(mm(read, tree["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(p, top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    mlp = lambda g, u, d: mm(act(mm(z, g)) * mm(z, u), d)
    out = jnp.zeros_like(z)
    if "shared_expert" in tree:
        out = mlp(*(tree["shared_expert"][k]["kernel"]
                    for k in ("gate_proj", "up_proj", "down_proj")))
    for e in range(first, first + held):
        weight = jnp.where(top_e == e, top_p, 0.0).sum(-1)
        out = out + weight[:, None] * mlp(*(tree[k][e - first] for k in (
            "gate_proj", "up_proj", "down_proj")))
    return out


@pytest.mark.parametrize("hidden_act", ["relu", "silu"])
@pytest.mark.parametrize("shared", [0, 16])
@pytest.mark.parametrize("routed_on", [None, 64, 24])
def test_held_experts_options_against_a_loop_over_the_experts(
        routed_on, shared, hidden_act):
    """``route_from`` (none, a tensor of the experts' width, one of another
    width: the router's first dim follows what it reads), ``shared_features``
    0 (no parameters, the sum from zeros) and ``hidden_act`` ``relu`` beside
    ``silu``, each combination against the loop written out; ``relu`` beside
    a shared expert, which no configuration has, is refused by name."""
    tree = _experts_tree(routed_on=routed_on or 64, shared=shared)
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 64))
    read = (None if routed_on is None else
            jax.random.normal(jax.random.PRNGKey(2), (2, 17, routed_on)))
    layer = HeldExpertsMlp(num_routed=8, top_k=3, first_held=0, num_held=8,
                           hidden_features=32, shared_features=shared,
                           hidden_act=hidden_act)
    if hidden_act == "relu" and shared:
        with pytest.raises(ValueError,
                           match="hidden_act 'relu' with a shared expert"):
            layer.apply({"params": tree}, z, read)
        return
    declared = jax.eval_shape(layer.init, jax.random.PRNGKey(0), z, read)
    assert jax.tree.map(lambda a: a.shape, declared["params"]) == (
        jax.tree.map(lambda a: a.shape, tree))
    got = layer.apply({"params": tree}, z, read)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[hidden_act]
    rows = lambda a: a.reshape(-1, a.shape[-1])
    want = _loop_over_experts(tree, rows(z), rows(z if read is None else read),
                              act).reshape(z.shape)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and the options are not each other: the other activation, the other
    # source, give another result
    other = {"relu": jax.nn.silu, "silu": jax.nn.relu}[hidden_act]
    assert float(jnp.abs(_loop_over_experts(
        tree, rows(z), rows(z if read is None else read), other
    ).reshape(z.shape) - got).max()) > 1e-2
    if routed_on == 64:
        assert float(jnp.abs(layer.apply({"params": tree}, z) - got
                             ).max()) > 1e-2


def test_held_experts_refuse_what_they_do_not_know():
    z = jnp.zeros((2, 5, 64))
    layer = lambda **kw: HeldExpertsMlp(
        num_routed=8, top_k=3, first_held=0, num_held=8, hidden_features=32,
        shared_features=0, **kw)
    with pytest.raises(ValueError, match="hidden_act 'gelu'"):
        layer(hidden_act="gelu").init(jax.random.PRNGKey(0), z)
    with pytest.raises(ValueError, match="route_from .* names other rows"):
        layer().init(jax.random.PRNGKey(0), z, jnp.zeros((2, 4, 64)))


def test_four_shares_of_two_experts_add_up_to_the_uncut_reference_layer():
    """The row's training deployment in small: four chips that hold 2 of the
    8 experts each (16 of 64 each, published), no shared expert — so nothing
    is counted once — routed from ANOTHER tensor than the experts read; the
    four partial results sum to the reference's whole layer, and no share is
    idle."""
    tree = _experts_tree()
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 64))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 17, 64))
    cfg = dict(TRUNK, moe_num_primary_experts=8)
    top_e, weight = ref.route(tree["router"], x.reshape(-1, 64), cfg,
                              ref.vit.EXACT)
    want = ref.experts({k: tree[k] for k in ref.BANKS}, z.reshape(-1, 64),
                       top_e, weight, cfg, ref.vit.EXACT).reshape(z.shape)
    shares = [HeldExpertsMlp(
        num_routed=8, top_k=3, first_held=first, num_held=2,
        hidden_features=32, shared_features=0, hidden_act="relu").apply(
        {"params": dict(tree, **{k: tree[k][first:first + 2]
                                 for k in ref.BANKS})}, z, x)
        for first in range(0, 8, 2)]
    assert all(float(jnp.abs(s).max()) > 1e-2 for s in shares)
    # four float32 partial sums of values up to 4 added in another order than
    # the reference's one running sum: 5e-6 absolute
    np.testing.assert_allclose(sum(shares), want, rtol=1e-5, atol=5e-6)
    # the reference given one share is that share
    one = ref.experts({k: tree[k][2:4] for k in ref.BANKS}, z.reshape(-1, 64),
                      top_e, weight, dict(cfg, moe_num_primary_experts=2,
                                          experts_held_from=2), ref.vit.EXACT)
    np.testing.assert_allclose(shares[1], one.reshape(z.shape), rtol=1e-5,
                               atol=1e-6)
