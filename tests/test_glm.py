"""models/glm.py at toy size (hidden 64, 4 heads of 24 + 8 query/key dims and
32 value dims on a 16-dim key/value latent, 4 index heads of 16 choosing 8 of
17 tokens so that the selection bites, published layers 2-6 of an 8-long
pattern: full + dense, three shared + sparse, full + sparse; 16 router outputs
top-3 under a sigmoid with a selection bias, experts 0-7 held; 16x16 px patch
4) on seeded weights, against the plain reference
(``benchmark/reference/glm.py``, which imports nothing of the program): the
forward, borrowed selections, the interleaved rotary pairing, the DDIM
trajectory, causality, serving, refusals, scopes and counters; how k and
v reach the attention launch: written once each by ``kv_b_proj``'s own GEMMs,
bit for bit the concatenate and the slice they replace; and how q does:
unturned, its rotation handed on, off the TPU bit for bit the parent's."""

import flax.linen as nn
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_glm
from benchmark.reference import glm as ref
from benchmark.reference import lowprec
from ddim_cold_tpu import serve
from ddim_cold_tpu.models import glm, hybrid, laguna
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import flash_attention as fa
from ddim_cold_tpu.ops import sampling

PUBLISHED = dict(
    model_type="glm_moe_dsa", hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
    head_dim=24, hidden_act="silu", attention_bias=False, rms_norm_eps=1e-5,
    q_lora_rank=32, kv_lora_rank=16, qk_head_dim=32, qk_nope_head_dim=24,
    qk_rope_head_dim=8, v_head_dim=32, rope_interleave=True,
    rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
    index_n_heads=4, index_head_dim=16, index_topk=8,
    indexer_rope_interleave=True,
    indexer_types=["full"] * 3 + ["shared"] * 3 + ["full", "shared"],
    mlp_layer_types=["dense"] * 3 + ["sparse"] * 5, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=3, moe_intermediate_size=32,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="noaux_tc", n_group=1, topk_group=1)
SIZES = dict(img_size=[16, 16], patch_size=4, in_chans=3, total_steps=2000)


def config(precision, **changes):
    return {**PUBLISHED, **SIZES, "precision": precision, "layers_from": 2,
            "source_values": {"n_routed_experts": 16}, "experts_held_from": 0,
            **changes}


TRUNK = weights_glm.trunk_of(config("float32"))


def model_and_params(precision, seed=7, **changes):
    dtype = weights_glm.DTYPES[precision]
    cfg = config(precision, **changes)
    model = hybrid.HybridDenoiser(
        trunk=weights_glm.trunk_of(cfg), img_size=(16, 16), patch_size=4,
        total_steps=2000, dtype=dtype, param_dtype=dtype)
    return model, weights_glm.make(cfg, seed)


def inputs(n=3, seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, 16, 16, 3))
    return x, jnp.array([1999, 700, 3][:n], jnp.int32)


def reference_forward(params, x, t, ops=ref.vit.EXACT, trunk=TRUNK):
    return ref.forward(params, x, t, trunk=trunk, patch_size=4, ops=ops)


def rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)) ** 2)))


def test_forward_matches_the_reference_in_float32():
    """17 tokens, 8 keys a query: rows 8-16 choose, and choose alike."""
    model, params = model_and_params("float32")
    x, t = inputs()
    got = model.apply({"params": params}, x, t)
    want = reference_forward(params, x, t)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    assert float(jnp.abs(want).mean()) > 0.05  # the comparison has a signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # and the selection is not a causal mask in disguise
    dense = reference_forward(params, x, t, trunk=dict(TRUNK, index_topk=17))
    assert rms(dense, want) > 2e-4  # ten times the tolerance above


#: rms of one bfloat16 forward against the float32 reference on the same
#: bfloat16 tree, a key or an expert that flips at a near-tie included; the
#: float8 control reads several times that
BF16_FORWARD_RMS = 4e-3


def test_forward_in_bfloat16_is_within_a_tolerance_the_float8_control_fails():
    model, params = model_and_params("bfloat16")
    x, t = inputs()
    want = reference_forward(params, x, t)
    got = model.apply({"params": params}, x, t)
    control = reference_forward(params, x, t, ops=lowprec.FP8)
    assert rms(got, want) < BF16_FORWARD_RMS < rms(control, want), (
        rms(got, want), rms(control, want))


def test_a_shared_layer_holds_no_indexer_and_attends_over_the_selection_it_is_handed():
    model, params = model_and_params("float32")
    has = [("indexer" in params[f"layers_{i}"]["self_attn"]) for i in range(5)]
    assert has == [True, False, False, False, True]
    layer = glm.GlmLayer(model.trunk, 1)  # published layer 3: shared, sparse
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 17, 64))
    full = jnp.asarray(np.tril(np.ones((2, 17, 17), bool)))
    few = full & (jnp.arange(17)[None, None, :] % 3 == 0)  # key 0 in every row
    pad = lambda keep: jnp.pad(keep.astype(jnp.int8), ((0, 0), (0, 7), (0, 7)))
    outs = {}
    for name, keep in (("full", full), ("few", few)):
        got, handed_on = layer.apply({"params": params["layers_1"]}, x, pad(keep))
        want, _ = ref.layer(params["layers_1"], x, keep, TRUNK, 1)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert (handed_on == pad(keep)).all()  # and hands it on as it got it
        outs[name] = got
    assert float(jnp.abs(outs["full"] - outs["few"]).max()) > 1e-3
    with pytest.raises(ValueError, match="shares a key selection"):
        layer.apply({"params": params["layers_1"]}, x)


def test_a_full_layer_selects_for_itself_whatever_it_is_handed():
    model, params = model_and_params("float32")
    layer = glm.GlmLayer(model.trunk, 4)  # published layer 6: full, sparse
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 17, 64))
    got, own = layer.apply({"params": params["layers_4"]}, x,
                           jnp.ones((1, 24, 24), jnp.int8))
    alone, own2 = layer.apply({"params": params["layers_4"]}, x)
    want, keep = ref.layer(params["layers_4"], x, None, TRUNK, 4)
    np.testing.assert_allclose(got, alone)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert (np.asarray(own)[:, :17, :17] != 0).tolist() == np.asarray(keep).tolist()
    assert (own == own2).all()
    assert np.asarray(keep).sum(-1).tolist() == [
        [min(t + 1, 8) for t in range(17)]]


def _parents_keys_and_values(kernel, c_kv, k_r, H, nope, vd):
    """k and v as the stack assembled them before: the published product, a
    head's ``[k_nope, v]`` side by side, then ``k_h = [k_nope_h, k_r]`` by a
    concatenate with k_r broadcast to every head, and v by a slice."""
    n, L, rot = k_r.shape
    kv = jnp.dot(c_kv, kernel).reshape(n, L, H, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None, :], (n, L, H, rot))],
        axis=-1)
    return k, kv[..., nope:]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("H,nope,rot,vd,rank", [
    (2, 192, 64, 256, 512),  # the published split: a head on two lane groups
    (5, 24, 8, 16, 12)])
def test_k_and_v_leave_the_gemms_bitwise_what_concatenate_and_slice_built(
        H, nope, rot, vd, rank, dtype):
    """The placement rows pass k_r, as drawn, through a product with 1 and add
    exact zeros to k_nope. c_kv and the kernel lie on a grid of quarters, so
    every sum is exact and the comparison does not hang on the order in which
    the CPU's GEMM adds at one shape or another."""
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    quarters = lambda key, shape: (jnp.round(4 * jax.random.normal(key, shape))
                                   / 4).astype(dtype)
    kernel = quarters(keys[0], (rank, H * (nope + vd)))
    c_kv = quarters(keys[1], (2, 7, rank))
    k_r = jax.random.normal(keys[2], (2, 7, rot), dtype)
    k, v = glm._KeysAndValuesInPlace(H, nope, vd, dtype, dtype).apply(
        {"params": {"kernel": kernel}}, c_kv, k_r)
    assert k.dtype == v.dtype == dtype
    assert k.shape == (2, 7, H * (nope + rot)) and v.shape == (2, 7, H * vd)
    want_k, want_v = _parents_keys_and_values(kernel, c_kv, k_r, H, nope, vd)
    for got, want in ((k, want_k), (v, want_v)):
        assert float(jnp.abs(want.astype(jnp.float32)).min(axis=(0, 1)).max()) > 0
        np.testing.assert_array_equal(
            np.asarray(got.reshape(want.shape), np.float32),
            np.asarray(want, np.float32))


class _ParentsLatentAttention(nn.Module):
    """``glm.LatentAttention`` as PR 41 had it: q turned as a whole array by
    ``apply_rotary(first=nope)`` before ``selected_attention``; the same
    parameter names, so the same tree."""

    trunk: dict
    indexer: bool

    @nn.compact
    def __call__(self, y, keep):
        c = self.trunk
        n, L, width = y.shape
        H, nope, rot = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"])
        hd = c["qk_head_dim"]
        kw = dict(dtype=jnp.float32, param_dtype=jnp.float32)
        rope = laguna.rotary_frequencies(c["rope_parameters"], rot)
        pairing = glm._pairing(c.get("rope_interleave", False))
        c_q, q, k_r, c_kv = glm.latent_paths(c, y, rope, pairing, **kw)
        q = laguna.apply_rotary(q, H, *rope, pairing=pairing, first=nope)
        k, v = glm._KeysAndValuesInPlace(H, nope, hd, name="kv_b_proj", **kw)(
            c_kv, k_r)
        if self.indexer:
            keep = glm.Indexer(c, name="indexer", **kw)(y, c_q)
        out = fa.selected_attention(
            q.reshape(n, L, H, hd), k.reshape(n, L, H, hd),
            v.reshape(n, L, H, hd), hd ** -0.5, keep)
        return glm._dense(width, "o_proj", **kw)(out.reshape(n, L, H * hd)), keep


@pytest.mark.parametrize("interleave", [True, False])
def test_off_the_tpu_the_attention_is_the_parents_bit_for_bit(interleave):
    """q handed on unturned and turned by ``apply_rotary`` inside
    ``selected_attention`` (the path off the TPU) is the whole-array rotation
    before it that the parent ran: the same operations on the same values."""
    trunk = dict(TRUNK, rope_interleave=interleave)
    _, params = model_and_params("float32")
    p = {"params": params["layers_0"]["self_attn"]}  # a full layer: an indexer
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 17, 64))
    got, keep = glm.LatentAttention(trunk, True).apply(p, y, None)
    want, keep2 = _ParentsLatentAttention(trunk, True).apply(p, y, None)
    np.testing.assert_array_equal(keep, keep2)
    np.testing.assert_array_equal(got, want)
    assert np.abs(np.asarray(got)).max() > 0


def _equations_before(jaxpr, wanted):
    """Every equation of ``jaxpr`` (and of the closed jaxprs inside those)
    that the variables ``wanted`` are computed from."""
    found = []
    for eqn in reversed(jaxpr.eqns):
        if not any(v in wanted for v in eqn.outvars):
            continue
        found.append(eqn)
        wanted |= {v for v in eqn.invars
                   if not isinstance(v, jax.extend.core.Literal)}
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _equations_before(inner, set(inner.outvars))
    return found


def test_nothing_of_a_head_wise_keys_size_is_moved_between_kv_b_proj_and_the_launch(
        monkeypatch):
    """What stands in for a counter (the layout has no second path to count):
    in the jaxpr of one layer, nothing that k and v are computed from is a
    ``concatenate``, ``broadcast_in_dim``, ``slice`` or ``pad`` of ``n·L·H·
    nope`` elements or more — the two GEMMs write what the launch reads."""
    model, params = model_and_params("float32")

    @jax.jit
    def the_launch(q, k, v, keep):
        return q + k + v

    turn_before = False  # the stand-in takes q as the launch does: unturned

    def stand_in(q, k, v, scale, keep, rotary):
        if turn_before:
            q = rotary.apply(q.reshape(*q.shape[:2], -1), H).reshape(q.shape)
        return the_launch(q, k, v, keep)

    monkeypatch.setattr(glm, "selected_attention", stand_in)
    layer = glm.GlmLayer(model.trunk, 1)  # published layer 3: shared, sparse
    n, L, H, nope = 3, 17, 4, 24
    x = jnp.zeros((n, L, 64))

    def traced():
        jaxpr = jax.make_jaxpr(
            lambda p, x, keep: layer.apply({"params": p}, x, keep))(
            params["layers_1"], x, jnp.ones((n, 24, 24), jnp.int8)).jaxpr
        (call,) = [e for e in jaxpr.eqns
                   if e.params.get("name") == "the_launch"]
        return jaxpr, call.invars

    jaxpr, (q, k, v, _) = traced()
    assert k.aval.shape == v.aval.shape == (n, L, H, 32)
    before = _equations_before(jaxpr, {k, v})
    names = {e.primitive.name for e in before}
    assert {"dot_general", "concatenate"} <= names  # the walk saw the GEMMs
    moved = [(e.primitive.name, var.aval.shape) for e in before
             if e.primitive.name in ("concatenate", "broadcast_in_dim", "slice",
                                     "pad", "gather", "dynamic_slice")
             for var in (*e.invars, *e.outvars)
             if np.prod(var.aval.shape) >= n * L * H * nope]
    assert not moved, moved
    # nor is q: it reaches the launch as q_b_proj wrote it, and is turned there
    rolls = lambda jaxpr, q: [
        e for e in _equations_before(jaxpr, {q})
        if e.primitive.name in ("slice", "concatenate")
        and np.prod(e.outvars[0].aval.shape) >= n * L * H * nope]
    assert not rolls(jaxpr, q)
    # the walk does tell: a q rotated as a whole array is rolled by slices
    turn_before = True
    jaxpr, (q, *_) = traced()
    assert rolls(jaxpr, q)


def test_the_parameter_tree_keeps_the_published_names_shapes_and_column_order():
    """``kv_b_proj/kernel`` is ``(rank, H·(nope + vd))`` and column
    ``h·(nope + vd) + j`` is what output j of head h reads: k_nope for j <
    nope, v after."""
    model, params = model_and_params("float32")
    x, t = inputs(1)
    made = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x, t))
    shapes = lambda tree: {jax.tree_util.keystr(k): v.shape for k, v in
                           jax.tree_util.tree_leaves_with_path(tree)}
    assert shapes(made["params"]) == shapes(params)
    attn = params["layers_1"]["self_attn"]
    assert sorted(attn) == ["kv_a_layernorm", "kv_a_proj_with_mqa", "kv_b_proj",
                            "o_proj", "q_a_layernorm", "q_a_proj", "q_b_proj"]
    H, nope, rot, vd, rank = 4, 24, 8, 32, 16
    assert attn["kv_b_proj"]["kernel"].shape == (rank, H * (nope + vd))
    assert attn["q_b_proj"]["kernel"].shape == (32, H * (nope + rot))
    kernel = attn["kv_b_proj"]["kernel"]
    c_kv = jax.random.normal(jax.random.PRNGKey(2), (1, 5, rank))
    k_r = jax.random.normal(jax.random.PRNGKey(3), (1, 5, rot))
    run = lambda kernel: glm._KeysAndValuesInPlace(H, nope, vd).apply(
        {"params": {"kernel": kernel}}, c_kv, k_r)
    k, v = (a.reshape(1, 5, H, 32) for a in run(kernel))
    for h, j in ((0, 0), (2, 23), (1, 24), (3, 55)):
        column = h * (nope + vd) + j
        k2, v2 = (a.reshape(1, 5, H, 32) for a in run(
            kernel.at[:, column].add(1.0)))
        moved = (np.abs(np.asarray(k2 - k)).max((0, 1)) > 0,
                 np.abs(np.asarray(v2 - v)).max((0, 1)) > 0)
        want = np.zeros((2, H, 32), bool)
        want[(0, h, j) if j < nope else (1, h, j - nope)] = True
        assert (np.stack(moved) == want).all(), (h, j)


@pytest.mark.parametrize("heads,first,pairing", [
    (3, 8, "interleave"), (1, 0, "interleave"), (3, 4, "rotate_half")])
def test_rotary_pairing_and_offset_on_the_token_major_array(heads, first,
                                                            pairing):
    """dims ``first .. first + 8`` of every 16-dim head turn, dims 2j and
    2j + 1 together (``interleave``) or j and j + 4 (``rotate_half``)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, heads * 16))
    inv, scale = laguna.rotary_frequencies(
        {"rope_theta": 8000000, "rope_type": "default"}, 8)
    got = laguna.apply_rotary(x, heads, inv, scale, pairing=pairing,
                              first=first)
    want = ref.rotary(x.reshape(2, 9, heads, 16), 8000000.0, first, 8,
                      pairing == "interleave").reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    view = lambda a: np.asarray(a).reshape(2, 9, heads, 16)
    turned = np.abs(view(got) - view(x))[:, 1:]
    assert turned[..., first:first + 8].max() > 0.1
    assert turned[..., :first].max(initial=0) == 0
    assert turned[..., first + 8:].max(initial=0) == 0
    # a rotation: each pair keeps its length
    pairs = ((lambda a: a[..., first:first + 8].reshape(2, 9, heads, 4, 2))
             if pairing == "interleave" else
             (lambda a: np.stack([a[..., first:first + 4],
                                  a[..., first + 4:first + 8]], -1)))
    np.testing.assert_allclose(np.linalg.norm(pairs(view(got)), axis=-1),
                               np.linalg.norm(pairs(view(x)), axis=-1),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="pairing"):
        laguna.apply_rotary(x, heads, inv, scale, pairing="pairs")


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
    """Every path is plain JAX off the TPU; the selection is piecewise
    constant and carries no gradient of its own."""
    model, params = model_and_params("float32")
    x, t = inputs(2)
    grads = jax.grad(lambda p: jnp.sum(
        model.apply({"params": p}, x, t) ** 2))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert norms["layers_1"]["self_attn"]["kv_b_proj"]["kernel"] > 0
    assert norms["layers_4"]["mlp"]["router"] > 0
    assert all(np.isfinite(v) for v in jax.tree.leaves(norms))
    assert max(jax.tree.leaves(norms["layers_0"]["self_attn"]["indexer"])) == 0


@pytest.mark.parametrize("change,match", [
    (dict(attention_bias=True), "attention_bias"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(n_group=8), "n_group"),
    (dict(v_head_dim=16), "v_head_dim 16 against qk_head_dim 32"),
    (dict(qk_nope_head_dim=16), "qk_nope_head_dim"),
    (dict(layers_from=3), "starts at a layer with a 'full' indexer"),
    (dict(indexer_types=["full"] * 5), "5 entries for layers 2..6"),
    (dict(indexer_types=["full", "full", "full", "local"] + ["shared"] * 4),
     "local"),
    (dict(experts_held_from=9), "held of 16 routed"),
    (dict(model_type="llama"), "'jamba', 'laguna', 'glm_moe_dsa', 'pangu"),
])
def test_what_the_stack_cannot_run_is_refused_at_construction(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.HybridDenoiser(trunk=dict(TRUNK, **change))


def test_the_stack_is_chosen_by_model_type_and_refuses_blocks_options():
    model, _ = model_and_params("float32")
    assert hybrid.stack_of(model.trunk) == (glm.check_trunk, glm.layer)
    for option in ("quant", "use_flash", "cache_mode"):
        with pytest.raises(ValueError, match=option):
            model.clone(**{option: "w8a16" if option == "quant" else True})


def test_the_named_scopes_and_counters_of_a_trace():
    """``trunk/mla | dsa_index | moe | route | mlp`` in the lowered text; one count a
    traced layer by indexer kind and by where q's rotation runs, one a
    selection and three an expert layer by path."""
    model, params = model_and_params("float32")
    x, t = inputs()
    metrics.reset()
    text = jax.jit(lambda p: model.apply({"params": p}, x, t)).lower(
        params).as_text(debug_info=True)
    for scope in ("trunk/mla", "trunk/dsa_index", "trunk/moe", "trunk/mlp",
                  "trunk/route"):
        assert scope in text, scope
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.dsa_indexer_layers", "kernels.dsa_select_schedule",
                     "kernels.moe_gmm_schedule",
                     "kernels.moe_gate_up_schedule",
                     "kernels.flash_fwd_rotary"):
            for key, count in series.get(name + "/by_key", {}).items():
                by_key[name, key] = by_key.get((name, key), 0) + count
    assert by_key == {("kernels.dsa_indexer_layers", "full"): 2,
                      ("kernels.dsa_indexer_layers", "shared"): 3,
                      ("kernels.flash_fwd_rotary", "xla"): 5,  # off the TPU
                      ("kernels.dsa_select_schedule", "xla"): 2,
                      ("kernels.moe_gmm_schedule", "xla"): 12,
                      ("kernels.moe_gate_up_schedule", "xla"): 4}
    metrics.reset()


def test_build_model_builds_the_trunk_from_a_yaml(tmp_path):
    """The trainer's ``build_model`` on a yaml whose ``trunk:`` carries the
    published keys: the same stack, and ``use_flash`` refused by name."""
    import yaml

    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import build_model

    def build(**extra):
        raw = dict(image_size=[16, 16], patch_size=4, trunk=TRUNK, **extra)
        path = tmp_path / "glm.yaml"
        path.write_text(yaml.safe_dump(raw))
        return build_model(load_config(str(path)))

    model = build()
    assert isinstance(model, hybrid.HybridDenoiser) and model.depth == 5
    assert hybrid.stack_of(model.trunk) == (glm.check_trunk, glm.layer)
    with pytest.raises(ValueError, match="use_flash"):
        build(use_flash=True)
