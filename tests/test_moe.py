"""Switch-MoE (models/moe.py) — routing oracle, aux loss, ep sharding, and
trainer integration. The reference has no MoE (its MLP is dense,
reference ViT.py:74-90); this is the 'expert' axis of the parallelism
story, beyond-parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.models.moe import SwitchMlp


def _mlp_params_and_out(key, B=2, N=16, D=8, E=4, cf=1.25):
    m = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                  capacity_factor=cf, drop=0.0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, N, D))
    # params only: init's variables also hold a "losses" entry, and passing
    # it back in would make apply APPEND a second sown value
    variables = {"params": m.init(key, x)["params"]}
    y, aux = m.apply(variables, x, mutable=["losses"])
    return m, variables, x, y, aux


def test_switch_mlp_routing_matches_numpy_oracle():
    """Top-1 routing with capacity: per batch row, the first C tokens
    arriving at each expert get gate·expert(x); overflow tokens get 0."""
    key = jax.random.PRNGKey(0)
    B, N, D, E = 2, 16, 8, 4
    cf = 0.5  # tight capacity → overflow actually happens
    m, variables, x, y, _ = _mlp_params_and_out(key, B, N, D, E, cf)
    p = variables["params"]

    import math

    C = max(1, math.ceil(N * cf / E))
    xn = np.asarray(x, np.float32)
    wr = np.asarray(p["router"])
    logits = xn @ wr
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros((B, N, D), np.float32)
    for b in range(B):
        counts = np.zeros(E, int)
        for n in range(N):
            e = int(np.argmax(probs[b, n]))
            gate = probs[b, n, e]
            if counts[e] < C:
                counts[e] += 1
                h = xn[b, n] @ np.asarray(p["w1"][e]) + np.asarray(p["b1"][e])
                h = 0.5 * h * (1.0 + np.vectorize(math.erf)(h / math.sqrt(2)))
                want[b, n] = (h @ np.asarray(p["w2"][e])
                              + np.asarray(p["b2"][e])) * gate
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)


def test_switch_mlp_aux_loss_sown_and_bounded():
    """The Switch load-balance loss E·Σ f_e·P_e is sown; it is ≥ 1 with
    equality only at perfect balance, and absent when not mutable."""
    key = jax.random.PRNGKey(1)
    m, variables, x, y, aux = _mlp_params_and_out(key)
    leaves = jax.tree.leaves(aux["losses"])
    assert len(leaves) == 1
    val = float(leaves[0])
    assert np.isfinite(val) and val >= 0.99  # ≥1 up to float error
    # immutable apply: sow is a silent no-op, same output
    y2 = m.apply(variables, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


def test_vit_with_experts_trains_and_routes_grads():
    """DiffusionViT(num_experts=4): forward is finite; the train step with
    the aux loss sends gradients through the router."""
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    model = DiffusionViT(img_size=(16, 16), patch_size=4, embed_dim=16,
                         depth=2, num_heads=2, total_steps=8, num_experts=4,
                         drop_rate=0.0, attn_drop_rate=0.0, drop_path_rate=0.0)
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
             jnp.asarray(rng.randint(1, 7, size=(4,)), jnp.int32))
    state = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10, batch)
    assert "moe" in state.params["blocks_0"]  # expert bank in place of mlp
    router_before = np.asarray(  # snapshot BEFORE the donating step
        state.params["blocks_0"]["moe"]["router"]).copy()
    step = make_train_step(model, moe_aux_weight=0.01)
    s2, loss, _ = step(state, batch, jax.random.PRNGKey(1), jnp.float32(5.0))
    assert np.isfinite(float(loss))
    # router moved → aux gradient flowed through the routing path
    delta = np.abs(np.asarray(s2.params["blocks_0"]["moe"]["router"])
                   - router_before)
    assert delta.max() > 0


@pytest.mark.parametrize("dispatch", ["einsum", "index"])
def test_expert_sharded_step_matches_single_device(dispatch):
    """dp×ep mesh: expert banks shard over 'expert', the step reproduces the
    unsharded result — for BOTH routing implementations (the einsums are
    layout-independent under GSPMD; the index path's gathers must be too)."""
    from ddim_cold_tpu.parallel import make_mesh, shard_batch, shard_train_state
    from ddim_cold_tpu.parallel.sharding import param_partition_specs
    from ddim_cold_tpu.train.step import create_train_state, make_train_step
    from jax.sharding import PartitionSpec as P

    def build():
        model = DiffusionViT(img_size=(16, 16), patch_size=4, embed_dim=16,
                             depth=1, num_heads=2, total_steps=8,
                             num_experts=4, drop_rate=0.0,
                             moe_dispatch=dispatch,
                             attn_drop_rate=0.0, drop_path_rate=0.0)
        rng = np.random.RandomState(0)
        batch = (jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
                 jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
                 jnp.asarray(rng.randint(1, 7, size=(4,)), jnp.int32))
        state = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10,
                                   batch)
        return model, state, batch

    model, s1, batch = build()
    step = make_train_step(model, moe_aux_weight=0.01)
    rng = jax.random.PRNGKey(7)
    s1, _, _ = step(s1, batch, rng, jnp.float32(5.0))

    _, s2, _ = build()
    mesh = make_mesh({"data": 2, "expert": 4})
    specs = param_partition_specs(s2.params, axes=("expert",))
    assert specs["blocks_0"]["moe"]["w1"] == P("expert", None, None)
    assert specs["blocks_0"]["moe"]["router"] == P()
    s2 = shard_train_state(s2, mesh, specs)
    s2, _, _ = step(s2, shard_batch(batch, mesh), rng, jnp.float32(5.0))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5),
        s1.params, s2.params)


def test_expert_parallel_composes_with_sequence_parallel():
    """ep×sp on one {data, seq, expert} mesh: ring attention over 'seq'
    (manual shard_map) with expert banks sharded over 'expert' (GSPMD) —
    the step must reproduce the unsharded single-device result."""
    from ddim_cold_tpu.parallel import make_mesh, shard_batch, shard_train_state
    from ddim_cold_tpu.parallel.sharding import param_partition_specs
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    def build(mesh=None):
        kw = dict(img_size=(16, 16), patch_size=4, embed_dim=16,
                  depth=1, num_heads=2, total_steps=8, num_experts=2,
                  drop_rate=0.0, attn_drop_rate=0.0, drop_path_rate=0.0)
        if mesh is not None:
            kw.update(seq_mesh=mesh, seq_axis="seq", batch_axis="data")
        model = DiffusionViT(**kw)
        rng = np.random.RandomState(0)
        batch = (jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
                 jnp.asarray(rng.randn(4, 16, 16, 3), jnp.float32),
                 jnp.asarray(rng.randint(1, 7, size=(4,)), jnp.int32))
        state = create_train_state(model, jax.random.PRNGKey(0), 1e-2, 10,
                                   batch)
        return model, state, batch

    model, s1, batch = build()
    rng = jax.random.PRNGKey(7)
    s1, _, _ = make_train_step(model, moe_aux_weight=0.01)(
        s1, batch, rng, jnp.float32(5.0))

    mesh = make_mesh({"data": 2, "seq": 2, "expert": 2})
    model2, s2, _ = build(mesh)
    specs = param_partition_specs(s2.params, axes=("expert",))
    s2 = shard_train_state(s2, mesh, specs)
    s2, _, _ = make_train_step(model2, moe_aux_weight=0.01)(
        s2, shard_batch(batch, mesh), rng, jnp.float32(5.0))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5),
        s1.params, s2.params)


@pytest.mark.isolated
def test_moe_trainer_end_to_end(tmp_path, synthetic_image_dir):
    """yaml num_experts=2 trains, evaluates (sow no-op on the immutable
    eval path), and checkpoints — in BOTH block layouts (scan_blocks
    composition was previously rejected; the scan now stacks the sown aux
    losses on the layer axis)."""
    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import run
    from tests.test_train import _write_config

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    num_experts=2, epoch=[0, 1]), "exp")
    result = run(cfg, str(tmp_path), log_every=2)
    assert result.steps == 5 and np.isfinite(result.last_val_loss)

    scanned = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                        num_experts=2, scan_blocks=True,
                                        epoch=[0, 1]), "exp")
    result = run(scanned, str(tmp_path / "scan"), log_every=2)
    assert result.steps == 5 and np.isfinite(result.last_val_loss)


def test_moe_expert_sharding_in_scan_layout():
    """Stacked scan_blocks MoE params are (depth, E, ...): the 'expert' spec
    must land on dim 1, not the leading layer axis (sharding dim 0 splits
    layers over the expert mesh — a crash whenever depth % E != 0, silently
    wrong layout otherwise). End-to-end: shard a depth-3, E-2 model on a
    {data, expert} mesh and take one finite step."""
    from ddim_cold_tpu.parallel.mesh import make_mesh, shard_batch, shard_train_state
    from ddim_cold_tpu.parallel.sharding import param_partition_specs
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    cfg = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=3,
               num_heads=2, num_experts=2, scan_blocks=True)
    model = DiffusionViT(**cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16, 3))
    t = jnp.array([3, 500, 9, 77], jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x, t)["params"]

    specs = param_partition_specs(params, axes=("expert",))
    # depth=3 is NOT divisible by E=2 — a dim-0 'expert' spec cannot even shard
    for spec in jax.tree.leaves(specs["blocks"]["moe"],
                                is_leaf=lambda s: not isinstance(s, dict)):
        if "expert" in tuple(spec):
            assert tuple(spec)[0] is None and tuple(spec)[1] == "expert", spec

    mesh = make_mesh({"data": 4, "expert": 2})
    batch = (x, x, t)
    state = create_train_state(model, jax.random.PRNGKey(2), lr=1e-3,
                               total_steps=10, sample_batch=batch)
    state = shard_train_state(state, mesh, specs)
    step = make_train_step(model, moe_aux_weight=0.01)
    state, loss, _ = step(state, shard_batch(batch, mesh),
                          jax.random.PRNGKey(3), jnp.float32(5.0))
    assert np.isfinite(float(loss)), loss


def test_moe_aux_loss_layout_parity():
    """The Switch aux loss is identical (same params, same inputs) whether
    the trunk is unrolled or nn.scan-stacked — the scan keeps the sown
    'losses' collection on the layer axis, and the step normalizes by total
    element count so both layouts weight it the same."""
    from ddim_cold_tpu.utils import checkpoint as ckpt

    cfg = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
               num_heads=2, num_experts=2, drop_rate=0.0, attn_drop_rate=0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16, 3))
    t = jnp.array([3, 500, 9, 77], jnp.int32)
    loop = DiffusionViT(**cfg)
    scan = DiffusionViT(scan_blocks=True, **cfg)
    params = loop.init(jax.random.PRNGKey(1), x, t)["params"]
    stacked = ckpt.stack_block_params(params)

    def total_aux(model, p):
        out, aux_vars = model.apply({"params": p}, x, t, mutable=["losses"])
        sown = jax.tree.leaves(aux_vars.get("losses", {}))
        n = sum(s.size for s in sown)
        return out, sum(jnp.sum(s) for s in sown) / n

    out_a, aux_a = total_aux(loop, params)
    out_b, aux_b = total_aux(scan, stacked)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_a),
                               rtol=1e-5, atol=1e-6)
    assert float(aux_a) > 0.0
    np.testing.assert_allclose(float(aux_b), float(aux_a), rtol=1e-6)


def test_expert_mesh_axis_validated(tmp_path, synthetic_image_dir):
    """An 'expert' mesh axis without (divisible) num_experts fails fast: the
    first thing _train checks, so nothing `isolated` contains is reached."""
    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import run
    from tests.test_train import _write_config

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    mesh={"data": 2, "expert": 2}), "exp")
    with pytest.raises(ValueError, match="expert"):
        run(cfg, str(tmp_path), log_every=2)


@pytest.mark.isolated
def test_moe_bridge_refusal_and_warm_start_fallback(tmp_path,
                                                    synthetic_image_dir):
    """MoE params have no reference torch layout: the pkl bridge refuses
    them with a clear error, and a warm-starting MoE run falls back to an
    orbax init persist instead of crashing at startup."""
    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.trainer import run
    from ddim_cold_tpu.utils import checkpoint as ckpt
    from tests.test_train import _write_config

    model = DiffusionViT(img_size=(16, 16), patch_size=4, embed_dim=16,
                         depth=1, num_heads=2, num_experts=2)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 16, 16, 3), np.float32),
                        np.zeros((1,), np.int32))["params"]
    with pytest.raises(ValueError, match="no reference torch layout"):
        ckpt.torch_state_dict_from_flax(params, patch_size=4)

    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    num_experts=2, epoch=[0, 1],
                                    initializing="warm.pkl"), "exp")
    result = run(cfg, str(tmp_path), log_every=2)
    assert result.steps == 5
    import os as _os

    init = _os.path.join(str(tmp_path), "Saved_Models", "warm.pkl")
    assert _os.path.isdir(init)  # orbax fallback, not a pkl file
    log = open(_os.path.join(result.run_dir, "train.log")).read()
    assert "init pkl export unavailable" in log


def test_num_experts_validated(tmp_path, synthetic_image_dir):
    from ddim_cold_tpu.config import load_config
    from tests.test_train import _write_config

    with pytest.raises(ValueError, match="num_experts"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  num_experts=0), "exp")


def test_switch_mlp_out_features_respected():
    """out_features != input width projects to the declared width (the field
    must not be dead code)."""
    m = SwitchMlp(num_experts=2, hidden_features=8, out_features=6, drop=0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 4))
    variables = {"params": m.init(jax.random.PRNGKey(1), x)["params"]}
    y = m.apply(variables, x)
    assert y.shape == (1, 8, 6)


def test_moe_config_knobs_validated(tmp_path, synthetic_image_dir):
    from ddim_cold_tpu.config import load_config
    from tests.test_train import _write_config

    with pytest.raises(ValueError, match="moe_capacity_factor"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  moe_capacity_factor=0.0), "exp")
    with pytest.raises(ValueError, match="moe_aux_weight"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  moe_aux_weight=-0.1), "exp")


def test_index_dispatch_matches_einsum():
    """The sort/gather dispatch is numerically interchangeable with the
    one-hot einsum dispatch — same params, same inputs, same outputs, same
    aux loss — including under tight capacity where overflow happens (the
    stable sort must drop exactly the cumsum-priority overflow set)."""
    key = jax.random.PRNGKey(3)
    for cf in (1.25, 0.5):  # roomy and overflowing
        B, N, D, E = 2, 16, 8, 4
        m_e = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                        capacity_factor=cf, drop=0.0)
        m_i = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                        capacity_factor=cf, drop=0.0, dispatch="index")
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, N, D))
        variables = {"params": m_e.init(key, x)["params"]}
        y_e, aux_e = m_e.apply(variables, x, mutable=["losses"])
        y_i, aux_i = m_i.apply(variables, x, mutable=["losses"])
        np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_e),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(aux_i)[0]),
            np.asarray(jax.tree.leaves(aux_e)[0]), rtol=1e-6)


def test_index_dispatch_gradients_match_einsum():
    """Both dispatch modes differentiate to the same parameter gradients —
    the gather/scatter-free combine must not detach any path."""
    key = jax.random.PRNGKey(4)
    B, N, D, E = 2, 12, 8, 4
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, N, D))
    m_e = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                    capacity_factor=0.75, drop=0.0)
    m_i = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                    capacity_factor=0.75, drop=0.0, dispatch="index")
    params = m_e.init(key, x)["params"]

    def loss(mod, p):
        return jnp.sum(mod.apply({"params": p}, x) ** 2)

    g_e = jax.grad(lambda p: loss(m_e, p))(params)
    g_i = jax.grad(lambda p: loss(m_i, p))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        g_e, g_i)


def test_index_dispatch_in_model_and_config(tmp_path, synthetic_image_dir):
    """moe_dispatch threads YAML → config → model → SwitchMlp, validates its
    values, and the index model trains a step."""
    from ddim_cold_tpu.config import load_config
    from ddim_cold_tpu.train.step import create_train_state, make_train_step
    from tests.test_train import _write_config

    with pytest.raises(ValueError, match="moe_dispatch"):
        load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                  moe_dispatch="sparse"), "exp")
    cfg = load_config(_write_config(str(tmp_path), synthetic_image_dir,
                                    num_experts=2, moe_dispatch="index"),
                      "exp")
    assert cfg.model_kwargs()["moe_dispatch"] == "index"

    model = DiffusionViT(img_size=(16, 16), patch_size=8, embed_dim=32,
                         depth=1, num_heads=2, num_experts=2,
                         moe_dispatch="index")
    r = np.random.RandomState(0)
    batch = (jnp.asarray(r.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray(r.randn(2, 16, 16, 3), jnp.float32),
             jnp.asarray(r.randint(1, 7, size=(2,)), jnp.int32))
    state = create_train_state(model, jax.random.PRNGKey(0), lr=1e-3,
                               total_steps=10, sample_batch=batch)
    step = make_train_step(model, moe_aux_weight=0.01)
    state, loss, _ = step(state, batch, jax.random.PRNGKey(1),
                          jnp.float32(5.0))
    assert np.isfinite(float(loss))


def test_index_dispatch_long_sequence_parity():
    """N=2501 (the 200px/p4 token count): the index path matches the einsum
    path at the scale it exists for. B=1 keeps the einsum reference's
    (B, N, E, C) dispatch tensor affordable (~31 MB) — at training batch
    sizes only the index path is viable, which is the point."""
    key = jax.random.PRNGKey(5)
    N, D, E = 2501, 32, 4
    m_e = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                    capacity_factor=1.25, drop=0.0)
    m_i = SwitchMlp(num_experts=E, hidden_features=D, out_features=D,
                    capacity_factor=1.25, drop=0.0, dispatch="index")
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, N, D))
    variables = {"params": m_e.init(key, x)["params"]}
    y_e = m_e.apply(variables, x)
    y_i = m_i.apply(variables, x)
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_e),
                               rtol=2e-5, atol=2e-6)
