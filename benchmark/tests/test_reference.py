"""The plain reference against the program's ``DiffusionViT`` at a toy size,
both in float32 (the configurations' bfloat16 is compared on the chip, at the
cells' own sizes, by every run's `correct`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import ddim, lowprec, train, vit

CONFIG = {"img_size": [16, 16], "patch_size": 4, "embed_dim": 32, "depth": 2,
          "num_heads": 4, "mlp_ratio": 1.0, "total_steps": 2000}


@pytest.fixture(scope="module")
def model_and_params():
    from ddim_cold_tpu.models import DiffusionViT

    jax.config.update("jax_default_matmul_precision", "float32")
    model = DiffusionViT(dtype=jnp.float32, img_size=(16, 16), patch_size=4,
                         embed_dim=32, depth=2, num_heads=4)
    return model, weights.make(CONFIG, 2**31 + 5)


def test_weights_have_the_programs_tree(model_and_params):
    model, params = model_and_params
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)),
        jnp.zeros((1,), jnp.int32))["params"])
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert all(a.shape == b.shape and b.dtype == jnp.float32 for a, b in
               zip(jax.tree.leaves(want), jax.tree.leaves(params)))
    again = weights.make(CONFIG, 2**31 + 5)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(again)))


def test_forward_matches_the_program(model_and_params):
    model, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16, 3))
    t = jnp.array([5, 100, 1999])
    got = model.apply({"params": params}, x, t)
    want = vit.forward(params, x, t, **weights.arch_of(CONFIG))
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_ddim_matches_the_program(model_and_params):
    from ddim_cold_tpu.ops import sampling

    model, params = model_and_params
    key = jax.random.PRNGKey(3)
    got = sampling.ddim_sample(model, params, key, k=200, n=2)
    x_init = jax.random.normal(key, (2, 16, 16, 3), jnp.float32)
    want = ddim.sample(params, x_init, k=200, total_steps=2000,
                       arch=weights.arch_of(CONFIG))
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_cold_batch_matches_the_programs_degradation():
    from ddim_cold_tpu.ops import degrade

    base = np.random.default_rng(0).integers(0, 256, (5, 16, 16, 3), np.uint8)
    t = np.array([1, 2, 3, 4, 1], np.int32)
    prepare = degrade.make_cold_prepare(size=16, max_step=4, chain=True)
    noisy, target, _ = prepare((jnp.asarray(base), jnp.asarray(t)), None)
    want_noisy, want_target = train.cold_batch(base, t)
    assert np.array_equal(np.asarray(noisy), want_noisy)
    assert np.array_equal(np.asarray(target), want_target)


def test_lower_precision_moves_the_forward(model_and_params):
    _, params = model_and_params
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 16, 3))
    t = jnp.array([5, 100, 1999])
    arch = weights.arch_of(CONFIG)
    exact = vit.forward(params, x, t, **arch)
    err = {name: float(jnp.sqrt(jnp.mean(
        (vit.forward(params, x, t, ops=ops, **arch) - exact) ** 2)))
        for name, ops in lowprec.BY_NAME.items()}
    assert err["float32"] == 0.0
    assert err["float8_e4m3"] > 4 * err["bfloat16"] > 0.0
