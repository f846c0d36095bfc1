"""The token-wise half of a ViT block as two kernels (ops/block_kernels.py),
on the CPU interpreter: against the XLA composition of the same ``Block`` on
one parameter tree, the counter that says which path a trace took, the
gradient through a deterministic forward, and the training step's trace,
which must never reach the kernels or import their module.

The interpreter fills what a block reads past its array with NaN, so a case
whose last row block is ragged also shows that nothing leaks out of the rows
it was read into.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ddim_cold_tpu.models.vit import Block, _kernels

def _f32(x):
    return np.asarray(x, np.float32)


def _close(got, want, dtype):
    """Equal within rounding order: in float32 a few units of 1e-5; in
    bfloat16 two units in the last place of the largest value (the residual
    stream is rounded at its own magnitude, so a sum that cancels inherits
    the stream's unit, not its own)."""
    want = _f32(want)
    tol = (dict(rtol=2e-5, atol=2e-5) if jnp.dtype(dtype) == jnp.float32
           else dict(rtol=2e-2, atol=2.0 ** -7 * np.abs(want).max()))
    np.testing.assert_allclose(_f32(got), want, **tol)


def _tree(blk, x, seed=0):
    """``blk``'s parameters with every leaf drawn, biases and LayerNorm
    offsets too (``init`` leaves them at 0 and 1, which would hide a bias
    the kernel forgot)."""
    params = blk.init(jax.random.PRNGKey(seed), x)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(treedef, [
        p + 0.05 * jax.random.normal(k, p.shape) for p, k in zip(leaves, keys)])


def _counted(fn):
    """``fn()`` and what it added to ``kernels.block_tokenwise``."""
    was = _kernels.by_key("kernels.block_tokenwise")
    out = fn()
    now = _kernels.by_key("kernels.block_tokenwise")
    return out, {k: n - was.get(k, 0) for k, n in now.items()
                 if n != was.get(k, 0)}


@pytest.mark.parametrize("mlp_ratio", [1.0, 4.0])
@pytest.mark.parametrize("tokens", [512, 517], ids=["whole", "ragged"])
@pytest.mark.parametrize("qkv_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("C", [256, 384])
def test_block_matches_the_xla_composition(C, dtype, qkv_bias, tokens,
                                           mlp_ratio):
    """The deterministic forward (both kernels, attention between them as it
    was) against the composition the same ``Block`` runs with
    ``deterministic=False`` and every rate 0, on one tree: same dtype, equal
    within rounding order. 512 tokens are whole row blocks, 517 end inside
    the last one (one of 528 rows in bfloat16, the second of 264 in float32)."""
    blk = Block(dim=C, num_heads=C // 64, mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias, dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, tokens, C), blk.dtype)
    params = _tree(blk, x)
    assert ("bias" in params["params"]["attn"]["qkv"]) == qkv_bias
    got, took = _counted(lambda: blk.apply(params, x))
    want, fell = _counted(lambda: blk.apply(params, x, deterministic=False))
    assert took == {"kernel": 1} and fell == {"xla": 1}
    assert got.dtype == want.dtype == blk.dtype and got.shape == x.shape
    assert np.isfinite(_f32(got)).all()
    _close(got, want, dtype)


@pytest.mark.parametrize("has_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens,rows", [(128, 64), (150, 64), (65, 80)])
def test_kernels_match_their_references(tokens, rows, dtype, has_bias):
    """Each launch against the XLA reference of the same function, at row
    blocks that divide the tokens (128 = 2 × 64), leave a ragged last block
    (150 = 2 × 64 + 22) or pass the array (65 in one block of 80): result in
    the input's dtype at every stage, equal within rounding order."""
    from ddim_cold_tpu.ops import block_kernels as bk

    C, hidden, dt = 256, 384, jnp.dtype(dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    rnd = lambda *shape: jax.random.normal(next(keys), shape)  # noqa: E731
    x, ctx = rnd(3, tokens, C).astype(dt), rnd(3, tokens, C).astype(dt)
    norm = (1.0 + 0.1 * rnd(C), 0.1 * rnd(C))
    w_qkv, b_qkv = 0.08 * rnd(C, 3 * C), 0.1 * rnd(3 * C) if has_bias else None
    packed = bk.ln_qkv(x, *norm, w_qkv, b_qkv, 1e-5, rows)
    want = bk.ln_qkv_reference(x, *norm, w_qkv, b_qkv, eps=1e-5)
    assert packed.dtype == want.dtype == dt
    assert packed.shape == (3, tokens, 3 * C)
    _close(packed, want, dtype)

    tail = (0.06 * rnd(C, C), 0.1 * rnd(C), *norm, 0.06 * rnd(C, hidden),
            0.1 * rnd(hidden), 0.06 * rnd(hidden, C), 0.1 * rnd(C))
    out = bk.block_tail(ctx, x, *tail, 1e-5, rows)
    want = bk.block_tail_reference(ctx, x, *tail, eps=1e-5)
    assert out.dtype == want.dtype == dt and out.shape == x.shape
    assert np.isfinite(_f32(out)).all()
    _close(out, want, dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernels_gelu_is_gelu_exact(dtype):
    """``block_tail``'s body divides by a reciprocal refined by two Newton
    steps where ``ops/quant.gelu_exact`` divides: the same function to
    float32 rounding over the whole clipped range and past it (the
    interpreter's seed is the coarsest there is, a bfloat16 reciprocal). Far
    in the negative tail, where the division gives erf = -1 and so an exact
    0, the product leaves 4e-8: the one place the two are told apart."""
    from ddim_cold_tpu.ops.quant import gelu_exact, gelu_exact_newton

    x = jnp.linspace(-9.0, 9.0, 300_001).astype(jnp.dtype(dtype))
    got, want = jax.jit(gelu_exact_newton)(x), jax.jit(gelu_exact)(x)
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_allclose(_f32(got), _f32(want), rtol=3e-7, atol=5e-7)


def test_row_block_is_chosen_from_the_shape():
    """Fewest equal blocks the VMEM model admits, in whole sublane tiles; no
    block where the image is short (the 64px models' 65 tokens: measured
    slower than XLA on the chip), the width leaves lanes empty or the weights
    do not fit."""
    from ddim_cold_tpu.ops import block_kernels as bk

    assert bk.row_block(2501, 256, 256, jnp.bfloat16) == 1264   # 2 blocks
    assert bk.row_block(517, 384, 384, jnp.bfloat16) == 528     # 1, past the array
    assert bk.row_block(517, 384, 384, jnp.float32) == 264      # 2, the last ragged
    assert bk.row_block(577, 256, 1024, jnp.float32) == 296     # 2 blocks
    assert bk.row_block(65, 384, 384, jnp.bfloat16) is None     # a short image
    assert bk.row_block(511, 256, 256, jnp.bfloat16) is None
    assert bk.row_block(2501, 192, 192, jnp.bfloat16) is None   # 192 % 128
    assert bk.row_block(2501, 256, 320, jnp.bfloat16) is None
    assert bk.row_block(197, 768, 3072, jnp.bfloat16) is None   # 14 MB of weights
    for C, hidden, dt in ((256, 256, jnp.bfloat16), (384, 1536, jnp.float32)):
        rows = bk.row_block(100_000, C, hidden, dt)
        isz = jnp.dtype(dt).itemsize
        assert bk._vmem_bytes(rows, C, hidden, isz) <= bk._SCOPED_VMEM_BYTES
        assert bk._vmem_bytes(2 * rows, C, hidden, isz) > bk._SCOPED_VMEM_BYTES


FALLBACKS = {
    "training": (dict(), dict(deterministic=False)),
    "probe": (dict(), dict(return_attention=True)),
    "quant": (dict(quant="xla"), dict()),
    "experts": (dict(num_experts=2), dict()),
    "sequence_parallel": (dict(seq_axis="seq"), dict()),
    "lanes": (dict(dim=192, num_heads=3), dict()),
    "weights_past_vmem": (dict(dim=768, num_heads=12, mlp_ratio=4.0), dict()),
    "input_dtype": (dict(dtype=jnp.bfloat16), dict()),
    "short_images": (dict(), dict()),
}


@pytest.mark.parametrize("case", ["kernel", *FALLBACKS])
def test_counter_says_which_path_a_trace_took(case):
    """``kernels.block_tokenwise``: +1 ``kernel`` for a deterministic trace
    of a plain block, +1 ``xla`` for each fallback — training, the attention
    probe, a quantised trunk, Switch-MoE experts, sequence parallelism, a
    width that leaves lanes empty, weights past the VMEM, an input not in the
    model's dtype, images of 65 tokens — and for ``init``. Traces only;
    nothing runs."""
    fields, call = FALLBACKS.get(case, (dict(), dict()))
    fields = dict(dict(dim=256, num_heads=4, mlp_ratio=1.0), **fields)
    if case == "sequence_parallel":
        fields["seq_mesh"] = Mesh(np.asarray(jax.devices()[:2]), ("seq",))
    blk = Block(**fields)
    tokens = 65 if case == "short_images" else 512
    x = jax.ShapeDtypeStruct((2, tokens, fields["dim"]), jnp.float32)
    rngs = {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)}
    params, took = _counted(lambda: jax.eval_shape(
        lambda: blk.init(rngs, jnp.zeros(x.shape, x.dtype))))
    assert took == {"xla": 1}  # init declares through the composition
    _, took = _counted(lambda: jax.eval_shape(
        lambda p, x: blk.apply(p, x, rngs={"dropout": rngs["dropout"]},
                               **call), params, x))
    assert took == {"kernel" if case == "kernel" else "xla": 1}


MESHES = {
    # name → (mesh axes, the axes an enclosing shard_map is manual over, path)
    "data_parallel": ({"data": 4}, None, "kernel"),
    "idle_model_axis": ({"data": 2, "model": 1}, None, "kernel"),
    "manual_over_all": ({"pipe": 2, "data": 2}, ("pipe", "data"), "kernel"),
    "tensor_parallel": ({"data": 2, "model": 2}, None, "xla"),
    "expert_parallel": ({"data": 2, "expert": 2}, None, "xla"),
    "pipeline_tensor_parallel": ({"pipe": 2, "model": 2}, ("pipe",), "xla"),
    "pipeline_idle_model_axis": ({"pipe": 2, "model": 1}, ("pipe",), "xla"),
}


@pytest.mark.parametrize("case", MESHES)
def test_mesh_decides_the_path(case):
    """The kernels are launched per device with their weights whole
    (``per_device``), so they run only where the ambient mesh leaves nothing
    to GSPMD: a ``data`` mesh, or a region manual over every axis. A
    tensor- or expert-parallel mesh keeps the composition, whose GEMMs GSPMD
    partitions by the Megatron-sharded weights, and so does
    ``parallel/pipeline.py``'s shard_map, manual over ``pipe`` alone with
    ``model`` left automatic: jit refuses a Mosaic launch there even where
    that axis has one device. Traces only."""
    from jax.sharding import PartitionSpec as P

    axes, manual, path = MESHES[case]
    n = int(np.prod(list(axes.values())))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))
    blk = Block(dim=256, num_heads=4, mlp_ratio=1.0)
    x = jnp.zeros((4, 512, 256))
    params = jax.eval_shape(lambda: blk.init(jax.random.PRNGKey(0), x))
    apply = lambda p, x: blk.apply(p, x)  # noqa: E731
    if manual is not None:
        apply = jax.shard_map(apply, mesh=mesh, in_specs=(P(), P()),
                              out_specs=P(), axis_names=frozenset(manual),
                              check_vma=False)
    with jax.set_mesh(mesh):
        _, took = _counted(lambda: jax.eval_shape(apply, params, x))
    assert took == {path: 1}


@pytest.mark.parametrize("use_flash", [False, True, "xla"],
                         ids=["dense", "flash", "blockwise"])
def test_attention_between_the_kernels_is_the_blocks_own(use_flash):
    """The kernels are token-wise and do not care which attention runs
    between them: the dense einsum, the flash kernel on the packed
    projection, the blockwise XLA path — each equals the composition with the
    same ``use_flash``."""
    blk = Block(dim=256, num_heads=4, mlp_ratio=1.0, qkv_bias=True,
                use_flash=use_flash)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 520, 256))
    params = _tree(blk, x)
    got, took = _counted(lambda: blk.apply(params, x))
    assert took == {"kernel": 1}
    np.testing.assert_allclose(
        _f32(got), _f32(blk.apply(params, x, deterministic=False)),
        rtol=2e-4, atol=2e-4)


def test_gradient_through_a_deterministic_forward_is_the_xla_paths():
    """Nobody differentiates the inference path today, and the entry is total
    all the same: the kernels' VJP is the VJP of their XLA reference, so the
    gradient by parameters and input equals the composition's."""
    blk = Block(dim=256, num_heads=4, mlp_ratio=1.0, qkv_bias=True)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 512, 256))
    params = _tree(blk, x)
    loss = lambda p, x, **kw: jnp.sum(  # noqa: E731
        jnp.sin(blk.apply(p, x, **kw)))
    got, took = _counted(lambda: jax.grad(loss, argnums=(0, 1))(params, x))
    want = jax.grad(loss, argnums=(0, 1))(params, x, deterministic=False)
    assert took == {"kernel": 1}
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(_f32(g), _f32(w), rtol=2e-4, atol=2e-4)


def test_scanned_and_unrolled_models_share_the_path_and_the_tree():
    """``DiffusionViT`` builds its blocks in a loop or under ``nn.scan``; both
    take the kernels on a deterministic forward, on the tree ``init`` made
    through the composition, and agree with their own training-mode forward."""
    from ddim_cold_tpu.models import DiffusionViT

    for scan in (False, True):
        model = DiffusionViT(img_size=(96, 96), patch_size=4, embed_dim=128,
                             depth=2, num_heads=2, scan_blocks=scan,
                             drop_rate=0.0, attn_drop_rate=0.0,
                             drop_path_rate=0.0)  # 577 tokens
        x = jax.random.uniform(jax.random.PRNGKey(2), (2, 96, 96, 3))
        t = jnp.asarray([3, 700], jnp.int32)
        params = model.init(jax.random.PRNGKey(0), x, t)
        got, took = _counted(lambda: model.apply(params, x, t))
        assert set(took) == {"kernel"}  # every trace of a block, no other
        want = model.apply(params, x, t, deterministic=False,
                           rngs={"dropout": jax.random.PRNGKey(1)})
        np.testing.assert_allclose(_f32(got), _f32(want), rtol=2e-4, atol=2e-4)


def test_training_step_never_reaches_the_kernels():
    """``train.step.make_train_step`` at ``flower200_p4``'s widths and depth,
    state from ``create_train_state`` (``init``), traced in a fresh
    interpreter: every block of both traces counts ``xla``, the step launches
    ``fwd`` and ``dqkv`` and neither of the two kernels, and ``ops.block_kernels`` was never imported — the
    training programs are the parent's by construction."""
    script = textwrap.dedent("""
        import re, sys
        import jax, jax.numpy as jnp
        from ddim_cold_tpu.models import MODEL_CONFIGS, DiffusionViT
        from ddim_cold_tpu.models.vit import _kernels
        from ddim_cold_tpu.train.step import create_train_state, make_train_step

        cfg = MODEL_CONFIGS["oxford_flower_200_p4"]
        model = DiffusionViT(dtype=jnp.bfloat16, use_flash=True, drop_rate=0.0,
                             attn_drop_rate=0.0, drop_path_rate=0.0, **cfg)
        img = jax.ShapeDtypeStruct((2, *cfg["img_size"], 3), jnp.float32)
        t = jax.ShapeDtypeStruct((2,), jnp.int32)
        state = jax.eval_shape(lambda: create_train_state(
            model, jax.random.PRNGKey(0), 1e-3, 100,
            (jnp.zeros(img.shape), jnp.zeros(img.shape),
             jnp.zeros((2,), jnp.int32))))
        traced = make_train_step(model).trace(
            state, (img, img, t), jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32))
        launched = set(re.findall(r"name=(\\w+)", str(traced.jaxpr)))
        assert {"fwd", "dqkv"} <= launched
        assert not {"ln_qkv", "block_tail"} & launched
        assert _kernels.by_key("kernels.block_tokenwise") == {
            "xla": 2 * cfg["depth"]}
        assert "ddim_cold_tpu.ops.block_kernels" not in sys.modules
        print("untouched")
    """)
    done = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("untouched")
