"""ops/flash_attention.py's latent forward (``fwd_latent``: a score in two
parts, the second over a key part all the heads share, the value head at its
own width) in interpreter mode, against dense float32 attention written out
head by head on the assembled ``[k_nope, k_r]`` and against
``latent_attention_xla`` (its stand-in off the TPU): the three head-size
triples the launch addresses, a ragged length and one of several chunks, the
blocks and the VMEM row, what it refuses, the counters, that the masked
forward it shares its body with is untouched, and the last q block's folds
on the rows it holds, bit for bit the folds on the whole block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.ops import flash_attention as fa
from tests.test_flash_masked import (EXTRAS, tail_key,
                                     with_and_without_short_folds)


def _operands(N, H, nope, rot, vd, B=1, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    draw = lambda key, *shape: jax.random.normal(key, shape, dtype)
    return (draw(ks[0], B, N, H, nope), draw(ks[1], B, N, H, rot),
            draw(ks[2], B, N, H, nope), draw(ks[3], B, N, rot),
            draw(ks[4], B, N, H, vd))


def _dense(q_nope, q_r, k_nope, k_r, v, scale, causal=True):
    """The published form: every head's key assembled as ``[k_nope_h, k_r]``,
    every score, an explicit boolean mask, one softmax."""
    B, N, H, _ = q_nope.shape
    q = jnp.concatenate([q_nope, q_r], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None], (B, N, H, k_r.shape[-1]))],
        -1)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * scale
    if causal:
        logits = jnp.where(np.tril(np.ones((N, N), bool)), logits, -jnp.inf)
    return jnp.einsum("bhnm,bmhd->bnhd", jax.nn.softmax(logits, -1), v)


@pytest.mark.parametrize("N,H", [(600, 2), (1300, 4)])
@pytest.mark.parametrize("nope,rot,vd", [
    (128, 64, 128),    # two heads' rotated parts on one lane group
    (256, 64, 256),    # two lane groups a part
    (128, 128, 256),   # a whole group of rotated dims; v wider than k_nope
])
def test_latent_forward_matches_dense_and_its_xla_stand_in(nope, rot, vd, N, H):
    """600 tokens: a ragged second chunk; 1,300: three chunks, two q blocks
    of which the first skips the last chunk."""
    ops = _operands(N, H, nope, rot, vd)
    scale = (nope + rot) ** -0.5
    want = _dense(*ops, scale)
    got = fa.flash_attention_latent(*ops, scale)
    assert got.shape == (1, N, H, vd) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=3e-6)
    np.testing.assert_allclose(fa.latent_attention_xla(*ops, scale), want,
                               rtol=2e-5, atol=3e-6)


def test_latent_forward_in_bfloat16_two_images_and_without_a_mask():
    ops = _operands(600, 2, 128, 64, 128, B=2, dtype=jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in ops]
    got = fa.flash_attention_latent(*ops, 192 ** -0.5)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               _dense(*f32, 192 ** -0.5), rtol=3e-2, atol=3e-2)
    full = fa.flash_attention_latent(*f32, 192 ** -0.5, causal=False)
    np.testing.assert_allclose(full, _dense(*f32, 192 ** -0.5, causal=False),
                               rtol=2e-5, atol=3e-6)


def test_each_head_reads_its_own_half_of_the_rotated_lane_group():
    """Heads 2g and 2g + 1 share 128 lanes of q_r: a change to one head's
    rotated part moves that head's context and no other's."""
    ops = list(_operands(40, 4, 128, 64, 128))
    base = fa.flash_attention_latent(*ops, 0.1)
    ops[1] = ops[1].at[:, :, 1].add(1.0)  # head 1: the upper half of group 0
    moved = fa.flash_attention_latent(*ops, 0.1)
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 1, 3))
    assert changed[1] > 1e-3 and changed[[0, 2, 3]].max() == 0.0


def test_blocks_come_from_the_shape_and_the_vmem_row():
    """The cell's shape takes q blocks of 1,024 rows over chunks of 512; a
    short sequence is one block; heads too wide for 1,024 rows fall to 512."""
    assert fa._latent_blocks(9217, 128, 128, jnp.bfloat16) == (1024, 512)
    assert fa._latent_blocks(37, 128, 128, jnp.float32) == (40, 40)
    assert fa._latent_vmem_bytes(1024, 512, 128, 128, 2) < fa._SCOPED_VMEM_BYTES
    assert fa._latent_blocks(9217, 512, 512, jnp.float32) == (512, 512)
    # the walk is the masked forward's: q block i of 1,024 rows sees chunks
    # 0..2i + 1 of 512
    n_q, n_kv, _ = fa._chunk_walk(9217, dict(
        bq=1024, bkv=512, n_valid=9217, causal=True, window=None))
    assert (n_q, n_kv) == (10, 19)


@pytest.mark.parametrize("extra", EXTRAS)
@pytest.mark.parametrize("rot", [64, 128])
def test_the_last_blocks_short_folds_are_bit_for_bit_the_whole_blocks(
        rot, extra, monkeypatch):
    """``fwd_latent`` at one q block of 1,024 rows and a few tokens more, two
    heads on one lane group of q_r (``rot`` 64: the head's half of the block
    is read at the short slice too) and a group each: as
    ``tests/test_flash_masked.py``'s test of the same name says — the short
    folds' result bit for bit the whole block's, ``kernels.flash_fwd_tail``
    ``<rows>/1024`` up to half a block and ``whole`` past it and on a block
    boundary — and the float32 dense reference within bfloat16's rounding."""
    bq, _ = fa._latent_blocks(2048, 128, 128, jnp.bfloat16)
    N = bq + EXTRAS[extra](bq)
    ops = _operands(N, 2, 128, rot, 128, seed=rot, dtype=jnp.bfloat16)
    scale = (128 + rot) ** -0.5
    got, whole = with_and_without_short_folds(
        lambda: fa.flash_attention_latent(*ops, scale), monkeypatch,
        tail_key(N - bq, bq))
    assert bq == 1024 and got.shape == (1, N, 2, 128)
    np.testing.assert_array_equal(got, whole)
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        _dense(*(x.astype(jnp.float32) for x in ops), scale),
        rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("nope,rot,vd,H,match", [
    (192, 64, 256, 2, "nope 192, rot 64, vd 256"),  # one and a half groups
    (128, 32, 128, 4, "rot 32"),
    (128, 64, 64, 2, "vd 64"),
    (128, 64, 128, 3, "an even number of heads"),
])
def test_head_sizes_the_launch_cannot_address_are_refused_by_name(
        nope, rot, vd, H, match):
    ops = _operands(16, H, nope, rot, vd)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention_latent(*ops, 0.1)
    # the plain-JAX path takes any sizes
    assert fa.latent_attention_xla(*ops, 0.1).shape == (1, 16, H, vd)


def test_operands_of_the_wrong_shape_are_refused():
    q_nope, q_r, k_nope, k_r, v = _operands(16, 2, 128, 64, 128)
    with pytest.raises(ValueError, match="ONE k_r"):
        fa.flash_attention_latent(q_nope, q_r, k_nope, k_r[:, :, None], v, 0.1)
    with pytest.raises(ValueError, match="ONE k_r"):
        fa.flash_attention_latent(q_nope, q_r, k_nope[:, :, :1], k_r, v, 0.1)


def test_counters_say_which_path_and_which_mask_a_trace_had():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    ops = _operands(24, 2, 128, 64, 128)
    fa.latent_attention(*ops, 0.1)  # off the TPU: plain JAX, counted as such
    fa.flash_attention_latent(*ops, 0.1)
    fa.flash_attention_latent(*ops, 0.1, causal=False)
    assert fa._kernels.by_key("kernels.flash_latent_schedule") == {"xla": 1}
    assert fa._kernels.by_key("kernels.flash_fwd_mask") == {
        "causal": 1, "none": 1}
    metrics.reset()


def test_the_launch_is_named_and_nothing_is_assembled_beside_it():
    """One ``pallas_call`` named ``fwd_latent``; beside it the program only
    re-views its operands token-major and lays k_r twice over on 128 lanes:
    no head-wise key, no padded or sliced q, k or v."""
    ops = _operands(40, 2, 128, 64, 128, dtype=jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: fa.flash_attention_latent(*a, 0.1))(*ops)
    kinds = [eqn.primitive.name for eqn in jaxpr.eqns]
    assert sorted(set(kinds)) == ["concatenate", "pallas_call", "reshape"]
    assert kinds.count("pallas_call") == kinds.count("concatenate") == 1
    call = jaxpr.eqns[kinds.index("pallas_call")]
    assert call.params["name"] == "fwd_latent"
    joined = jaxpr.eqns[kinds.index("concatenate")]
    assert [v.aval.shape for v in joined.outvars] == [(1, 40, 128)]
    assert [v.aval.shape for v in call.invars] == [
        (1, 40, 256), (1, 40, 128), (1, 40, 256), (1, 40, 128), (1, 40, 256)]


def test_latent_attention_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    ops = _operands(24, 2, 128, 64, 128)
    loss = lambda fn: lambda *a: jnp.sum(fn(*a, 0.1) ** 2)
    got = jax.grad(loss(fa.latent_attention), argnums=(0, 1, 3, 4))(*ops)
    want = jax.grad(loss(_dense), argnums=(0, 1, 3, 4))(*ops)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    with pytest.raises(NotImplementedError, match="fwd_latent kernel has no "
                                                  "backward"):
        jax.grad(lambda q: jnp.sum(fa._latent_no_vjp(
            q, *ops[1:], 0.1, True) ** 2))(ops[0])
