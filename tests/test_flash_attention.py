"""Flash-attention kernel parity: the Pallas fused path must match the dense
einsum path (the reference semantics, ViT.py:110-114) on both odd and aligned
sequence lengths, under grad, and when slotted into the full model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.models import DiffusionViT
from ddim_cold_tpu.ops.flash_attention import _dense_attention_f32, flash_attention


def _rand_qkv(rng, B, N, H, D):
    ks = jax.random.split(jax.random.PRNGKey(rng), 3)
    return tuple(jax.random.normal(k, (B, N, H, D), jnp.float32) for k in ks)


def _forward(q, k, v, scale, bq, bkv, *, with_lse, packed=False):
    """``flash_attention._flash_forward`` on ``(B, N, H, D)`` q, k, v, handed
    over as three token-major arrays or (``packed``) as the one projection the
    model's qkv GEMM writes: → (context ``(B, N, H, D)``, lse)."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B, N, H, D = q.shape
    if packed:
        operands = (jnp.stack([q, k, v], axis=2).reshape(B, N, 3 * H * D),)
    else:
        operands = tuple(x.reshape(B, N, H * D) for x in (q, k, v))
    out, lse = fa._flash_forward(operands, H, scale, bq, bkv,
                                 with_lse=with_lse)
    return out.reshape(B, N, H, D), lse


@pytest.mark.parametrize("N", [8, 257, 320])
def test_flash_matches_dense(N):
    """257 = the OxfordFlower-64 sequence (odd, needs padding); 320 aligned."""
    q, k, v = _rand_qkv(0, 2, N, 4, 16)
    scale = 16**-0.5
    ours = np.asarray(flash_attention(q, k, v, scale))
    _, want = _dense_attention_f32(q, k, v, scale)
    np.testing.assert_allclose(ours, np.asarray(want), rtol=2e-5, atol=2e-6)


def test_flash_block_q_smaller_than_seq():
    """Multiple query blocks per head (block_q < N) tile correctly."""
    q, k, v = _rand_qkv(1, 1, 100, 2, 8)
    ours = np.asarray(flash_attention(q, k, v, 8**-0.5, 32))
    _, want = _dense_attention_f32(q, k, v, 8**-0.5)
    np.testing.assert_allclose(ours, np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("N,bq,bkv", [(300, 64, 128), (257, 32, 64)])
def test_flash_blocked_kv_matches_dense(N, bq, bkv):
    """K/V streamed in chunks (n_kv > 1): the online-softmax accumulation
    across kv blocks must match the dense softmax, including the masked
    padded tail of the last chunk."""
    q, k, v = _rand_qkv(4, 2, N, 2, 16)
    scale = 16**-0.5
    ours = np.asarray(flash_attention(q, k, v, scale, bq, bkv))
    _, want = _dense_attention_f32(q, k, v, scale)
    np.testing.assert_allclose(ours, np.asarray(want), rtol=2e-5, atol=2e-6)


def test_flash_long_sequence_bounded_vmem():
    """N well past the in-repo maximum (2501): the kernel's VMEM need is set
    by (block_q, block_kv), not N — this shape would not fit a single-pass
    K/V-resident kernel's VMEM on real hardware."""
    q, k, v = _rand_qkv(5, 1, 4096, 1, 8)
    scale = 8**-0.5
    ours = np.asarray(flash_attention(q, k, v, scale, 512, 512))
    _, want = _dense_attention_f32(q, k, v, scale)
    np.testing.assert_allclose(ours, np.asarray(want), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("N,H,D,bq,streamed", [
    (65, 3, 32, None, (32, 32)),      # vit_tiny's tokens; scale not 2^k
    (300, 2, 16, 64, (64, 128)),      # several q blocks, scale 2^-2 folded
    (2501, 4, 64, None, (256, 512)),  # the 200px trunk: 512 x 2560, 2^-3
])
def test_resident_forward_matches_scaled_scores_streamed_and_dense(
        N, H, D, bq, streamed, dtype, monkeypatch):
    """The K/V-resident forward (blocks left to the kernel, or one chunk asked
    for) against (a) the same launch with the f32 scores scaled, as the kernel
    did before a power-of-two scale was folded into q: BITWISE, context and
    lse; (b) the streamed kernel and (c) the dense f32 path, to this file's
    tolerances. With and without the lse result."""
    from ddim_cold_tpu.ops import flash_attention as fa

    dt = jnp.dtype(dtype)
    q, k, v = (x.astype(dt) for x in _rand_qkv(31, 1, N, H, D))
    scale = D ** -0.5
    tol = dict(rtol=2e-5, atol=2e-6) if dtype == "float32" else dict(
        rtol=2e-2, atol=2e-2)

    with_lse, lse = _forward(q, k, v, scale, bq, None, with_lse=True)
    without, none = _forward(q, k, v, scale, bq, None, with_lse=False)
    assert none is None
    np.testing.assert_array_equal(np.asarray(with_lse, np.float32),
                                  np.asarray(without, np.float32))
    with monkeypatch.context() as patch:
        patch.setattr(fa, "_scale_folds_into_q", lambda scale: False)
        old, old_lse = _forward(q, k, v, scale, bq, None, with_lse=True)
    np.testing.assert_array_equal(np.asarray(without, np.float32),
                                  np.asarray(old, np.float32))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(old_lse))

    chunked, chunked_lse = _forward(q, k, v, scale, *streamed,
                                    with_lse=True)
    _, dense = _dense_attention_f32(q, k, v, scale)
    for want in (chunked, dense):
        np.testing.assert_allclose(np.asarray(without, np.float32),
                                   np.asarray(want, np.float32), **tol)
    # the residual keeps the q padding, which differs with block_q
    np.testing.assert_allclose(np.asarray(lse[:, :N]),
                               np.asarray(chunked_lse[:, :N]),
                               rtol=2e-5, atol=2e-5)


def test_forward_schedule_is_chosen_from_the_shape(monkeypatch):
    """Blocks left ``None``: K/V resident at the 200px trunk (2,501 tokens,
    head size 64), streamed at 32,768 tokens — asked of the choice alone,
    nothing runs — and ``kernels.flash_fwd_schedule`` says which. Explicit
    blocks are honoured."""
    from ddim_cold_tpu.ops import flash_attention as fa

    def traced(n, *blocks, dtype=jnp.bfloat16, grad=False):
        """Trace (no kernel runs) → (schedule counted, {kernel: grid})."""
        before = fa._kernels.by_key("kernels.flash_fwd_schedule")
        grids = {}
        real = fa.pl.pallas_call

        def spy(kernel, **kw):
            grids[kw.get("name")] = tuple(kw["grid"])
            return real(kernel, **kw)

        x = jax.ShapeDtypeStruct((1, n, 1, 64), dtype)
        fn = lambda q, k, v: fa.flash_attention(  # noqa: E731
            q, k, v, 0.125, *blocks).astype(jnp.float32).sum()
        with monkeypatch.context() as patch:
            patch.setattr(fa.pl, "pallas_call", spy)
            jax.eval_shape(jax.grad(fn, argnums=(0, 1, 2)) if grad else fn,
                           x, x, x)
        after = fa._kernels.by_key("kernels.flash_fwd_schedule")
        return ({key: after[key] - before.get(key, 0) for key in after
                 if after[key] != before.get(key, 0)}, grids)

    # the choice itself, from padded tokens / padded head size / dtype
    assert fa._fwd_blocks(None, None, 2504, 128, jnp.bfloat16) == (512, 2560)
    assert fa._fwd_blocks(None, None, 2504, 128, jnp.float32) == (512, 2560)
    assert fa._fwd_blocks(None, None, 32768, 128, jnp.bfloat16) == (256, 512)
    assert fa._fwd_blocks(256, 512, 2504, 128, jnp.bfloat16) == (256, 512)
    assert fa._fwd_blocks(128, None, 2504, 128, jnp.bfloat16) == (128, 2560)
    assert fa._fwd_blocks(None, 1024, 2504, 128, jnp.bfloat16) == (256, 1024)
    # ... is one the VMEM model admits, and shrinks block_q before it streams
    for n_pad, dt in ((2504, jnp.bfloat16), (6512, jnp.bfloat16),
                      (10000, jnp.bfloat16), (5504, jnp.float32)):
        bq, bkv = fa._fwd_blocks(None, None, n_pad, 128, dt)
        assert bkv >= n_pad and fa._fwd_vmem_bytes(
            bq, bkv, 128, jnp.dtype(dt).itemsize) <= fa._SCOPED_VMEM_BYTES
    assert fa._fwd_blocks(None, None, 6512, 128, jnp.bfloat16)[0] == 256
    assert fa._fwd_blocks(None, None, 10000, 128, jnp.bfloat16)[0] == 128

    assert traced(2501) == ({"resident": 1}, {"fwd": (1, 1, 5, 1)})
    assert traced(2501, dtype=jnp.float32) == ({"resident": 1},
                                               {"fwd": (1, 1, 5, 1)})
    assert traced(32768) == ({"streamed": 1}, {"fwd": (1, 1, 128, 64)})
    assert traced(2501, 256, 512) == ({"streamed": 1}, {"fwd": (1, 1, 10, 5)})
    assert traced(2501, *fa.NS_FLASH_BLOCKS) == ({"resident": 1},
                                                 {"fwd": (1, 1, 5, 1)})
    assert traced(4096, 512, 512) == ({"streamed": 1}, {"fwd": (1, 1, 8, 8)})
    # under grad: the VJP's forward is resident; the backward picks its own
    # blocks (test_backward_layout_and_schedule_are_chosen_from_the_shape)
    schedule, grids = traced(2501, grad=True)
    assert schedule == {"resident": 1} and grids["fwd"] == (1, 1, 5, 1)
    assert set(grids) == {"fwd", "dqkv"}


@pytest.mark.parametrize("H,D,layout", [
    (4, 64, "in_place"),     # the 200px trunk: two heads a lane group
    (12, 32, "in_place"),    # vit_tiny: four heads a lane group
    (2, 128, "in_place"),    # one head a lane group
    (1, 64, "head_major"),   # one local head under Ulysses: H·D = 64
    (3, 32, "head_major"),   # heads do not fill the last column block
    (4, 80, "head_major"),   # 128 % 80
    (2, 256, "head_major"),  # a head wider than the lanes
])
def test_forward_layout_is_chosen_from_the_shape(H, D, layout, monkeypatch):
    """``128 % D == 0`` and ``H·D % 128 == 0``: q, k, v and the context are
    addressed where they lie, grid (images, lane groups, q blocks, chunks);
    anything else is laid out head-major, grid (images·heads, 1, q blocks,
    chunks); 300 tokens are one q block and one resident chunk. Asked
    of the trace alone — nothing runs — for both entries, primal and VJP, and
    ``kernels.flash_fwd_layout`` says which."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B, N = 2, 300
    grids, real = [], fa.pl.pallas_call

    def spy(kernel, **kw):
        if kw.get("name") == "fwd":
            grids.append(tuple(kw["grid"]))
        return real(kernel, **kw)

    x = jax.ShapeDtypeStruct((B, N, H, D), jnp.bfloat16)
    packed = jax.ShapeDtypeStruct((B, N, 3 * H * D), jnp.bfloat16)
    apart = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, 0.125).astype(jnp.float32).sum()
    one = lambda qkv: fa.flash_attention_qkv(  # noqa: E731
        qkv, H, 0.125).astype(jnp.float32).sum()
    before = fa._kernels.by_key("kernels.flash_fwd_layout")
    with monkeypatch.context() as patch:
        patch.setattr(fa.pl, "pallas_call", spy)
        jax.eval_shape(apart, x, x, x)
        jax.eval_shape(one, packed)
        jax.eval_shape(jax.grad(apart, argnums=(0, 1, 2)), x, x, x)
        assert jax.eval_shape(jax.grad(one), packed).shape == packed.shape
    after = fa._kernels.by_key("kernels.flash_fwd_layout")
    assert {key: after[key] - before.get(key, 0) for key in after
            if after[key] != before.get(key, 0)} == {layout: 4}
    want = (B, H * D // 128, 1, 1) if layout == "in_place" else (
        B * H, 1, 1, 1)
    assert grids == [want] * 4


@pytest.mark.parametrize("dtype,tol", [
    ("float32", dict(rtol=1e-4, atol=1e-5)),
    ("bfloat16", dict(rtol=2e-2, atol=2e-2)),
])
@pytest.mark.parametrize("N,blocks", [
    (33, (None, None)), (300, (None, None)), (33, (64, 64)), (300, (64, 128))],
    ids=["fused-33", "fused-300", "one_chunk-33", "streamed-300"])
def test_packed_entry_gradient_matches_dense(N, blocks, dtype, tol):
    """``flash_attention_qkv`` under ``jax.grad`` — the in-place forward with
    its lse, the backward (``dqkv`` where the blocks are left to it, ``dq`` +
    ``dkv`` at explicit blocks) writing the three gradients into the
    projection's column order — against autodiff through the dense einsum on
    the same slices, at the tolerances of the (q, k, v) entry's gradient
    tests; and the (q, k, v) entry's own gradients BITWISE the packed entry's
    columns."""
    from ddim_cold_tpu.ops.flash_attention import flash_attention_qkv

    B, H, D = 1, 4, 32
    scale = D ** -0.5
    qkv = jnp.stack(_rand_qkv(43, B, N, H, D), axis=2).reshape(
        B, N, 3 * H * D).astype(dtype)
    unpack = lambda x: [x.reshape(B, N, 3, H, D)[:, :, i]  # noqa: E731
                        for i in range(3)]

    def loss_packed(qkv):
        out = flash_attention_qkv(qkv, H, scale, *blocks)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_apart(qkv):
        out = flash_attention(*unpack(qkv), scale, *blocks)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(qkv):
        out = _dense_attention_f32(*unpack(qkv), scale)[1]
        return jnp.sum(out.astype(jnp.float32) ** 2)

    ours = jax.grad(loss_packed)(qkv)
    assert ours.shape == qkv.shape and ours.dtype == qkv.dtype
    np.testing.assert_array_equal(
        np.asarray(ours, np.float32),
        np.asarray(jax.grad(loss_apart)(qkv), np.float32))
    want = jax.grad(loss_dense)(qkv)
    for name, got, ref in zip("qkv", unpack(ours), unpack(want)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   err_msg=f"d{name}", **tol)


def _backward(q, k, v, g, scale, bq, bkv, *, packed, lse_tail=None):
    """``flash_attention._flash_backward`` on ``(B, N, H, D)`` q, k, v and
    cotangent ``g``, residuals from the VJP's own forward at the same blocks:
    → dq, dk, dv stacked ``(3, B, N, H·D)``. ``lse_tail`` overwrites the
    residual's entries past token N (the forward's stale q rows)."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B, N, H, D = q.shape
    if packed:
        operands = (jnp.stack([q, k, v], axis=2).reshape(B, N, 3 * H * D),)
    else:
        operands = tuple(x.reshape(B, N, H * D) for x in (q, k, v))
    out, lse = fa._flash_forward(operands, H, scale, bq, bkv, with_lse=True)
    if lse_tail is not None:
        lse = lse.at[:, N:].set(lse_tail)
    grads = fa._flash_backward(operands, out, lse, g.reshape(B, N, H * D), H,
                               scale, bq, bkv)
    if packed:
        assert grads[0].shape == operands[0].shape
        assert grads[0].dtype == operands[0].dtype
        return grads[0].reshape(B, N, 3, H * D).transpose(2, 0, 1, 3)
    return jnp.stack(grads)


def _two_launches(monkeypatch):
    """The shape is one the ``"dqkv"`` row of the VMEM model does not admit:
    blocks left to the kernels give today's ``dq`` + ``dkv`` (what explicit
    blocks force too, where a test can say what it needs with them)."""
    from ddim_cold_tpu.ops import flash_attention as fa

    real = fa._bwd_vmem_bytes
    monkeypatch.setattr(fa, "_bwd_vmem_bytes", lambda kernel, *a: (
        1 << 40 if kernel == "dqkv" else real(kernel, *a)))


@pytest.mark.parametrize("dtype,tol", [
    ("float32", dict(rtol=1e-4, atol=1e-5)),
    ("bfloat16", dict(rtol=2e-2, atol=2e-2)),
])
@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
@pytest.mark.parametrize("blocks", [(None, None), (512, 512), (128, 128)],
                         ids=["fused", "resident", "streamed"])
def test_backward_ragged_edge_is_finite_and_matches_dense(blocks, packed,
                                                          dtype, tol):
    """300 tokens, two heads of 64 on the lanes: every block of q, k, v, the
    cotangent and the gradients ends past the arrays (the interpreter fills
    what a program reads there with NaN), and the lse residual's tail — what
    the forward computed for its stale q rows — is poisoned with NaN on top.
    Nothing of it may reach a gradient: finite, and autodiff through the
    dense einsum to the tolerances of the gradient tests."""
    B, N, H, D = 1, 300, 2, 64
    scale = D ** -0.5
    q, k, v, g = (x.astype(dtype) for x in (
        *_rand_qkv(51, B, N, H, D), _rand_qkv(52, B, N, H, D)[0]))
    ours = _backward(q, k, v, g, scale, *blocks, packed=packed,
                     lse_tail=jnp.nan)
    assert np.isfinite(np.asarray(ours, np.float32)).all()
    _, vjp = jax.vjp(
        lambda q, k, v: _dense_attention_f32(q, k, v, scale)[1], q, k, v)
    want = vjp(g.astype(jnp.float32))
    for name, got, ref in zip(("dq", "dk", "dv"), ours, want):
        np.testing.assert_allclose(
            np.asarray(got, np.float32).reshape(B, N, H, D),
            np.asarray(ref, np.float32), err_msg=name, **tol)


@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
@pytest.mark.parametrize("dtype,budget,tol", [
    ("float32", 2_800_000, dict(rtol=1e-4, atol=1e-5)),
    ("bfloat16", 2_400_000, dict(rtol=2e-2, atol=2e-2)),
    ("float32", None, dict(rtol=1e-4, atol=1e-5)),
    ("bfloat16", None, dict(rtol=2e-2, atol=2e-2)),
], ids=["float32-cut_budget", "bfloat16-cut_budget", "float32-fused",
        "bfloat16-fused"])
def test_backward_unequal_q_blocks_on_a_ragged_length_match_dense(
        dtype, budget, tol, packed, monkeypatch):
    """``dq`` and ``dkv`` choose their q blocks apart, so the tokens their
    last q blocks cover can differ: with the VMEM budget cut so that 600
    tokens stream, ``dq`` runs at block_q 128 (it writes delta for 640
    tokens) and ``dkv`` at 256 (it reads 768). What ``dkv`` reads past the
    sequence (delta nobody wrote, the lse residual's poisoned tail) may not
    reach dk or dv: finite, and dense's to the gradient tests' tolerances.
    At the chip's own budget (``fused``) the same 600 tokens are one ``dqkv``
    launch — q, do and the statistics 640 rows, two K/V blocks of 512, the
    second ragged — held to the same."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B, N, H, D = 1, 600, 2, 64
    scale = D ** -0.5
    if budget is None:
        assert fa._bwd_blocks(None, None, N, 128, dtype, 2) == {
            "dqkv": (640, 512)}
    else:
        monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", budget)
        blocks = fa._bwd_blocks(None, None, N, 128, dtype, 2)
        (dq_q, _), (dkv_q, _) = blocks["dq"], blocks["dkv"]
        assert (dq_q, dkv_q) == (128, 256)  # or the case below shows nothing
        assert fa.tiling.round_up(N, dq_q) < fa.tiling.round_up(N, dkv_q)
    q, k, v, g = (x.astype(dtype) for x in (
        *_rand_qkv(53, B, N, H, D), _rand_qkv(54, B, N, H, D)[0]))
    ours = _backward(q, k, v, g, scale, None, None, packed=packed,
                     lse_tail=jnp.nan)
    assert np.isfinite(np.asarray(ours, np.float32)).all()
    _, vjp = jax.vjp(
        lambda q, k, v: _dense_attention_f32(q, k, v, scale)[1], q, k, v)
    want = vjp(g.astype(jnp.float32))
    for name, got, ref in zip(("dq", "dk", "dv"), ours, want):
        np.testing.assert_allclose(
            np.asarray(got, np.float32).reshape(B, N, H, D),
            np.asarray(ref, np.float32), err_msg=name, **tol)


@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
@pytest.mark.parametrize("H,D", [(4, 64), (8, 32)],
                         ids=["two_heads_a_group", "four_heads_a_group"])
@pytest.mark.parametrize("N", [2501, 600])
def test_fused_backward_is_the_two_launches_to_summation_order(
        N, H, D, packed, monkeypatch):
    """The one ``dqkv`` launch against ``dq`` + ``dkv`` (the same shape with
    the ``"dqkv"`` row refusing it) on the same bf16 operands, residuals and
    cotangent: dk and dv are the same sums in the same order — BITWISE — and
    dq differs by the order of its f32 sum alone (K/V blocks of 512 where
    ``dq`` folds one 2,560-row chunk), so by at most two roundings of the
    bf16 result."""
    from ddim_cold_tpu.ops import flash_attention as fa

    q, k, v, g = (x.astype(jnp.bfloat16) for x in (
        *_rand_qkv(61, 1, N, H, D), _rand_qkv(62, 1, N, H, D)[0]))
    scale = D ** -0.5
    n_pad, whole = fa.tiling.round_up(N, 8), fa.tiling.round_up(N, 128)
    assert fa._bwd_blocks(None, None, n_pad, 128, jnp.bfloat16,
                          128 // D) == {"dqkv": (whole, 512)}
    fused = np.asarray(_backward(q, k, v, g, scale, None, None,
                                 packed=packed), np.float32)
    with monkeypatch.context() as patch:
        _two_launches(patch)
        blocks = fa._bwd_blocks(None, None, n_pad, 128, jnp.bfloat16, 128 // D)
        assert blocks == {"dq": (512, whole), "dkv": (whole, 512)}
        two = np.asarray(_backward(q, k, v, g, scale, None, None,
                                   packed=packed), np.float32)
    np.testing.assert_array_equal(fused[1], two[1], err_msg="dk")
    np.testing.assert_array_equal(fused[2], two[2], err_msg="dv")
    np.testing.assert_allclose(fused[0], two[0], rtol=2 ** -7, atol=1e-6,
                               err_msg="dq")
    assert (fused[0] != two[0]).mean() < 0.2


@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
def test_a_shape_the_fused_row_refuses_runs_the_launches_explicit_blocks_ask_for(
        packed, monkeypatch):
    """Where the ``"dqkv"`` row does not admit the sequence, blocks left to
    the kernels are ``dq`` + ``dkv`` at the blocks each picks, counted
    ``resident`` — the program explicit blocks of the same sizes ask for:
    dq, dk and dv BITWISE."""
    from ddim_cold_tpu.ops import flash_attention as fa

    N, H, D = 384, 4, 64
    q, k, v, g = (x.astype(jnp.bfloat16) for x in (
        *_rand_qkv(63, 1, N, H, D), _rand_qkv(64, 1, N, H, D)[0]))
    given = np.asarray(_backward(q, k, v, g, D ** -0.5, N, N, packed=packed),
                       np.float32)
    _two_launches(monkeypatch)
    assert fa._bwd_blocks(None, None, N, 128, jnp.bfloat16, 2) == {
        "dq": (N, N), "dkv": (N, N)}
    before = fa._kernels.by_key("kernels.flash_bwd_schedule")
    left = np.asarray(_backward(q, k, v, g, D ** -0.5, None, None,
                                packed=packed), np.float32)
    now = fa._kernels.by_key("kernels.flash_bwd_schedule")
    assert {key: n - before.get(key, 0) for key, n in now.items()
            if n != before.get(key, 0)} == {"resident": 1}
    np.testing.assert_array_equal(left, given)


@pytest.mark.parametrize("fused", [False, True], ids=["dq_dkv", "dqkv"])
def test_gradient_head_of_256_at_1841_tokens_is_finite_and_matches_dense(
        fused, monkeypatch):
    """The public entry at the chip's own budget where the two kernels' q
    blocks differ on a ragged length: float32, one head of 256, 1,841 tokens
    — ``dq`` holds K and V whole at block_q 128 (1,920 tokens of delta),
    ``dkv`` streams q at 256 (2,048 read): the ``"dqkv"`` row of the VMEM
    model does not admit the shape. With four times the budget it does
    (``dqkv``): the one launch on 256 lanes, q, do and the statistics 1,920
    rows, four K/V blocks of 512, the last ragged."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B, N, H, D = 1, 1841, 1, 256
    scale = D ** -0.5
    if fused:
        monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES",
                            4 * fa._SCOPED_VMEM_BYTES)
    assert fa._bwd_blocks(None, None, fa.tiling.round_up(N, 8), D,
                          jnp.float32) == (
        {"dqkv": (1920, 512)} if fused
        else {"dq": (128, 1920), "dkv": (256, 512)})
    q, k, v = _rand_qkv(55, B, N, H, D)
    g = _rand_qkv(56, B, N, H, D)[0]
    ours = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, scale) * g), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(
        _dense_attention_f32(q, k, v, scale)[1] * g), argnums=(0, 1, 2))(
            q, k, v)
    for name, got, ref in zip(("dq", "dk", "dv"), ours, want):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   err_msg=name, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("N,H,D,layout,schedule,grids", [
    # the 200px trunk: two heads a lane group; ONE launch, q, do and the
    # statistics resident, five K/V blocks of 512 and then dq's five row
    # blocks (packed: a step more a K/V block for dv's column block)
    (2501, 4, 64, "in_place", "fused",
     {"dqkv": (2, 2, 10), "dqkv_packed": (2, 2, 15)}),
    (2501, 12, 32, "in_place", "fused",
     {"dqkv": (2, 3, 10), "dqkv_packed": (2, 3, 15)}),
    # one local head of 64 under Ulysses, head sizes 80 and 256: head-major,
    # one head a group, the same launch (three results either way)
    (2501, 1, 64, "head_major", "fused",
     {"dqkv": (2, 1, 10), "dqkv_packed": (2, 1, 10)}),
    (2501, 4, 80, "head_major", "fused",
     {"dqkv": (8, 1, 10), "dqkv_packed": (8, 1, 10)}),
    (2501, 2, 256, "head_major", "fused",
     {"dqkv": (4, 1, 20), "dqkv_packed": (4, 1, 20)}),
    # too long for the one launch: dq still holds K and V whole at block_q
    # 128; dkv streams already
    (8192, 4, 64, "in_place", "resident",
     {"dq": (2, 2, 64, 1), "dq_packed": (2, 2, 64, 1),
      "dkv": (2, 2, 16, 32), "dkv_packed": (2, 2, 16, 33)}),
    # too long to be resident: both stream at (256, 512)
    (32768, 4, 64, "in_place", "streamed",
     {"dq": (2, 2, 128, 64), "dq_packed": (2, 2, 128, 64),
      "dkv": (2, 2, 64, 128), "dkv_packed": (2, 2, 64, 129)}),
])
def test_backward_layout_and_schedule_are_chosen_from_the_shape(
        N, H, D, layout, schedule, grids, monkeypatch):
    """The backward's addressing follows the forward's rule (``128 % D == 0``
    and ``H·D % 128 == 0``: in place, grid (images, lane groups, outer
    blocks, inner chunks); anything else head-major, grid (images·heads, 1,
    ...)) and its blocks the shape: ONE launch (``dqkv``, ``fused``, grid
    (rows, lane groups, steps)) where the sequence is resident for it, else
    ``dq`` and ``dkv``, each kernel's own blocks (``resident`` or
    ``streamed`` by dq's K/V chunking). Asked of the trace alone — nothing
    runs — for both entries; ``kernels.flash_bwd_layout`` and
    ``kernels.flash_bwd_schedule`` say which."""
    from ddim_cold_tpu.ops import flash_attention as fa

    B = 2
    seen, real = {}, fa.pl.pallas_call

    def spy(kernel, **kw):
        seen[kw.get("name")] = tuple(kw["grid"])
        return real(kernel, **kw)

    def counted(before):
        return {name: {key: n - before[name].get(key, 0) for key, n in
                       fa._kernels.by_key(name).items()
                       if n != before[name].get(key, 0)}
                for name in before}

    names = ("kernels.flash_bwd_layout", "kernels.flash_bwd_schedule")
    before = {name: fa._kernels.by_key(name) for name in names}
    x = jax.ShapeDtypeStruct((B, N, H, D), jnp.bfloat16)
    packed = jax.ShapeDtypeStruct((B, N, 3 * H * D), jnp.bfloat16)
    scale = D ** -0.5
    with monkeypatch.context() as patch:
        patch.setattr(fa.pl, "pallas_call", spy)
        jax.eval_shape(jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, scale).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
            x, x, x)
        apart = dict(seen)
        assert jax.eval_shape(jax.grad(lambda qkv: fa.flash_attention_qkv(
            qkv, H, scale).astype(jnp.float32).sum()), packed
            ).shape == packed.shape
    assert counted(before) == {names[0]: {layout: 2}, names[1]: {schedule: 2}}
    assert set(seen) - {"fwd"} == {name.removesuffix("_packed")
                                   for name in grids}
    for name in set(seen) - {"fwd"}:
        assert (apart[name], seen[name]) == (grids[name],
                                             grids[name + "_packed"])

    # the choice itself: explicit blocks are honoured by both kernels (a q
    # block is a multiple of 128, the statistics' lane tile); one side given
    # leaves the other to the kernel
    blocks = lambda *a: fa._bwd_blocks(*a, 2504, 128, jnp.bfloat16, 2)  # noqa: E731
    two = lambda dq, dkv: {"dq": dq, "dkv": dkv}  # noqa: E731
    assert blocks(None, None) == {"dqkv": (2560, 512)}
    assert blocks(256, 512) == two((256, 512), (256, 512))
    assert blocks(300, 500) == two((384, 512), (384, 512))
    assert blocks(128, None) == two((128, 2560), (128, 512))
    assert blocks(None, 256) == two((256, 256), (2560, 256))
    assert blocks(None, 1024) == two((256, 1024), (256, 1024))  # no room whole
    # float32 with two heads on the lanes: the "dqkv" row refuses 2,560 rows
    # at every block; one head a lane group of 256 lanes fits at block 256
    assert fa._bwd_blocks(None, None, 2504, 128, jnp.float32, 2) == two(
        (256, 2560), (2560, 256))
    assert fa._bwd_blocks(None, None, 2504, 256, jnp.bfloat16) == {
        "dqkv": (2560, 256)}
    with monkeypatch.context() as patch:  # were the row to refuse the trunk
        _two_launches(patch)
        assert blocks(None, None) == two((512, 2560), (2560, 512))
        for kernel, pair in blocks(None, None).items():
            assert fa._bwd_vmem_bytes(kernel, *pair, 128, 2,
                                      2) <= fa._SCOPED_VMEM_BYTES
    assert fa._bwd_vmem_bytes("dqkv", 2560, 512, 128, 2,
                              2) <= fa._SCOPED_VMEM_BYTES


@pytest.mark.parametrize("config,use_flash,traces", [
    ("oxford_flower_200_p4", True, 6),  # the flower200_train_dp4 cell's
    ("vit_tiny", False, 0),             # the vit_tiny64_train_loader cell's
])
def test_trainer_traces_the_backward_in_place_and_resident(config, use_flash,
                                                           traces):
    """The trainer's own step (``train.step.make_train_step``), traced at the
    benchmark's widths and depth, nothing run: each flash layer's backward is
    counted once, ``in_place`` and ``fused`` (one ``dqkv`` launch a layer),
    and a model with ``use_flash`` off counts nothing."""
    from ddim_cold_tpu.models import MODEL_CONFIGS
    from ddim_cold_tpu.ops import flash_attention as fa
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    cfg = MODEL_CONFIGS[config]
    model = DiffusionViT(dtype=jnp.bfloat16, use_flash=use_flash,
                         drop_rate=0.0, attn_drop_rate=0.0,
                         drop_path_rate=0.0, **cfg)
    img = jax.ShapeDtypeStruct((2, *cfg["img_size"], 3), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.int32)
    state = jax.eval_shape(lambda: create_train_state(
        model, jax.random.PRNGKey(0), 1e-3, 100,
        (jnp.zeros(img.shape), jnp.zeros(img.shape), jnp.zeros((2,), jnp.int32))))
    names = ("kernels.flash_bwd_layout", "kernels.flash_bwd_schedule")
    before = [fa._kernels.by_key(name) for name in names]
    jax.eval_shape(make_train_step(model), state, (img, img, t),
                   jax.ShapeDtypeStruct((2,), jnp.uint32),
                   jax.ShapeDtypeStruct((), jnp.float32))
    for name, was, key in zip(names, before, ("in_place", "fused")):
        now = fa._kernels.by_key(name)
        assert {k: n - was.get(k, 0) for k, n in now.items()
                if n != was.get(k, 0)} == ({key: traces} if traces else {})


def test_flash_bf16_inputs():
    q, k, v = _rand_qkv(2, 1, 64, 2, 8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, 8**-0.5)
    assert out.dtype == jnp.bfloat16
    _, want = _dense_attention_f32(qb, kb, vb, 8**-0.5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


def test_flash_gradient_matches_dense():
    """Custom VJP (recompute backward) ≡ autodiff through the einsum path."""
    q, k, v = _rand_qkv(3, 1, 33, 2, 8)
    scale = 8**-0.5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention_f32(q, k, v, scale)[1] ** 2)

    g_ours = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for ours, want in zip(g_ours, g_want):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("N,bq,bkv", [(300, 64, 128), (130, 32, 64)])
def test_flash_gradient_blocked_matches_dense(N, bq, bkv):
    """The Pallas backward (dq kernel + transposed dk/dv kernel) over
    multiple q AND kv chunks, including the masked padded tails, must match
    autodiff through the dense einsum."""
    q, k, v = _rand_qkv(6, 1, N, 2, 16)
    scale = 16**-0.5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale, bq, bkv) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention_f32(q, k, v, scale)[1] ** 2)

    g_ours = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, ours, want in zip("qkv", g_ours, g_want):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(None, None), (256, 2560)],
                         ids=["fused", "dq_dkv"])
def test_flash_gradient_north_star_shape_matches_dense(blocks):
    """The Pallas BACKWARD at the exact north-star shape — N=2501 tokens
    (200px, patch 4, +1 time token), H=4, D=64, production default blocks
    (the one ``dqkv`` launch) and the two launches explicit blocks ask for —
    against autodiff through the dense einsum (VERDICT r4 item 9: forward
    was exercised at this length, the 200px training stage runs the
    backward, and Mosaic has rejected this kernel family on hardware once;
    interpret mode proves the math, the tile-rule guard below covers the
    lowering constraints)."""
    q, k, v = _rand_qkv(13, 1, 2501, 4, 64)
    scale = 64**-0.5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale, *blocks) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_attention_f32(q, k, v, scale)[1] ** 2)

    g_ours = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, ours, want in zip("qkv", g_ours, g_want):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(want),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("blocks", [(None, None), "NS_FLASH_BLOCKS"],
                         ids=["fused", "dq_dkv"])
def test_flash_bf16_gradient_north_star_shape_matches_dense(blocks):
    """The Pallas BACKWARD on bf16 inputs at the north-star shape (N=2501,
    H=4, D=64; the blocks left to the kernels, which is the one ``dqkv``
    launch the dp4 cell runs, and the tuned NS_FLASH_BLOCKS, which ask for
    ``dq`` + ``dkv``) — against autodiff through the dense
    f32 oracle on the same bf16 inputs. The 200px training stage runs this
    exact backward in bf16, and the bf16-gemm-v2 kernel routes its backward
    GEMMs through the input dtype — a path the f32 gradient tests above
    never touch. Tolerances follow the bf16 forward tests (~2e-2):
    the comparison isolates kernel-vs-einsum error on identical bf16
    operands, not bf16-vs-f32 rounding."""
    from ddim_cold_tpu.ops.flash_attention import NS_FLASH_BLOCKS

    if blocks == "NS_FLASH_BLOCKS":
        blocks = NS_FLASH_BLOCKS
    q32, k32, v32 = _rand_qkv(19, 1, 2501, 4, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    scale = 64**-0.5

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, scale, *blocks)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        out = _dense_attention_f32(q, k, v, scale)[1]
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_ours = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, ours, want in zip("qkv", g_ours, g_want):
        assert ours.dtype == jnp.bfloat16, f"d{name} dtype {ours.dtype}"
        np.testing.assert_allclose(np.asarray(ours, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2, err_msg=f"d{name}")


def test_flash_bf16_north_star_headline_config_matches_dense():
    """The 200px sampler's shape: bf16 inputs, N=2501, H=4,
    D=64, the NS_FLASH_BLOCKS single-chunk config — against the dense
    f32 oracle on the same bf16 inputs. The bf16-gemm-v2 kernel runs its
    GEMMs in bf16 here (input dtype), so this pins the numerics of the
    production sampler configuration, not just the f32 test shapes."""
    from ddim_cold_tpu.ops.flash_attention import NS_FLASH_BLOCKS

    q32, k32, v32 = _rand_qkv(17, 1, 2501, 4, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    scale = 64**-0.5
    out = flash_attention(q, k, v, scale, *NS_FLASH_BLOCKS)
    assert out.dtype == jnp.bfloat16
    want = _dense_attention_f32(q, k, v, scale)[1]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_model_use_flash_parity():
    """DiffusionViT(use_flash=True) ≡ the einsum model in eval mode — same
    params tree (flash adds no parameters), same outputs."""
    cfg = dict(img_size=(16, 16), patch_size=4, embed_dim=32, depth=2, num_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
    t = jnp.array([3, 500], jnp.int32)
    base = DiffusionViT(**cfg)
    params = base.init(jax.random.PRNGKey(1), x, t)["params"]
    flash = DiffusionViT(use_flash=True, **cfg)
    out_base = base.apply({"params": params}, x, t)
    out_flash = flash.apply({"params": params}, x, t)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_base),
                               rtol=2e-4, atol=2e-5)


def test_model_flash_blocks_tuning_matches_default():
    """flash_blocks threads model → Attention → kernel and changes only the
    schedule, never the numbers — including a block_kv far past N (clamped
    inside the kernel to the padded sequence: fully VMEM-resident K/V)."""
    cfg = dict(img_size=(16, 16), patch_size=4, embed_dim=32, depth=2, num_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
    t = jnp.array([3, 500], jnp.int32)
    base = DiffusionViT(use_flash=True, **cfg)
    params = base.init(jax.random.PRNGKey(1), x, t)["params"]
    want = np.asarray(base.apply({"params": params}, x, t))
    for blocks in ((8, 8), (16, 4096)):
        tuned = DiffusionViT(use_flash=True, flash_blocks=blocks, **cfg)
        got = np.asarray(tuned.apply({"params": params}, x, t))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_model_remat_flash_gradients_match():
    """remat (jax.checkpoint per block) composed with the flash custom-VJP:
    the memory-tight 200px training combination. Gradients must equal the
    non-remat flash model's — recompute may not perturb the custom backward."""
    import jax.numpy as jnp

    cfg = dict(img_size=(16, 16), patch_size=4, embed_dim=32, depth=2,
               num_heads=4, drop_rate=0.0, attn_drop_rate=0.0,
               drop_path_rate=0.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
    t = jnp.array([3, 500], jnp.int32)
    base = DiffusionViT(use_flash=True, **cfg)
    params = base.init(jax.random.PRNGKey(1), x, t)["params"]
    rem = DiffusionViT(use_flash=True, remat=True, **cfg)

    def loss(model, p):
        return jnp.sum(model.apply({"params": p}, x, t) ** 2)

    g_base = jax.grad(lambda p: loss(base, p))(params)
    g_rem = jax.grad(lambda p: loss(rem, p))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        g_base, g_rem)


def test_model_attention_probe_still_works_with_flash():
    """return_attention_layer forces the weights-producing path even when
    use_flash is on (the kernel never materializes attention weights)."""
    cfg = dict(img_size=(16, 16), patch_size=4, embed_dim=32, depth=2, num_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 16, 3))
    t = jnp.zeros((1,), jnp.int32)
    model = DiffusionViT(use_flash=True, **cfg)
    params = model.init(jax.random.PRNGKey(1), x, t)["params"]
    attn = model.apply({"params": params}, x, t, return_attention_layer=1)
    assert attn.shape == (1, 4, 17, 17)
    s = np.asarray(jnp.sum(attn, axis=-1))
    np.testing.assert_allclose(s, np.ones_like(s), rtol=1e-5)


def _tile_rule_spy(monkeypatch, fa):
    """Install a pallas_call spy asserting every BlockSpec satisfies the TPU
    tile rule — dtype-aware sublane unit (f32 8, bf16 16, int8 32) × lane
    128, each dim exempt when the block spans the whole array dim — against
    the real call arguments; returns the call-name list for count
    assertions. CPU interpret mode does not enforce the rule, so this spy is
    what stands between a green CI and a Mosaic rejection on chip."""
    from jax.experimental import pallas as pl

    from ddim_cold_tpu.ops import tiling

    def check(block, arr, dtype, ctx):
        assert len(block) == len(arr.shape), (ctx, block, arr.shape)
        if len(block) < 2:
            return
        (bs, bl), (asub, alane) = block[-2:], arr.shape[-2:]
        unit = tiling.sublane_unit(dtype)
        assert bs % unit == 0 or bs == asub, (ctx, block, arr.shape, unit)
        assert bl % 128 == 0 or bl == alane, (ctx, block, arr.shape)

    real = pl.pallas_call
    calls = []

    def spy(kernel, **kw):
        inner = real(kernel, **kw)

        def wrapper(*ops):
            name = getattr(kernel, "func", kernel).__name__
            calls.append(name)
            in_specs = kw["in_specs"]
            for i, (spec, op) in enumerate(zip(in_specs, ops)):
                if spec.block_shape is not None:  # else: left in HBM, no block
                    check(spec.block_shape, op, op.dtype, f"{name} in[{i}]")
            outs = kw["out_shape"]
            outs = outs if isinstance(outs, (list, tuple)) else [outs]
            specs = kw["out_specs"]
            specs = specs if isinstance(specs, (list, tuple)) else [specs]
            for i, (spec, o) in enumerate(zip(specs, outs)):
                check(spec.block_shape, o, o.dtype, f"{name} out[{i}]")
            return inner(*ops)

        return wrapper

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    return calls


def test_block_sweep_configs_satisfy_tpu_tile_rule(monkeypatch):
    """The FLASH_BLOCK_SWEEP geometries at the exact 200px shape (N=2501)
    must pass the same tile rule — an entry Mosaic rejects would only be
    found on the chip."""
    from ddim_cold_tpu.ops import flash_attention as fa

    calls = _tile_rule_spy(monkeypatch, fa)
    q, k, v = _rand_qkv(11, 1, 2501, 1, 64)  # 1 head: forward-only sweep
    for bq, bkv in fa.FLASH_BLOCK_SWEEP:
        out = flash_attention(q, k, v, 64**-0.5, bq, bkv)
        assert np.isfinite(np.asarray(out)).all(), (bq, bkv)
    assert calls.count("_fwd_kernel") == len(fa.FLASH_BLOCK_SWEEP), calls
    assert len(calls) == len(fa.FLASH_BLOCK_SWEEP), calls


def test_block_specs_satisfy_tpu_tile_rule(monkeypatch):
    """Every BlockSpec the kernels build must satisfy Mosaic's TPU tiling
    rule: the last two dims of a block are divisible by (8, 128) or equal
    the array's. CPU interpret mode never enforces this, which let a
    (1, bq) lse row block ship and fail to compile on real hardware at the
    200px config (N=2501, BH=64) — this guard reproduces the check the TPU
    lowering applies, against the real pallas_call arguments."""
    from ddim_cold_tpu.ops import flash_attention as fa

    calls = _tile_rule_spy(monkeypatch, fa)
    # 65 = vit_tiny, 257 = oxford_flower_64, 2501 = the 200px north-star
    # shape that failed on hardware (keep it last: largest)
    for N, H, D in ((65, 12, 32), (257, 4, 64), (2501, 4, 64)):
        q, k, v = _rand_qkv(7, 1, N, H, D)
        scale = D**-0.5
        out = flash_attention(q, k, v, scale)
        assert np.isfinite(np.asarray(out)).all()
        g = jax.grad(lambda q: flash_attention(q, k, v, scale).sum())(q)
        assert np.isfinite(np.asarray(g)).all()
    # per shape: primal fwd + vjp fwd + the backward: dqkv, and at 2,501
    # tokens in float32, which its VMEM row refuses, dq + dkv
    assert calls.count("_fwd_kernel") == 6 and len(calls) == 10, calls
    assert calls.count("_bwd_dqkv_kernel") == 2, calls
    assert calls[-2:] == ["_bwd_dq_kernel", "_bwd_dkv_kernel"]
    # the trunk's own dtype at 2,501 tokens: vjp fwd + dqkv at (2560, 512)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    g = jax.grad(lambda q: flash_attention(q, k, v, scale).astype(
        jnp.float32).sum())(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()
    assert calls[10:] == ["_fwd_kernel", "_bwd_dqkv_kernel"]


def test_odd_requested_blocks_legalized_at_200px(monkeypatch):
    """Regression for the 200px tile-legality bug: a hand-tuned block size
    that doesn't divide the dtype's tile unit (say 300, or N itself at
    N=2501) used to flow straight into the BlockSpecs via ``min(block, N)``
    — silently fine under CPU interpret, a Mosaic reject on chip. Every
    request must now be legalized (ops/tiling.legal_block), forward and
    backward, f32 and bf16, at both 200px token counts (p4 N=2501,
    p8 N=626)."""
    from ddim_cold_tpu.ops import flash_attention as fa

    calls = _tile_rule_spy(monkeypatch, fa)
    cases = [(2501, jnp.float32, 300, 500), (2501, jnp.float32, 2501, 2501),
             (626, jnp.bfloat16, 100, 104), (626, jnp.bfloat16, 8, 632)]
    for N, dtype, bq, bkv in cases:
        q, k, v = (x.astype(dtype) for x in _rand_qkv(13, 1, N, 1, 64))
        scale = 64**-0.5
        out = fa.flash_attention(q, k, v, scale, bq, bkv)
        assert np.isfinite(np.asarray(out, np.float32)).all(), (N, bq, bkv)
        g = jax.grad(lambda q: fa.flash_attention(
            q, k, v, scale, bq, bkv).astype(jnp.float32).sum())(q)
        assert np.isfinite(np.asarray(g, np.float32)).all(), (N, bq, bkv)
    assert calls.count("_fwd_kernel") == 2 * len(cases), calls


def test_legal_block_policy():
    """The pad-or-clamp helper itself (pure host arithmetic)."""
    from ddim_cold_tpu.ops import tiling

    assert tiling.legal_block(256, 2504, jnp.float32) == 256
    assert tiling.legal_block(300, 2504, jnp.float32) == 304   # round up
    assert tiling.legal_block(300, 2504, jnp.bfloat16) == 304  # 304 % 16 == 0
    assert tiling.legal_block(100, 2504, jnp.bfloat16) == 112
    assert tiling.legal_block(4096, 626, jnp.bfloat16) == 640  # clamp to dim⁺
    assert tiling.legal_block(8, 2504, jnp.bfloat16) == 16     # sub-unit
    assert tiling.legal_block(100, 384, jnp.float32, lane=True) == 128
    # K of the dequant matmul: lane for the activation AND int8 sublane
    assert tiling.legal_block(100, 384, jnp.bfloat16, lane=True,
                              min_unit=32) == 128
    assert tiling.sublane_unit(jnp.float32) == 8
    assert tiling.sublane_unit(jnp.bfloat16) == 16
    assert tiling.sublane_unit(jnp.int8) == 32
    with pytest.raises(ValueError):
        tiling.legal_block(0, 64, jnp.float32)
    with pytest.raises(ValueError):
        tiling.sublane_unit(jnp.float64)


def _sub_jaxprs(val):
    """Jaxpr-valued payloads inside an eqn param (Jaxpr, ClosedJaxpr, lists)."""
    if hasattr(val, "eqns"):
        return [val]
    if hasattr(val, "jaxpr"):
        return [val.jaxpr]
    if isinstance(val, (list, tuple)):
        return [j for item in val for j in _sub_jaxprs(item)]
    return []


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _kernel_dot_eqns(jaxpr):
    """dot_general eqns INSIDE pallas_call kernel bodies (the MXU GEMMs)."""
    dots = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        inner = eqn.params["jaxpr"]
        inner = getattr(inner, "jaxpr", inner)
        dots += [e for e in _iter_eqns(inner)
                 if e.primitive.name == "dot_general"]
    return dots


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_gemms_run_in_input_dtype_with_f32_accumulation(dtype):
    """CPU guard for the bf16-gemm-v2 contract, on TRACED dtypes: every GEMM
    inside the Pallas kernels (forward AND both backward kernels) must take
    its operands in the model's input dtype — an explicit f32 upcast would
    silently cost ~4× MXU throughput on v5e and double VMEM traffic, which no
    numerics test can see — while accumulating in f32 via
    preferred_element_type (which every parity test above DOES depend on).
    Asserting on the jaxpr pins both halves of the contract on CPU, where the
    perf regression itself is unmeasurable. Keyed to KERNEL_REV so a future
    kernel revision must revisit this contract explicitly rather than
    inheriting a stale guard."""
    from ddim_cold_tpu.ops import flash_attention as fa

    assert fa.KERNEL_REV == "fused-trunk-v3", (
        "kernel revision changed — re-derive the GEMM dtype contract here")

    dt = jnp.dtype(dtype)
    q, k, v = (x.astype(dt) for x in _rand_qkv(23, 1, 64, 2, 8))
    scale = 8**-0.5

    fwd = jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, scale))(q, k, v)
    fwd_dots = _kernel_dot_eqns(fwd.jaxpr)
    assert len(fwd_dots) == 2, fwd_dots  # q·kᵀ logits + p·v

    bwd = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, scale).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    # fwd rerun (2) + the dqkv kernel's five a head (scores, pᵀ·do, dp,
    # dsᵀ·q, ds·k) = 7; at explicit blocks dq (scores, dp, ds·k) + dkv
    # (scores, pᵀ·do, dp, dsᵀ·q) make it 9
    bwd_dots = _kernel_dot_eqns(bwd.jaxpr)
    assert len(bwd_dots) == 7, bwd_dots
    two = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, scale, 64, 64).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    assert len(_kernel_dot_eqns(two.jaxpr)) == 9
    bwd_dots += _kernel_dot_eqns(two.jaxpr)

    for eqn in fwd_dots + bwd_dots:
        pref = eqn.params.get("preferred_element_type")
        assert pref is not None and jnp.dtype(pref) == jnp.float32, eqn
        for invar in eqn.invars:
            assert invar.aval.dtype == dt, (
                f"kernel GEMM operand traced as {invar.aval.dtype}, "
                f"expected input dtype {dt}: {eqn}")


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_fused_kernel_gemms_run_in_input_dtype(dtype):
    """fused-trunk-v3 extension of the GEMM dtype guard: every dot inside
    the fused trunk-attention megakernel (qkv dequant producer, logits,
    p·v, proj consumer) and the fused Mlp kernel (x·w1, gelu·w2) takes its
    operands in the ACTIVATION dtype with f32 accumulation — the int8
    weights are upcast to the activation dtype, never to f32."""
    from ddim_cold_tpu.ops import quant
    from ddim_cold_tpu.ops.flash_attention import fused_trunk_attention

    dt = jnp.dtype(dtype)
    C, H, N = 64, 2, 40
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, N, C)), dt)
    wq = jnp.asarray(rng.integers(-127, 128, (C, 3 * C)), jnp.int8)
    wp = jnp.asarray(rng.integers(-127, 128, (C, C)), jnp.int8)
    sq = jnp.ones((3 * C,), jnp.float32)
    bq = jnp.zeros((3 * C,), jnp.float32)
    sp = jnp.ones((C,), jnp.float32)
    bp = jnp.zeros((C,), jnp.float32)

    fwd = jax.make_jaxpr(lambda xx: fused_trunk_attention(
        xx, wq, sq, bq, wp, sp, bp, num_heads=H, scale=(C // H) ** -0.5,
        block_q=48, block_kv=48))(x)
    dots = _kernel_dot_eqns(fwd.jaxpr)
    # q projection + kv-chunk projection + proj consumer, plus the unrolled
    # per-head logits and p·v dots
    assert len(dots) == 3 + 2 * H, dots
    for eqn in dots:
        pref = eqn.params.get("preferred_element_type")
        assert pref is not None and jnp.dtype(pref) == jnp.float32, eqn
        for invar in eqn.invars:
            assert invar.aval.dtype == dt, (
                f"fused kernel GEMM operand traced as {invar.aval.dtype}, "
                f"expected input dtype {dt}: {eqn}")

    x2 = jnp.asarray(rng.standard_normal((N, C)), dt)
    w1 = jnp.asarray(rng.integers(-127, 128, (C, C)), jnp.int8)
    mlp = jax.make_jaxpr(lambda xx: quant.mlp_pallas(
        xx, w1, bp, w1, bp, scale1=sp, scale2=sp, mode="pallas",
        block_m=48))(x2)
    mdots = _kernel_dot_eqns(mlp.jaxpr)
    assert len(mdots) == 2, mdots  # x·w1, gelu(h)·w2
    for eqn in mdots:
        pref = eqn.params.get("preferred_element_type")
        assert pref is not None and jnp.dtype(pref) == jnp.float32, eqn
        for invar in eqn.invars:
            assert invar.aval.dtype == dt, eqn


def test_fused_kernel_w8a8_gemms_hit_int8_path():
    """w8a8: the two weight-side GEMMs in each fused kernel run int8×int8
    with int32 accumulation (requantized activations); the attention's
    logits/p·v dots stay in the f32 compute dtype."""
    from ddim_cold_tpu.ops import quant
    from ddim_cold_tpu.ops.flash_attention import fused_trunk_attention

    C, H, N = 64, 2, 40
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, N, C)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 128, (C, 3 * C)), jnp.int8)
    wp = jnp.asarray(rng.integers(-127, 128, (C, C)), jnp.int8)
    sq = jnp.ones((3 * C,), jnp.float32)
    bq = jnp.zeros((3 * C,), jnp.float32)
    sp = jnp.ones((C,), jnp.float32)
    bp = jnp.zeros((C,), jnp.float32)

    fwd = jax.make_jaxpr(lambda xx: fused_trunk_attention(
        xx, wq, sq, bq, wp, sp, bp, num_heads=H, scale=(C // H) ** -0.5,
        block_q=48, block_kv=48, mode="w8a8"))(x)
    dots = _kernel_dot_eqns(fwd.jaxpr)
    assert len(dots) == 3 + 2 * H, dots
    int8_dots = [e for e in dots
                 if all(v.aval.dtype == jnp.int8 for v in e.invars)]
    assert len(int8_dots) == 3, dots  # q + kv producers, proj consumer
    for eqn in int8_dots:
        assert jnp.dtype(eqn.params["preferred_element_type"]) == jnp.int32

    x2 = jnp.asarray(rng.standard_normal((N, C)), jnp.float32)
    w1 = jnp.asarray(rng.integers(-127, 128, (C, C)), jnp.int8)
    mlp = jax.make_jaxpr(lambda xx: quant.mlp_pallas(
        xx, w1, bp, w1, bp, scale1=sp, scale2=sp, mode="w8a8",
        block_m=48))(x2)
    mdots = _kernel_dot_eqns(mlp.jaxpr)
    assert len(mdots) == 2, mdots
    for eqn in mdots:
        assert all(v.aval.dtype == jnp.int8 for v in eqn.invars), eqn
        assert jnp.dtype(eqn.params["preferred_element_type"]) == jnp.int32


from ddim_cold_tpu.ops.flash_attention import blockwise_attention_xla  # noqa: E402


@pytest.mark.parametrize("N,bkv", [(8, 512), (257, 64), (300, 128)])
def test_blockwise_xla_matches_dense(N, bkv):
    """The pure-XLA blockwise path (the Mosaic-free safety net) must match
    dense softmax attention, including odd N with a masked padded tail."""
    q, k, v = _rand_qkv(8, 2, N, 4, 16)
    scale = 16**-0.5
    ours = np.asarray(blockwise_attention_xla(q, k, v, scale, bkv))
    _, want = _dense_attention_f32(q, k, v, scale)
    np.testing.assert_allclose(ours, np.asarray(want), rtol=2e-5, atol=2e-6)


def test_model_use_flash_xla_parity():
    """DiffusionViT(use_flash='xla') ≡ the einsum model in eval mode, and the
    YAML surface parses the string (false/true/'xla')."""
    import jax.numpy as jnp

    cfg = dict(img_size=(16, 16), patch_size=4, embed_dim=32, depth=2, num_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3))
    t = jnp.array([3, 500], jnp.int32)
    base = DiffusionViT(**cfg)
    params = base.init(jax.random.PRNGKey(1), x, t)["params"]
    xla = DiffusionViT(use_flash="xla", **cfg)
    np.testing.assert_allclose(
        np.asarray(xla.apply({"params": params}, x, t)),
        np.asarray(base.apply({"params": params}, x, t)),
        rtol=2e-4, atol=2e-5)

    from ddim_cold_tpu.config import _check_use_flash

    assert _check_use_flash("xla") == "xla"
    assert _check_use_flash(True) is True
    assert _check_use_flash("pallas") is True
    assert _check_use_flash(False) is False
    with pytest.raises(ValueError, match="use_flash"):
        _check_use_flash("fast")
