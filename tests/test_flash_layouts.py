"""The two layout sweeps of the flash-attention kernels: the launches that
read q, k, v where the projection wrote them, BITWISE the launches on the
same shape laid out head-major first — forward (144 cases) and backward
(108). A file of their own so that ``--dist loadfile`` can run them beside
``test_flash_attention.py`` instead of behind it.

The head-major side of a case does not depend on how the in-place side was
handed its operands, so each distinct reference is computed once a module
(``_head_major_forward`` / ``_head_major_backward``) and every case keeps its
own launch, its own bitwise comparison and its own counter assertions."""

import functools

import numpy as np
import pytest

from ddim_cold_tpu.ops import flash_attention as fa
from tests.test_flash_attention import _backward, _forward, _rand_qkv


def _operands(D, N, dtype, *seeds):
    """q, k, v (and one cotangent a second seed) for two lane groups of
    heads of size ``D``: a function of the shape alone, so a case and the
    reference it shares see the same arrays."""
    H = 2 * 128 // D
    first, *more = seeds
    return H, tuple(x.astype(dtype) for x in (
        *_rand_qkv(first, 2, N, H, D),
        *(_rand_qkv(seed, 2, N, H, D)[0] for seed in more)))


def _counted(key):
    return dict(fa._kernels.by_key(key))


def _laid_out_head_major(key, launch):
    """``launch()`` with the layout rule saying no → its result, having
    counted exactly one ``head_major`` launch under ``key`` and no other."""
    before = _counted(key)
    with pytest.MonkeyPatch.context() as patch:  # the rule says no: as before
        patch.setattr(fa, "_heads_per_lane_group", lambda heads, head_dim: None)
        result = launch()
    after = _counted(key)
    assert after["head_major"] == before.get("head_major", 0) + 1
    assert after.get("in_place", 0) == before.get("in_place", 0)
    return result


@functools.cache
def _head_major_forward(D, N, dtype, blocks, with_lse):
    """The forward on the shape transposed and zero-padded to head-major
    first → (context, lse or None) as numpy. What it depends on is the key:
    not ``packed``, the in-place side's operand form."""
    _, (q, k, v) = _operands(D, N, dtype, 41)
    want, want_lse = _laid_out_head_major(
        "kernels.flash_fwd_layout", lambda: _forward(
            q, k, v, D ** -0.5, *blocks, with_lse=with_lse))
    return np.asarray(want, np.float32), (
        None if want_lse is None else np.asarray(want_lse))


@pytest.mark.parametrize("with_lse", [False, True], ids=["primal", "lse"])
@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
@pytest.mark.parametrize("blocks", [(None, None), (64, 128)],
                         ids=["resident", "streamed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("N", [8, 257, 300])
@pytest.mark.parametrize("D", [32, 64, 128])
def test_in_place_forward_is_bitwise_the_head_major_forward(
        D, N, dtype, blocks, packed, with_lse):
    """The forward reading q, k, v where the projection wrote them — several
    heads on the 128 lanes, the token axis ending inside the last block (the
    interpreter fills what lies past it with NaN) — against the same shape
    transposed and zero-padded to head-major first: the context BITWISE, and
    (``with_lse``) the log-sum-exp of every true row. Handed over as three
    arrays (``flash_attention``'s form) or as the one packed projection
    (``flash_attention_qkv``'s)."""
    H, (q, k, v) = _operands(D, N, dtype, 41)
    before = _counted("kernels.flash_fwd_layout")
    ours, lse = _forward(q, k, v, D ** -0.5, *blocks, with_lse=with_lse,
                         packed=packed)
    after = _counted("kernels.flash_fwd_layout")
    assert after["in_place"] - before.get("in_place", 0) == 1
    assert after.get("head_major", 0) == before.get("head_major", 0)
    want, want_lse = _head_major_forward(D, N, dtype, blocks, with_lse)
    assert ours.dtype == q.dtype and np.isfinite(
        np.asarray(ours, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(ours, np.float32), want)
    if with_lse:
        assert lse.shape == want_lse.shape == (2 * H, lse.shape[1])
        np.testing.assert_array_equal(np.asarray(lse[:, :N]), want_lse[:, :N])
    else:
        assert lse is None and want_lse is None


def _spied_backward(*args, packed):
    """``_backward`` → (gradients, the blocks ``_bwd_blocks`` chose for it)."""
    chosen, real = [], fa._bwd_blocks

    def spy(*spied):
        chosen.append(real(*spied))
        return chosen[-1]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_bwd_blocks", spy)
        grads = _backward(*args, packed=packed)
    (blocks,) = chosen
    return grads, blocks


@functools.cache
def _head_major_backward(D, N, dtype, blocks):
    """The backward on the shape transposed and zero-padded to head-major
    first → (dq, dk, dv stacked, as numpy; the blocks it ran at). Handed the
    packed projection: head-major unpacks it before anything is laid out, so
    the launches see the arrays they would from three, and the unpacking and
    the restacking of the gradients around them stay run."""
    _, (q, k, v, g) = _operands(D, N, dtype, 47, 48)
    want, chosen = _laid_out_head_major(
        "kernels.flash_bwd_layout", lambda: _spied_backward(
            q, k, v, g, D ** -0.5, *blocks, packed=True))
    return np.asarray(want, np.float32), chosen


@pytest.mark.parametrize("packed", [False, True], ids=["qkv_apart", "packed"])
@pytest.mark.parametrize("blocks", [(None, None), (512, 512), (64, 128)],
                         ids=["fused", "resident", "streamed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("N", [8, 257, 300])
@pytest.mark.parametrize("D", [32, 64, 128])
def test_in_place_backward_is_bitwise_the_head_major_backward(
        D, N, dtype, blocks, packed):
    """The backward — the one ``dqkv`` launch where the blocks are left to it,
    ``dq`` and ``dkv`` where they are given — reading q, k, v and the
    cotangent where the model holds them and writing the gradients where the
    qkv GEMM's backward reads them — several heads on the 128 lanes, the
    token axis ending inside the last block (the interpreter fills what lies
    past it with NaN), the packed gradient written block by block by the one
    launch, or begun by ``dq`` and completed by ``dkv`` — against the same
    shape transposed and zero-padded to head-major first, at equal blocks:
    dq, dk and dv BITWISE (``dqkv``'s dq, where heads share the lanes, to the
    order of an f32 sum)."""
    _, (q, k, v, g) = _operands(D, N, dtype, 47, 48)
    before = _counted("kernels.flash_bwd_layout")
    ours, chosen = _spied_backward(q, k, v, g, D ** -0.5, *blocks,
                                   packed=packed)
    after = _counted("kernels.flash_bwd_layout")
    assert after["in_place"] - before.get("in_place", 0) == 1
    assert after.get("head_major", 0) == before.get("head_major", 0)
    want, want_chosen = _head_major_backward(D, N, dtype, blocks)
    assert chosen == want_chosen  # equal blocks, or nothing is shown
    if blocks[0] is None:
        assert set(chosen) == {"dqkv"}
    else:
        streamed = blocks[0] < N and N > 128
        assert (chosen["dq"][1] < N) == streamed  # dq: K/V chunks
        assert (chosen["dkv"][0] < N) == streamed  # dkv: q chunks
    assert ours.dtype == q.dtype and np.isfinite(
        np.asarray(ours, np.float32)).all()
    for name, got, ref in zip(("dq", "dk", "dv"), ours, want):
        got = np.asarray(got, np.float32)
        if name == "dq" and blocks[0] is None and D < 128:
            # dqkv multiplies a head's OWN head_dim rows of kᵀ into its rows
            # of dqᵀ; head-major, padded to the lanes, all 128: the same
            # products, which the CPU's dot sums in another order
            np.testing.assert_allclose(got, ref, err_msg=name, **(
                dict(rtol=0, atol=2e-6) if dtype == "float32"
                else dict(rtol=2 ** -7, atol=1e-6)))
        else:
            np.testing.assert_array_equal(got, ref, err_msg=name)
