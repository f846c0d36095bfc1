"""ops/flash_attention.py's masked forward on shared K/V heads (``fwd_masked``)
in interpreter mode, against dense float32 attention and against
``blockwise_attention_xla`` (its stand-in off the TPU): causal and window
masks; 1, 6 and 9 query heads a K/V head; token counts that are and are not
multiples of the 512-token chunk; a window smaller and larger than a chunk;
which chunks a q block visits; the counter; the unmasked path untouched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.ops import flash_attention as fa


def _qkv(N, H, KV, D, B=1, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = lambda heads: (B, N, heads, D)
    return (jax.random.normal(ks[0], shape(H), dtype),
            jax.random.normal(ks[1], shape(KV), dtype),
            jax.random.normal(ks[2], shape(KV), dtype))


def _dense(q, k, v, scale, causal, window):
    """Every score, an explicit boolean mask, one softmax."""
    N, H = q.shape[1:3]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * scale
    row, col = jnp.arange(N)[:, None], jnp.arange(N)[None]
    sees = jnp.ones((N, N), bool)
    if causal:
        sees &= col <= row
    if window is not None:
        sees &= col > row - window
    p = jax.nn.softmax(jnp.where(sees, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhnm,bmhd->bnhd", p, v)


@pytest.mark.parametrize("N,H,KV,D,causal,window", [
    (600, 6, 1, 128, True, None),    # 6 heads a K/V head, a ragged 2nd chunk
    (1030, 9, 1, 128, True, 512),    # 9 a head; 3 chunks, 2 visited a block
    (1024, 2, 2, 128, True, 300),    # whole chunks; a window inside a chunk
    (700, 2, 1, 128, True, 600),     # a window longer than a chunk
    (530, 2, 1, 128, False, None),   # shared heads alone: no mask but the end
    (37, 4, 2, 16, True, 8),         # the toy trunk's shape: heads padded
])
def test_masked_forward_matches_dense_and_blockwise(N, H, KV, D, causal, window):
    q, k, v = _qkv(N, H, KV, D)
    scale = D ** -0.5
    want = _dense(q, k, v, scale, causal, window)
    got = fa.flash_attention(q, k, v, scale, causal=causal, window=window)
    xla = fa.blockwise_attention_xla(q, k, v, scale, causal=causal,
                                     window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(xla, want, rtol=2e-5, atol=2e-6)


def test_masked_forward_in_bfloat16():
    q, k, v = _qkv(600, 2, 1, 128, dtype=jnp.bfloat16)
    want = _dense(*(x.astype(jnp.float32) for x in (q, k, v)), 128 ** -0.5,
                  True, 512)
    got = fa.flash_attention_masked(q, k, v, 128 ** -0.5, window=512)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=3e-2,
                               atol=3e-2)


def test_a_q_block_visits_only_the_chunks_its_mask_meets():
    """4,097 tokens at blocks (512, 512): under a window of 512 every q block
    meets at most 2 of the 9 chunks, under the causal mask block i meets
    chunks 0..i; the grid's last axis is the longest of those."""
    geometry = dict(bq=512, bkv=512, n_valid=4097, lib=fa._Ints)
    window = [fa._visible_chunks(i, causal=True, window=512, **geometry)
              for i in range(9)]
    assert window == [(0, 0)] + [(i - 1, i) for i in range(1, 9)]
    causal = [fa._visible_chunks(i, causal=True, window=None, **geometry)
              for i in range(9)]
    assert causal == [(0, i) for i in range(9)]
    assert fa._visible_chunks(3, causal=False, window=None,
                              **geometry) == (0, 8)
    assert fa._masked_blocks(4097, jnp.bfloat16) == (512, 512)
    assert fa._masked_blocks(37, jnp.float32) == (40, 40)


def test_counter_says_which_mask_a_trace_had(monkeypatch):
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    q, k, v = _qkv(40, 2, 1, 128)
    for kwargs in (dict(causal=True), dict(causal=True, window=16),
                   dict(causal=False)):
        fa.flash_attention(q, k, v, 0.1, **kwargs)
    fa.flash_attention(q, q, q, 0.1)  # the unmasked kernel counts "none" too
    assert fa._kernels.by_key("kernels.flash_fwd_mask") == {
        "causal": 1, "window": 1, "none": 2}
    metrics.reset()


def test_the_unmasked_path_lowers_to_the_same_text_with_the_new_arguments():
    """``causal`` and ``window`` at their defaults, and K/V heads equal to the
    query heads, are the launch the benchmark's ``%fwd`` readers know: same
    lowered text, and no ``fwd_masked`` in it."""
    q, k, v = _qkv(300, 4, 4, 64)
    old = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, 0.125)).lower(
        q, k, v).as_text()
    new = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, 0.125, causal=False, window=None)).lower(q, k, v).as_text()
    assert old == new and "fwd_masked" not in old
    masked = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, 0.125, causal=True)).lower(q, k, v).as_text()
    assert masked != old


def test_what_the_masked_forward_refuses():
    q, k, v = _qkv(40, 4, 2, 128)
    with pytest.raises(ValueError, match="causal window"):
        fa.flash_attention(q, k, v, 0.1, window=8)
    with pytest.raises(ValueError, match="blocks from the"):
        fa.flash_attention(q, k, v, 0.1, 128, 128, causal=True)
    with pytest.raises(ValueError, match="divide into"):
        fa.flash_attention_masked(q[:, :, :3], k, v, 0.1)


def test_masked_attention_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    q, k, v = _qkv(24, 4, 2, 16)
    loss = lambda fn: lambda q: jnp.sum(fn(q, k, v, 0.25, causal=True,
                                           window=8) ** 2)
    got = jax.grad(loss(fa.masked_attention))(q)  # the XLA path, on the CPU
    want = jax.grad(lambda q: jnp.sum(_dense(q, k, v, 0.25, True, 8) ** 2))(q)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    with pytest.raises(NotImplementedError, match="fwd_masked kernel has no "
                                                  "backward"):
        jax.grad(lambda q: jnp.sum(fa._masked_no_vjp(
            q, k, v, 0.25, True, 8) ** 2))(q)
