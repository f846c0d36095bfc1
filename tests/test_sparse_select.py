"""ops/sparse_select.py and the selected attention forward (``fwd_selected``)
of ops/flash_attention.py: the threshold selection against ``lax.top_k`` on
seeded scores and on scores with deliberate ties (all kept), the two
selection launches (``dsa_index``, ``dsa_select``) in interpreter mode against
the XLA path, the attention launch against a dense masked softmax and its XLA
stand-in, the counters, what has no backward saying so by name, and the last
q block's folds on the rows it holds, bit for bit the folds on the whole
block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import flash_attention as fa
from ddim_cold_tpu.ops import sparse_select as ss
from tests.test_flash_masked import (EXTRAS, _rotary, tail_key,
                                     with_and_without_short_folds)


def _index_inputs(n, L, J, D, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (n, L, J, D), dtype),
            jax.random.normal(ks[1], (n, L, D), dtype),
            jax.random.normal(ks[2], (n, L, J), jnp.float32))


def _causal(L):
    return np.tril(np.ones((L, L), bool))


def test_threshold_selection_is_top_k_on_seeded_scores():
    """Without ties: row t keeps min(t + 1, top) keys, and they are
    ``lax.top_k``'s of its visible scores."""
    L, top = 60, 12
    scores = jax.random.normal(jax.random.PRNGKey(1), (2, L, L))
    keep = np.asarray(ss.threshold_mask_xla(scores, top))
    assert (keep.sum(-1) == np.minimum(np.arange(L) + 1, top)).all()
    assert not (keep & ~_causal(L)).any()
    seen = jnp.where(_causal(L), scores, -jnp.inf)
    _, best = jax.lax.top_k(seen, top)
    for t in range(top - 1, L):
        assert set(np.flatnonzero(keep[1, t])) == set(np.asarray(best[1, t]))
    # fewer tokens than top: everything visible
    assert (np.asarray(ss.threshold_mask_xla(scores[:, :8, :8], top))
            == _causal(8)).all()


def _tied_scores(L, seed=2):
    """Scores on a grid of five values, with both zeros among them."""
    raw = jax.random.randint(jax.random.PRNGKey(seed), (1, L, L), -2, 3)
    return jnp.where(raw == 0, jnp.where(jnp.arange(L) % 2 == 0, 0.0, -0.0),
                     raw.astype(jnp.float32) * 0.5)


def test_every_key_that_ties_with_the_threshold_is_kept():
    """The stated rule, where a ``topk`` would cut the tie: row t keeps
    {s <= t : I_ts >= tau_t}, tau_t its ``top``-th largest visible score."""
    L, top = 48, 6
    scores = _tied_scores(L)
    keep = np.asarray(ss.threshold_mask_xla(scores, top))[0]
    s = np.asarray(scores)[0]
    for t in range(L):
        visible = s[t, :t + 1]
        tau = np.sort(visible)[::-1][top - 1] if t + 1 >= top else -np.inf
        assert (keep[t, :t + 1] == (visible >= tau)).all()  # -0.0 >= 0.0
        assert not keep[t, t + 1:].any()
    assert (keep.sum(-1) > top).any()  # ties did keep more than top somewhere
    assert (keep.sum(-1)[top - 1:] >= top).all()


@pytest.mark.parametrize("L,top", [(48, 6), (600, 40)])
def test_select_kernel_keeps_ties_as_the_xla_path_does(L, top):
    """``dsa_select`` alone on tied scores (both zeros, negatives), in
    interpreter mode: bit for bit the XLA mask; rows and columns past the
    sequence are zeros."""
    scores = _tied_scores(L)
    length = ss.mask_length(L, jnp.float32)
    padded = jnp.pad(scores, ((0, 0), (0, length - L), (0, length - L)),
                     constant_values=jnp.nan)  # what lies there is unspecified
    got = np.asarray(ss._select_call(padded, top=top, n_valid=L,
                                     interpret=True))
    want = np.asarray(ss.threshold_mask_xla(scores, top))
    assert got.dtype == np.int8 and got.shape == (1, length, length)
    assert (got[:, :L, :L] == want).all()
    assert not got[:, L:].any() and not got[:, :, L:].any()


@pytest.mark.parametrize("n,L,J,D,top", [
    (2, 70, 4, 32, 16),     # the toy trunk's kind of shape: one ragged block
    (1, 600, 3, 128, 100),  # two blocks of 512, the second ragged
    (1, 40, 2, 128, 64),    # fewer tokens than top: every visible key
])
def test_the_selection_launches_match_the_xla_path(n, L, J, D, top):
    q, k, w = _index_inputs(n, L, J, D)
    length = ss.mask_length(L, q.dtype)
    got = np.asarray(ss.select_kernel(q, k, w, top, length))
    want = np.asarray(ss.select_xla(q, k, w, top, length))
    assert (got == want).all()
    # with a few index heads a score is an exact 0 wherever every head's
    # ReLU is: those tie, and ties are kept
    kept = want[:, :L, :L].sum(-1)
    assert (kept >= np.minimum(np.arange(L) + 1, top)).all()
    # the scores themselves, on the causal part (above it: unspecified)
    block, _ = fa._masked_blocks(L, q.dtype)
    scores = ss._index_call(q.reshape(n, L, J * D), k, w, heads=J, dim=D,
                            block=block, length=length, interpret=True)
    np.testing.assert_allclose(
        np.where(_causal(L), scores[:, :L, :L], 0.0),
        np.where(_causal(L), ss.index_scores_xla(q, k, w), 0.0),
        rtol=1e-4, atol=1e-4)


def test_select_counts_its_path_and_is_the_xla_path_off_the_chip():
    metrics.reset()
    q, k, w = _index_inputs(1, 40, 2, 16)
    got = ss.select(q, k, w, 8)
    assert ss._kernels.by_key("kernels.dsa_select_schedule") == {"xla": 1}
    assert (np.asarray(got) == np.asarray(
        ss.select_xla(q, k, w, 8, ss.mask_length(40, q.dtype)))).all()
    metrics.reset()


def _qkv(N, H, KV, D, B=1, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = lambda heads: (B, N, heads, D)
    return (jax.random.normal(ks[0], shape(H), dtype),
            jax.random.normal(ks[1], shape(KV), dtype),
            jax.random.normal(ks[2], shape(KV), dtype))


def _dense(q, k, v, scale, keep):
    """Every score, the selection as an explicit boolean mask, one softmax."""
    N, H = q.shape[1:3]
    k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * scale
    sees = jnp.asarray(_causal(N)) & (keep[:, :N, :N] != 0)
    p = jax.nn.softmax(jnp.where(sees[:, None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhnm,bmhd->bnhd", p, v)


def _keep(B, N, top, dtype, seed=3):
    q, k, w = _index_inputs(B, N, 2, 16, seed)
    return ss.select_xla(q, k, w, top, ss.mask_length(N, dtype))


@pytest.mark.parametrize("N,H,KV,D,top", [
    (600, 2, 2, 256, 100),   # the published head size, a ragged 2nd chunk
    (1030, 2, 1, 128, 300),  # 3 chunks; 2 query heads a K/V head
    (37, 4, 4, 32, 8),       # the toy trunk's shape: heads padded to the lanes
])
def test_selected_forward_matches_dense_and_its_xla_stand_in(N, H, KV, D, top):
    q, k, v = _qkv(N, H, KV, D)
    keep = _keep(1, N, top, q.dtype)
    want = _dense(q, k, v, D ** -0.5, keep)
    got = fa.flash_attention_selected(q, k, v, D ** -0.5, keep)
    xla = fa.selected_attention(q, k, v, D ** -0.5, keep)  # off the TPU
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(xla, want, rtol=2e-5, atol=2e-6)


def test_a_row_whose_first_chunks_hold_none_of_its_keys():
    """Late queries that attend only to keys of the LAST chunk, early ones to
    key 0 alone: a chunk without a key of a row leaves nothing in its result."""
    N, D = 1100, 128
    q, k, v = _qkv(N, 1, 1, D, seed=4)
    length = ss.mask_length(N, q.dtype)
    keep = np.zeros((1, length, length), np.int8)
    keep[0, :1030, 0] = 1
    for t in range(1030, N):
        keep[0, t, 1025:t + 1:2] = 1
    keep = jnp.asarray(keep)
    got = fa.flash_attention_selected(q, k, v, D ** -0.5, keep)
    np.testing.assert_allclose(got, _dense(q, k, v, D ** -0.5, keep),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[0, 500, 0], v[0, 0, 0], rtol=1e-6)


def test_selected_forward_in_bfloat16():
    q, k, v = _qkv(600, 2, 2, 256, dtype=jnp.bfloat16)
    keep = _keep(1, 600, 64, q.dtype)
    got = fa.flash_attention_selected(q, k, v, 256 ** -0.5, keep)
    want = _dense(*(x.astype(jnp.float32) for x in (q, k, v)), 256 ** -0.5, keep)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


@pytest.mark.parametrize("extra", EXTRAS)
@pytest.mark.parametrize("turned", [False, True], ids=["turned_before", "turned"])
def test_the_last_blocks_short_folds_are_bit_for_bit_the_whole_blocks(
        turned, extra, monkeypatch):
    """``fwd_selected`` at one q block of 512 rows and a few tokens more, the
    published head of 256 (with and without the in-launch turn of its last 64
    dims), two query heads on one K/V head: the int8 selection's tile is read
    at the short slice — 32 rows are one packed tile — and the result is bit
    for bit the whole block's, as ``tests/test_flash_masked.py``'s test of the
    same name says; against the dense float32 reference within bfloat16's
    rounding."""
    bq, _ = fa._masked_blocks(2048, jnp.bfloat16)
    N = bq + EXTRAS[extra](bq)
    q, k, v = _qkv(N, 2, 1, 256, seed=5, dtype=jnp.bfloat16)
    keep = _keep(1, N, 200, q.dtype)
    rotary = _rotary(64, "interleave", 192) if turned else None
    got, whole = with_and_without_short_folds(
        lambda: fa.flash_attention_selected(q, k, v, 256 ** -0.5, keep, rotary),
        monkeypatch, tail_key(N - bq, bq))
    assert bq == 512 and got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_array_equal(got, whole)
    want = _dense(*(x.astype(jnp.float32)
                    for x in (fa._turned_by_xla(q, rotary), k, v)),
                  256 ** -0.5, keep)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)


def test_the_selected_launch_has_a_name_and_a_counter_key_of_its_own():
    metrics.reset()
    q, k, v = _qkv(40, 2, 2, 128)
    keep = _keep(1, 40, 8, q.dtype)
    selected = str(jax.make_jaxpr(lambda q, k, v, m: fa.flash_attention_selected(
        q, k, v, 0.1, m))(q, k, v, keep))
    masked = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention_masked(
        q, k, v, 0.1, causal=True))(q, k, v))
    assert "fwd_selected" in selected and "fwd_masked" not in selected
    assert "fwd_masked" in masked and "fwd_selected" not in masked
    assert fa._kernels.by_key("kernels.flash_fwd_mask") == {
        "selected": 1, "causal": 1}
    metrics.reset()


def test_what_the_selected_forward_refuses():
    q, k, v = _qkv(40, 4, 2, 128)
    keep = _keep(1, 40, 8, q.dtype)
    with pytest.raises(ValueError, match="the selection of 40 tokens is int8"):
        fa.flash_attention_selected(q, k, v, 0.1, keep[:, :32])
    with pytest.raises(ValueError, match="the selection of 40 tokens is int8"):
        fa.flash_attention_selected(q, k, v, 0.1, keep.astype(jnp.int32))
    with pytest.raises(ValueError, match="divide into"):
        fa.flash_attention_selected(q[:, :, :3], k, v, 0.1, keep)


def test_selected_attention_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    q, k, v = _qkv(24, 2, 2, 16)
    keep = _keep(1, 24, 6, q.dtype)
    loss = lambda fn: lambda q: jnp.sum(fn(q, k, v, 0.25, keep) ** 2)
    got = jax.grad(loss(fa.selected_attention))(q)  # the XLA path, on the CPU
    want = jax.grad(loss(_dense))(q)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    with pytest.raises(NotImplementedError, match="fwd_selected kernel has no "
                                                  "backward"):
        jax.grad(lambda q: jnp.sum(fa._selected_no_vjp(
            q, k, v, keep, 0.25) ** 2))(q)
