"""ops/flash_attention.py's masked forward on shared K/V heads (``fwd_masked``)
in interpreter mode, against dense float32 attention and against
``blockwise_attention_xla`` (its stand-in off the TPU): causal and window
masks; 1, 6 and 9 query heads a K/V head; token counts that are and are not
multiples of the 512-token chunk; a window smaller and larger than a chunk;
which chunks a q block visits; the counter; the unmasked path untouched. And
the option of the same body that ``fwd_selected`` and ``fwd_masked`` take: an
UNTURNED q turned inside the launch, once a q block, against ``apply_rotary``
before it. And the fold: ONE program of ``fwd_masked`` holding a K/V chunk for
several query heads of its K/V head, bit for bit a program a head, the heads
and the q block from the shape under a row budget; ``fwd_selected`` and
``fwd_latent`` the programs they were. And the last q block's folds on the
rows it holds: bit for bit the folds on the whole block, at the lengths that
have such a block and only there."""

import hashlib

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import flash_attention as fa
from ddim_cold_tpu.ops import sparse_select as ss
from ddim_cold_tpu.ops.rotary import Rotary, apply_rotary, rotary_tables


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


# --- q turned inside the launch ----------------------------------------------

def _rotary(rot, pairing, first):
    """``rot`` dims from ``first`` on, the ``default`` frequencies."""
    inv = 8000000.0 ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    return Rotary(inv, 1.0, pairing, first)


def _selection(B, N, dtype, seed=9):
    """A third of the visible keys and every query's own: int8, whole blocks."""
    length = ss.mask_length(N, dtype)
    keep = jax.random.uniform(jax.random.PRNGKey(seed), (B, length, length)) < 0.3
    return (keep | jnp.eye(length, dtype=bool)).astype(jnp.int8)


def _turned(q, rotary):
    """q turned as the parent turned it: the whole array, in XLA, one jit."""
    B, N, H, D = q.shape
    return jax.jit(lambda q: rotary.apply(q.reshape(B, N, H * D), H))(
        q).reshape(q.shape)


def _ulps(a, b):
    """Distance in representable values of the arrays' dtype."""
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]
    ordered = lambda x: (lambda i: np.where(i < 0, np.iinfo(bits).min - i, i))(
        np.asarray(x).view(bits).astype(np.int64))
    return np.abs(ordered(a) - ordered(b))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("pairing,D,first,rot", [
    ("interleave", 256, 192, 64),   # the published head: its second lane group
    ("rotate_half", 256, 192, 64),
    ("rotate_half", 128, 0, 128),   # every dim of a one-group head turns
    ("interleave", 384, 136, 48),   # inside the middle group of three
])
def test_a_q_block_turned_in_vmem_is_apply_rotarys(pairing, D, first, rot, dtype):
    """:func:`_turn_q_block` over every block of a q of 300 tokens x 2 heads:
    the lanes that pass through bit for bit, the lanes that turn to ONE unit
    in the last place of q's dtype — the same float32 products and one
    rounding; on the CPU LLVM contracts ``x·cos + partner·sin`` into a fused
    multiply-add one way inside the interpreted body and another in XLA's own
    fusion, which moves the last bit of a few elements in 100,000 (on the
    chip: PERF.md section 6, PR 42)."""
    N, H, bq = 300, 2, 128
    rotary = _rotary(rot, pairing, first)
    geometry = fa._turn_geometry(rotary)
    assert geometry == (first // 128, first % 128, rot // 2,
                        pairing == "rotate_half")
    q = jax.random.normal(jax.random.PRNGKey(2), (1, N, H, D), dtype)
    cos, sin = rotary_tables(384, 128, rotary.inv_freq, 1.0, pairing=pairing,
                             first=geometry[1])

    def kernel(q_ref, cos_ref, sin_ref, o_ref):
        fa._turn_q_block(q_ref, cos_ref, sin_ref, o_ref.at[0], None, *geometry)

    block = pl.BlockSpec((1, bq, D), lambda i, h: (0, i, h))
    table = pl.BlockSpec((bq, 128), lambda i, h: (i, 0))
    got = pl.pallas_call(
        kernel, grid=(pl.cdiv(N, bq), H), in_specs=[block, table, table],
        out_specs=block, out_shape=jax.ShapeDtypeStruct((1, N, H * D), dtype),
        interpret=True)(q.reshape(1, N, H * D), cos, sin).reshape(q.shape)
    want = _turned(q, rotary)
    passes = np.ones(D, bool)
    passes[first:first + rot] = False
    np.testing.assert_array_equal(got[..., passes], q[..., passes])
    np.testing.assert_array_equal(want[..., passes], q[..., passes])
    apart = _ulps(got, want)
    assert apart.max() <= 1 and (apart > 0).mean() < 1e-3
    assert np.abs(np.asarray(got - q, np.float32))[:, 1:, :, ~passes].max() > 0.1


@pytest.mark.parametrize("pairing,N,B,KV,D,first,rot,scale,dtype", [
    # the published head, 2 images, a ragged 2nd q block, 1/16 folded into q
    ("interleave", 600, 2, 2, 256, 192, 64, 256 ** -0.5, jnp.bfloat16),
    # a scale that does not fold: the scores are scaled, the scratch is not
    ("interleave", 600, 1, 2, 256, 192, 64, 0.07, jnp.bfloat16),
    ("rotate_half", 600, 1, 1, 256, 192, 64, 256 ** -0.5, jnp.bfloat16),
    ("rotate_half", 1030, 1, 2, 256, 192, 64, 0.07, jnp.float32),  # 3 blocks
    ("interleave", 530, 1, 2, 128, 0, 128, 0.125, jnp.float32),    # all dims
    ("interleave", 37, 2, 4, 32, 24, 8, 0.2, jnp.float32),  # the toy: padded
])
def test_the_selected_launch_turns_an_unturned_q(pairing, N, B, KV, D, first,
                                                 rot, scale, dtype):
    """``fwd_selected`` with the tables on q as its projection wrote it,
    against the same launch without them on ``apply_rotary(q, first=…)``, and
    against ``selected_attention_xla`` of that q. To one unit in the last
    place of the result's dtype where q is bfloat16 (the turned q is
    ``apply_rotary``'s to that, see above; bit for bit on the chip), to
    float32 rounding otherwise."""
    H = 4 if D == 32 else 2
    rotary = _rotary(rot, pairing, first)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, N, H, D), dtype)
    k, v = (jax.random.normal(kk, (B, N, KV, D), dtype) for kk in ks[1:])
    keep = _selection(B, N, dtype)
    got = fa.flash_attention_selected(q, k, v, scale, keep, rotary)
    turned = _turned(q, rotary)
    launch = fa.flash_attention_selected(turned, k, v, scale, keep)
    oracle = fa.selected_attention_xla(
        *(x.astype(jnp.float32) for x in (turned, k, v)), scale, keep)
    assert got.shape == q.shape and got.dtype == q.dtype
    f32 = lambda x: np.asarray(x, np.float32)
    if dtype == jnp.bfloat16:
        np.testing.assert_allclose(f32(got), f32(launch), atol=2 ** -8, rtol=2 ** -8)
        np.testing.assert_allclose(f32(got), f32(oracle), atol=2e-2)
    else:
        np.testing.assert_allclose(got, launch, rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(got, oracle, rtol=2e-5, atol=2e-5)
    # and it is the TURNED q that was attended with
    assert np.abs(f32(got) - f32(fa.flash_attention_selected(
        q, k, v, scale, keep))).max() > 1e-2


def test_a_head_whose_rotated_dims_straddle_a_lane_group_is_turned_before_the_launch():
    """Dims 96..159 of a 256 head lie in two lane groups: no roll inside one
    group reaches every partner, so ``apply_rotary`` turns q and the launch
    is the one without tables — bit for bit that, and counted ``xla``."""
    rotary = _rotary(64, "rotate_half", 96)
    assert fa._turn_geometry(rotary) is None
    assert fa._turn_geometry(_rotary(64, "rotate_half", 64)) == (0, 64, 32, True)
    q, k, v = _qkv(300, 2, 2, 256)
    keep = _selection(1, 300, q.dtype)
    metrics.reset()
    got = fa.flash_attention_selected(q, k, v, 0.0625, keep, rotary)
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"xla": 1}
    want = fa.flash_attention_selected(
        apply_rotary(q.reshape(1, 300, 512), 2, rotary.inv_freq, 1.0,
                     first=96).reshape(q.shape), k, v, 0.0625, keep)
    np.testing.assert_array_equal(got, want)
    assert fa._kernels.by_key("kernels.flash_fwd_rotary") == {"xla": 1}
    metrics.reset()


#: Laguna-S-2.1's two entries of ``rope_parameters`` at a head of 128: every
#: dim turns, partner 64 lanes away (window layers); the first 64 dims turn by
#: YaRN's frequencies, its factor in the tables (full layers)
LAGUNA_ROPE = {
    "window": {"rope_type": "default", "rope_theta": 10000,
               "partial_rotary_factor": 1},
    "causal": {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
               "original_max_position_embeddings": 8192, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5}}


def _laguna_rotary(kind, head_dim=128):
    from ddim_cold_tpu.models.laguna import rotary_frequencies

    return Rotary(*rotary_frequencies(LAGUNA_ROPE[kind], head_dim))


@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("rep", [6, 9])
@pytest.mark.parametrize("kind,window,half", [("causal", None, 32),
                                              ("window", 512, 64)])
def test_the_masked_launch_turns_an_unturned_q(kind, window, half, rep, rows):
    """``fwd_masked`` with the tables on q as ``q_proj`` wrote it against the
    same launch without them on ``apply_rotary``'s q: Laguna-S-2.1's two
    rotations (``half`` pairs of a 128 head), 6 and 9 query heads a K/V head,
    1 and 4 rows, 600 tokens — the second q block ends inside its 512 — and
    the model's scale, which is no power of two and so stays on the scores.
    On bfloat16 operands BIT FOR BIT: here the interpreted body and XLA's
    fusion round the rotated lanes alike."""
    rotary, scale = _laguna_rotary(kind), 128 ** -0.5
    assert fa._turn_geometry(rotary) == (0, 0, half, True)
    assert not fa._scale_folds_into_q(scale)
    q, k, v = _qkv(600, rep, 1, 128, B=rows, seed=rep + rows,
                   dtype=jnp.bfloat16)
    got = fa.flash_attention_masked(q, k, v, scale, window=window,
                                    rotary=rotary)
    want = fa.flash_attention_masked(_turned(q, rotary), k, v, scale,
                                     window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_array_equal(got, want)
    # and it is the TURNED q that was attended with
    f32 = lambda x: np.asarray(x, np.float32)
    assert np.abs(f32(got) - f32(fa.flash_attention_masked(
        q, k, v, scale, window=window))).max() > 0.5


@pytest.mark.parametrize("kind,window", [("causal", None), ("window", 8)])
def test_the_masked_launch_turns_a_float32_q_in_padded_heads(kind, window):
    """The toy trunk's shape in float32 — heads of 16 zero-padded to the
    lanes, 37 tokens in one block, 2 and 3 query heads a K/V head, 3 rows:
    the launch turns the first 8 (full) or all 16 (window) dims of each
    padded head, to float32 rounding of ``apply_rotary`` then the launch and
    of dense attention."""
    rotary = _laguna_rotary(kind, 16)
    assert fa._turn_geometry(rotary) == (0, 0, 4 if window is None else 8, True)
    q, k, v = _qkv(37, 6 if window else 4, 2, 16, B=3, seed=5)
    got = fa.flash_attention_masked(q, k, v, 0.25, window=window,
                                    rotary=rotary)
    turned = _turned(q, rotary)
    np.testing.assert_allclose(got, fa.flash_attention_masked(
        turned, k, v, 0.25, window=window), rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got, _dense(turned, k, v, 0.25, True, window),
                               rtol=2e-5, atol=2e-5)


def test_off_the_tpu_masked_attention_turns_q_before_the_oracle():
    """``masked_attention(..., rotary=)`` on the CPU: ``apply_rotary`` then
    ``blockwise_attention_xla``, bit for bit, counted ``xla``; called
    directly, the launch counts ``kernel``, and ``xla`` for a head it cannot
    turn, which it is handed turned; no rotation, no count."""
    q, k, v = _qkv(40, 6, 2, 128)
    rotary = _laguna_rotary("causal")
    count = lambda: fa._kernels.by_key("kernels.flash_fwd_rotary")
    metrics.reset()
    fa.masked_attention(q, k, v, 0.1, window=16)
    fa.flash_attention_masked(q, k, v, 0.1, window=16)
    assert count() == {}
    got = fa.masked_attention(q, k, v, 0.1, window=16, rotary=rotary)
    assert count() == {"xla": 1}
    np.testing.assert_array_equal(got, fa.blockwise_attention_xla(
        apply_rotary(q.reshape(1, 40, 768), 6, *rotary[:2]).reshape(q.shape),
        k, v, 0.1, causal=True, window=16))
    jax.make_jaxpr(lambda q: fa.flash_attention_masked(
        q, k, v, 0.1, rotary=rotary))(q)
    assert count() == {"xla": 1, "kernel": 1}
    straddling = _rotary(64, "rotate_half", 96)
    q2, k2, v2 = _qkv(40, 2, 1, 256)
    got = fa.flash_attention_masked(q2, k2, v2, 0.1, rotary=straddling)
    assert count() == {"xla": 2, "kernel": 1}
    np.testing.assert_array_equal(got, fa.flash_attention_masked(
        straddling.apply(q2.reshape(1, 40, 512), 2).reshape(q2.shape),
        k2, v2, 0.1))
    metrics.reset()


def _pallas_call(fn, *args):
    """The one ``pallas_call`` equation in the jaxpr of ``fn(*args)``."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    (eqn,) = found
    return eqn


def _launch(fn, *args):
    """Name, operand count and scratch shapes of that launch."""
    eqn = _pallas_call(fn, *args)
    scratch = eqn.params["grid_mapping"].num_scratch_operands
    shapes = [(v.aval.shape, v.aval.dtype) for v in eqn.params["jaxpr"].invars]
    return (eqn.params["name"], len(eqn.invars),
            shapes[len(shapes) - scratch:])


def test_the_launches_without_the_option_are_the_parents():
    """``fwd_masked``, ``fwd_latent`` and ``fwd_selected`` without a rotation
    to run: the operands, the scratch (accumulator, running max, running
    denominator — and a head's half of q_r for the latent launch) and the
    names they had; with it ``fwd_selected`` and ``fwd_masked`` keep their
    names and take two tables and one more scratch, the turned q block. (A
    K/V head a query head, so that ``fwd_masked`` too holds one head a
    program; the group's program is further down.)"""
    f32 = jnp.dtype("float32")
    walk = lambda bq, lanes: [((bq, lanes), f32), ((bq, 128), f32),
                              ((bq, 128), f32)]
    q, k, v = _qkv(40, 2, 2, 256)
    assert _launch(lambda q, k, v: fa.flash_attention_masked(
        q, k, v, 0.1, window=16), q, k, v) == ("fwd_masked", 3, walk(40, 256))
    keep = _selection(1, 40, q.dtype)
    assert _launch(lambda q, k, v, m: fa.flash_attention_selected(
        q, k, v, 0.1, m), q, k, v, keep) == ("fwd_selected", 4, walk(40, 256))
    qn, kn, vv = (jnp.ones((1, 40, 2, 128)) for _ in range(3))
    qr, kr = jnp.ones((1, 40, 2, 64)), jnp.ones((1, 40, 64))
    assert _launch(lambda *a: fa.flash_attention_latent(*a, 0.1),
                   qn, qr, kn, kr, vv) == (
        "fwd_latent", 5, walk(40, 128) + [((1, 40, 128), f32)])
    rotary = _rotary(64, "interleave", 192)
    assert _launch(lambda q, k, v, m: fa.flash_attention_selected(
        q, k, v, 0.1, m, rotary), q, k, v, keep) == (
        "fwd_selected", 6, walk(40, 256) + [((40, 256), f32)])
    assert _launch(lambda q, k, v: fa.flash_attention_masked(
        q, k, v, 0.1, window=16, rotary=rotary), q, k, v) == (
        "fwd_masked", 5, walk(40, 256) + [((40, 256), f32)])


def test_the_rotary_counter_says_where_q_was_turned():
    """``kernels.flash_fwd_rotary``: ``kernel`` where the launch turns q (the
    TPU's path; here interpreted), ``xla`` off the TPU and for a head the
    launch cannot address; nothing where no rotation is handed on; and its
    row in the table of ``obs/metrics.py``."""
    assert "kernels.flash_fwd_rotary" in {name for name, *_ in metrics.METRICS}
    q, k, v = _qkv(40, 2, 2, 256)
    keep = _selection(1, 40, q.dtype)
    rotary = _rotary(64, "interleave", 192)
    count = lambda: fa._kernels.by_key("kernels.flash_fwd_rotary")
    metrics.reset()
    fa.flash_attention_selected(q, k, v, 0.1, keep)
    fa.selected_attention(q, k, v, 0.1, keep)
    assert count() == {}
    jax.make_jaxpr(lambda q: fa.flash_attention_selected(
        q, k, v, 0.1, keep, rotary))(q)
    assert count() == {"kernel": 1}
    got = fa.selected_attention(q, k, v, 0.1, keep, rotary)  # off the TPU
    assert count() == {"kernel": 1, "xla": 1}
    np.testing.assert_array_equal(got, fa.selected_attention_xla(
        apply_rotary(q.reshape(1, 40, 512), 2, rotary.inv_freq, 1.0,
                     pairing="interleave", first=192).reshape(q.shape),
        k, v, 0.1, keep))
    metrics.reset()


# --- the query heads of a K/V head folded by ONE program ---------------------

@pytest.mark.parametrize("rep,KV,N,window,turned,fold", [
    (7, 2, 600, None, False, (7, 256)),   # full; a ragged last chunk
    (7, 2, 1024, 700, True, (7, 256)),    # window over chunks, with the turn
    (7, 1, 1024, 200, False, (7, 256)),   # the far chunk wholly masked for
                                          # the last rows of q block 2
    (6, 2, 600, None, True, (6, 256)),
    (9, 1, 1030, 512, True, (9, 256)),    # three chunks, two visited a block
    (9, 2, 600, 600, False, (9, 256)),
    (16, 1, 600, None, False, (8, 256)),  # 8 of the 16: two groups a K/V head
    (16, 1, 1024, 300, True, (8, 256)),
    (2, 2, 37, 8, False, (2, 48)),        # one short block
    (11, 1, 600, None, False, (1, 512)),  # a prime above the budget: one head
    (1, 3, 600, 200, True, (1, 512)),     # a K/V head a query head
], ids=lambda x: None if isinstance(x, bool) else str(x).replace(" ", ""))
def test_the_fold_is_bit_for_bit_a_program_a_head(rep, KV, N, window, turned,
                                                  fold):
    """``rep`` query heads on each of ``KV`` K/V heads, bfloat16, the model's
    scale on the scores: the launch whose programs fold a chunk into ``fold``
    = (heads, q rows) against the SAME call with K and V repeated to a head
    each, which the rule can only give the one-head program at 512 rows —
    bit for bit; and against ``blockwise_attention_xla`` within bfloat16's
    rounding of the result."""
    rotary, scale = (_laguna_rotary("causal") if turned else None), 128 ** -0.5
    q, k, v = _qkv(N, rep * KV, KV, 128, seed=rep, dtype=jnp.bfloat16)
    assert fa._masked_fold(rep, N, 128, q.dtype) == fold
    masked = lambda q, k, v: fa.flash_attention_masked(
        q, k, v, scale, window=window, rotary=rotary)
    mapping = _pallas_call(masked, q, k, v).params["grid_mapping"]
    heads, bq = fold
    assert sorted(mapping.grid[1:3]) == sorted((rep * KV // heads,
                                                -(-N // bq)))
    got = masked(q, k, v)
    a_head = masked(q, *(jnp.repeat(x, rep, axis=2) for x in (k, v)))
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_array_equal(got, a_head)
    f32 = lambda x: np.asarray(x, np.float32)
    xla = fa.blockwise_attention_xla(
        *(x.astype(jnp.float32) for x in (fa._turned_by_xla(q, rotary), k, v)),
        scale, causal=True, window=window)
    np.testing.assert_allclose(f32(got), f32(xla), rtol=3e-2, atol=3e-2)


def test_the_fold_in_float32_padded_heads_and_several_rows():
    """The toy trunk's shape: heads of 16 zero-padded to the lanes, float32,
    3 rows, 7 query heads a K/V head, a scale that folds into q — against
    dense attention to float32 rounding and the one-head program bit for
    bit."""
    q, k, v = _qkv(40, 14, 2, 16, B=3, seed=3)
    assert fa._scale_folds_into_q(0.25)
    got = fa.flash_attention_masked(q, k, v, 0.25, window=8)
    np.testing.assert_array_equal(got, fa.flash_attention_masked(
        q, *(jnp.repeat(x, 7, axis=2) for x in (k, v)), 0.25, window=8))
    np.testing.assert_allclose(got, _dense(q, k, v, 0.25, True, 8),
                               rtol=2e-5, atol=2e-6)


def _grid_and_blocks(fn, *args):
    """Grid and block shapes of the one ``pallas_call`` of ``fn(*args)``."""
    mapping = _pallas_call(fn, *args).params["grid_mapping"]
    return tuple(mapping.grid), [
        tuple(getattr(d, "block_size", d) for d in b.block_shape)
        for b in mapping.block_mappings]


def test_a_group_is_one_program_and_a_selection_keeps_one_head():
    """Four query heads on ONE K/V head: one program, its q, result and
    scratch four heads wide, K and V one; turned, the q blocks outside the
    groups and the turned blocks four wide too. The same shape under a
    selection, and the latent launch, stay one head a program."""
    f32 = jnp.dtype("float32")
    q, k, v = _qkv(40, 4, 1, 128)
    masked = lambda q, k, v: fa.flash_attention_masked(q, k, v, 0.1, window=16)
    assert _launch(masked, q, k, v) == (
        "fwd_masked", 3, [((40, 512), f32)] * 3)
    assert _grid_and_blocks(masked, q, k, v) == (
        (1, 1, 1, 1), [(1, 40, 512), (1, 40, 128), (1, 40, 128), (1, 40, 512)])
    turned = lambda q, k, v: fa.flash_attention_masked(
        q, k, v, 0.1, rotary=_laguna_rotary("window"))
    assert _launch(turned, q, k, v) == (
        "fwd_masked", 5, [((40, 512), f32)] * 4)
    q8, k8, v8 = _qkv(600, 16, 2, 128)  # 8 heads a K/V head at 256 rows
    grid, blocks = _grid_and_blocks(turned, q8, k8, v8)
    assert grid == (1, 3, 2, 2)  # rows, q blocks, groups, chunks
    assert blocks == [(1, 256, 1024), (1, 512, 128), (1, 512, 128),
                      (256, 128), (256, 128), (1, 256, 1024)]
    keep = _selection(1, 40, q.dtype)
    selected = lambda q, k, v, m: fa.flash_attention_selected(q, k, v, 0.1, m)
    assert _launch(selected, q, k, v, keep) == (
        "fwd_selected", 4, [((40, 128), f32)] * 3)
    assert _grid_and_blocks(selected, q, k, v, keep) == (
        (1, 4, 1, 1), [(1, 40, 128)] * 3 + [(1, 40, 40), (1, 40, 128)])
    qn, kn, vv = (jnp.ones((1, 40, 2, 128)) for _ in range(3))
    qr, kr = jnp.ones((1, 40, 2, 64)), jnp.ones((1, 40, 64))
    grid, blocks = _grid_and_blocks(
        lambda *a: fa.flash_attention_latent(*a, 0.1), qn, qr, kn, kr, vv)
    assert grid == (1, 2, 1, 1) and blocks == [(1, 40, 128)] * 6


#: sha256 (16 hex digits) of ``str(jax.make_jaxpr(...))`` of the launches that
#: hold one head a program: the kernel's body, grid, blocks and scratch,
#: operation for operation, under ``tests/conftest.py``'s matmul precision. A
#: change that MEANS to alter one of these programs pins its text anew; the
#: fold must not. At 600 tokens the five launches at q blocks of 512 hold 88
#: rows in their last block, whose folds run on 96 rows since the PR that
#: gave the body its short folds (which pinned them anew); ``latent``, one
#: block of 600 rows, is as taken on be8ed9d, the parent of the PR that let
#: ``fwd_masked`` fold a group. ``_1024``: the same launches at two whole
#: blocks, taken on 533c891, the parent of the short folds' PR — a sequence
#: without a short last block lowers to the text it had.
ONE_HEAD_PROGRAMS = {
    "masked_rep1_window": "ee558bc49b65afcc",
    "masked_rep1_causal_turned": "ccc76d0242ae1af8",
    "selected_rep7": "41dce0031576b19f",
    "selected_rep7_turned": "6e891755474613ff",
    "latent": "cc9134dac65e7eaa",
    "masked_rep11_prime": "63569fa1f191b721",
    "masked_rep1_window_1024": "bc5c890064df000c",
    "masked_rep1_causal_turned_1024": "6f9acf1f8941384d",
    "selected_rep7_1024": "847d7f7bef87bbef",
    "selected_rep7_turned_1024": "49a1d9ee34a5243c",
    "latent_1024": "d18c70ed9d187532",
    "masked_rep11_prime_1024": "a4cd76a22ce540d6",
}


@pytest.mark.parametrize("launch", sorted(ONE_HEAD_PROGRAMS))
def test_one_head_launches_lower_to_the_text_they_had(launch):
    """``rep`` 1, a ``rep`` the budget cannot divide, any selection and the
    latent launch: the jaxpr — the ``pallas_call`` with its body — is letter
    for letter the pinned one; at a whole number of blocks, the one of the
    tree before the last block had folds of its own."""
    ones = lambda *shape: jnp.ones(shape, jnp.bfloat16)
    tokens = 1024 if launch.endswith("_1024") else 600
    qkv = lambda H, KV: (ones(1, tokens, H, 128), ones(1, tokens, KV, 128),
                         ones(1, tokens, KV, 128))
    rotary = Rotary(tuple(10000.0 ** (-i / 64) for i in range(64)), 1.0,
                    "rotate_half", 0)
    keep = jnp.ones((1, 1024, 1024), jnp.int8)
    fn, args = {
        "masked_rep1_window": (lambda q, k, v: fa.flash_attention_masked(
            q, k, v, 0.1, window=200), qkv(4, 4)),
        "masked_rep1_causal_turned": (lambda q, k, v: fa.flash_attention_masked(
            q, k, v, 128 ** -0.5, rotary=rotary), qkv(4, 4)),
        "selected_rep7": (lambda q, k, v, m: fa.flash_attention_selected(
            q, k, v, 0.1, m), qkv(14, 2) + (keep,)),
        "selected_rep7_turned": (lambda q, k, v, m: fa.flash_attention_selected(
            q, k, v, 0.1, m, rotary), qkv(14, 2) + (keep,)),
        "latent": (lambda *a: fa.flash_attention_latent(*a, 0.1),
                   (ones(1, tokens, 2, 128), ones(1, tokens, 2, 64),
                    ones(1, tokens, 2, 128), ones(1, tokens, 64),
                    ones(1, tokens, 2, 128))),
        "masked_rep11_prime": (lambda q, k, v: fa.flash_attention_masked(
            q, k, v, 0.1), qkv(11, 1)),
    }[launch.removesuffix("_1024")]
    text = str(jax.make_jaxpr(fn)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        ONE_HEAD_PROGRAMS[launch]


# --- the last q block's folds on the rows it holds ---------------------------

#: tokens past the last full q block, at a q block of ``bq`` rows
EXTRAS = {"0": lambda bq: 0, "1": lambda bq: 1, "2": lambda bq: 2,
          "31": lambda bq: 31, "33": lambda bq: 33,
          "half": lambda bq: bq // 2, "half+8": lambda bq: bq // 2 + 8}


def tail_key(extra, bq):
    """The key of ``kernels.flash_fwd_tail`` at ``extra`` tokens past the last
    full block of ``bq`` rows, written out: short folds on the rows that
    block holds in whole groups of 32, up to half a block."""
    rows = -(-extra // 32) * 32
    return f"{rows}/{bq}" if 0 < rows <= bq // 2 else "whole"


def with_and_without_short_folds(launch, monkeypatch, want):
    """``launch()`` as the rule has it, and with the last block's folds on
    every row (the ``tail`` of the ``_call`` None) — the counter's two keys
    checked on the way."""
    count = lambda: fa._kernels.by_key("kernels.flash_fwd_tail")
    assert "kernels.flash_fwd_tail" in {name for name, *_ in metrics.METRICS}
    metrics.reset()
    got = launch()
    assert count() == {want: 1}
    with monkeypatch.context() as patch:
        patch.setattr(fa, "_tail_rows", lambda n_valid, bq: None)
        whole = launch()
    assert count() == ({"whole": 2} if want == "whole" else
                       {want: 1, "whole": 1})
    metrics.reset()
    return got, whole


TAIL_LAUNCHES = {
    # (rep, KV, full q blocks, window, turned, dtype): the last block sees a
    # whole chunk and the diagonal's
    "causal": (1, 2, 1, None, False, jnp.bfloat16),
    # no chunk whole: no unmasked short fold in the program
    "window": (1, 1, 2, 300, False, jnp.bfloat16),
    "window_float32": (1, 1, 2, 300, False, jnp.float32),
    "group7": (7, 1, 1, None, False, jnp.bfloat16),
    "group6_window_turned": (6, 1, 2, 300, True, jnp.bfloat16),
    "turned": (1, 2, 1, None, True, jnp.bfloat16),
}


@pytest.mark.parametrize("extra", EXTRAS)
@pytest.mark.parametrize("launch", TAIL_LAUNCHES)
def test_the_last_blocks_short_folds_are_bit_for_bit_the_whole_blocks(
        launch, extra, monkeypatch):
    """``fwd_masked`` at ``k · bq`` tokens and a few more — one head a program
    and a folded group, causal and under a window, with and without the
    in-launch turn: where the rows the last block holds, in whole groups of
    32, are at most half a block, its folds run on those rows, and every row
    of the result is bit for bit the one of the launch whose folds run on the
    whole block; past half a block and on a block boundary the launch IS that
    one (``kernels.flash_fwd_tail``: ``whole``). Against the dense reference
    at the tolerances of the tests above. In float32 to a unit in the last
    place: the CPU's float32 GEMM sums a product of 32 rows in another order
    than one of 512 (the MXU has one order)."""
    rep, KV, blocks, window, turned, dtype = TAIL_LAUNCHES[launch]
    _, bq = fa._masked_fold(rep, 2048, 128, dtype)
    N = blocks * bq + EXTRAS[extra](bq)
    want = tail_key(N - blocks * bq, bq)
    assert fa._tail_key(fa._tail_rows(N, bq), bq) == want
    rotary, scale = (_laguna_rotary("causal") if turned else None), 128 ** -0.5
    q, k, v = _qkv(N, rep * KV, KV, 128, seed=blocks, dtype=dtype)
    got, whole = with_and_without_short_folds(
        lambda: fa.flash_attention_masked(q, k, v, scale, window=window,
                                          rotary=rotary),
        monkeypatch, want)
    assert got.shape == q.shape and got.dtype == q.dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, whole, rtol=2e-6, atol=3e-7)
        np.testing.assert_allclose(got, _dense(q, k, v, scale, True, window),
                                   rtol=2e-5, atol=2e-6)
    else:
        np.testing.assert_array_equal(got, whole)
        f32 = lambda x: np.asarray(x, np.float32)
        xla = fa.blockwise_attention_xla(
            *(x.astype(jnp.float32)
              for x in (fa._turned_by_xla(q, rotary), k, v)),
            scale, causal=True, window=window)
        np.testing.assert_allclose(f32(got), f32(xla), rtol=3e-2, atol=3e-2)


def test_the_tail_rule_gives_the_published_shapes_their_rows():
    """:func:`_tail_rows` at the cells' lengths and q blocks: every sampled
    sequence is ``k² + 1`` tokens (SmallThinker's ``k² + 1`` and one more), so
    the last block holds one row or two and its folds run on 32; and over
    every length to three blocks, the rows are the block's held rows in whole
    groups of 32, at most half a block, else None."""
    assert fa._TAIL_ROWS == 32
    for tokens, bq in ((9217, 1024), (16385, 1024), (9217, 512), (4097, 256),
                       (16130, 256), (16385, 256)):
        assert fa._tail_rows(tokens, bq) == 32
    for bq in (256, 512, 1024):
        for tokens in range(1, 3 * bq + 1):
            held = (tokens - 1) % bq + 1
            rows = fa._tail_rows(tokens, bq)
            if rows is None:
                assert -(-held // 32) * 32 > bq // 2
            else:
                assert held <= rows < held + 32 and rows % 32 == 0
                assert rows <= bq // 2
    assert fa._tail_rows(40, 40) is None and fa._tail_rows(600, 600) is None


def test_the_fold_rule_gives_the_published_shapes_their_pairs():
    """:func:`_masked_fold` at the three configurations that launch
    ``fwd_masked`` (heads of 128, bfloat16): SmallThinker 28 / 4 at 16,130
    tokens, Laguna 48 | 72 / 8 at 4,097, Nemotron 32 / 2 at 16,385; and over
    every ``rep`` to 64, both lane counts, short and long sequences, the
    heads divide ``rep``, are the most the row budget admits at the q block
    chosen, and more than one head never holds more than the budget."""
    fold = lambda rep, tokens: fa._masked_fold(rep, tokens, 128, jnp.bfloat16)
    assert fold(28 // 4, 16130) == (7, 256)
    assert fold(48 // 8, 4097) == (6, 256)
    assert fold(72 // 8, 4097) == (9, 256)
    assert fold(32 // 2, 16385) == (8, 256)
    assert fa._FOLD_ROWS == 2304
    for lanes in (128, 256):
        rows = fa._FOLD_ROWS * 128 // lanes
        for dtype in (jnp.bfloat16, jnp.float32):
            for tokens in (16385, 4097, 600, 200, 37):
                full = fa._masked_blocks(tokens, dtype)[0]
                for rep in range(1, 65):
                    heads, bq = fa._masked_fold(rep, tokens, lanes, dtype)
                    assert rep % heads == 0
                    if heads == 1:
                        assert bq == full
                        continue
                    assert bq == min(256, full) and heads * bq <= rows
                    assert not any(rep % f == 0 and f * bq <= rows
                                   for f in range(heads + 1, rep + 1))


def test_the_fold_counter_says_how_many_heads_a_program_held():
    """``kernels.flash_fwd_fold``, +1 a trace of ``fwd_masked`` or
    ``fwd_selected``, key = the heads ONE program folds a chunk into: the
    rule's choice for the shape, 1 under a selection; nothing for the
    launches that have no K/V head to share (``fwd``, ``fwd_latent``) or off
    the TPU's path; and its row in the table of ``obs/metrics.py``."""
    assert "kernels.flash_fwd_fold" in {name for name, *_ in metrics.METRICS}
    count = lambda: fa._kernels.by_key("kernels.flash_fwd_fold")
    metrics.reset()
    for rep, tokens in ((1, 40), (7, 40), (7, 600), (16, 600), (6, 40)):
        q, k, v = _qkv(tokens, rep, 1, 128, dtype=jnp.bfloat16)
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention_masked(
            q, k, v, 0.1, window=16))(q, k, v)
    assert count() == {"1": 1, "7": 2, "8": 1, "6": 1}
    q, k, v = _qkv(40, 4, 2, 256)
    fa.flash_attention_selected(q, k, v, 0.1, _selection(1, 40, q.dtype))
    assert count()["1"] == 2
    before = count()
    fa.flash_attention(q, q, q, 0.1)
    fa.masked_attention(q, k, v, 0.1)  # off the TPU: blockwise XLA
    qn = jnp.ones((1, 40, 2, 128))
    fa.flash_attention_latent(qn, jnp.ones((1, 40, 2, 64)), qn,
                              jnp.ones((1, 40, 64)), qn, 0.1)
    assert count() == before
    metrics.reset()


def test_layers_that_launch_the_same_shapes_trace_the_body_once(monkeypatch):
    """The launch is an inline ``jit``: three layers at one shape and one at
    another, in one program, trace ``_fwd_masked_kernel`` twice — a folded
    body is ``heads`` chains of Python to trace, and a warm set-up pays for
    every trace — while the program holds all four launches, each under its
    own caller's scope."""
    traced = []
    body = fa._fwd_masked_kernel

    def counting(*refs, **geometry):
        traced.append(geometry["window"])
        return body(*refs, **geometry)

    monkeypatch.setattr(fa, "_fwd_masked_kernel", counting)
    q, k, v = _qkv(48, 14, 2, 128, seed=9, dtype=jnp.bfloat16)
    scale = 0.0884  # no other test's static arguments: a trace of its own

    def stack(q, k, v):
        for layer, window in enumerate((16, 16, None, 16)):
            with jax.named_scope(f"layer{layer}"):
                q = fa.flash_attention_masked(q, k, v, scale, window=window)
        return q

    jaxpr = jax.make_jaxpr(stack)(q, k, v)
    assert traced == [16, None]
    launches = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [str(e.source_info.name_stack).split("/")[0] for e in launches] == [
        "layer0", "layer1", "layer2", "layer3"]
    want = q
    for window in (16, 16, None, 16):
        want = fa.flash_attention_masked(
            want, *(jnp.repeat(x, 7, axis=2) for x in (k, v)), scale,
            window=window)
    np.testing.assert_array_equal(jax.jit(stack)(q, k, v), want)
