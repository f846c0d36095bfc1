"""ops/short_conv.py: the Pallas launch (interpreter mode here) against the
XLA form, which is held to the written-out sum in float64; with and without a
bias and the head-wise L2 norm, at lengths of one token, less than a strip, one
past a block and one past two, several images a call (an image's first tokens
must not see the image before), causality, the shapes the launch admits and
the blocks it chooses, the counter of which path a trace took, and the
gradient through the launch's ``custom_vjp``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import short_conv as sc

#: two lane groups: two heads of 128 under the norm
D, TAPS = 256, 4


def operands(n, L, dtype, bias, seed=0, d=D, taps=TAPS):
    """``u`` of order one, taps within torch's default ±taps^−½, a bias of
    order 0.1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    u = jax.random.normal(ks[0], (n, L, d)).astype(dtype)
    w = jax.random.uniform(ks[1], (taps, d), minval=-taps ** -0.5,
                           maxval=taps ** -0.5).astype(dtype)
    b = (0.1 * jax.random.normal(ks[2], (d,))).astype(dtype) if bias else None
    return u, w, b


def written_out(u, w, b, l2_head_dim=None, dtype=None):
    """The module docstring's equations in float64, rounded where they
    round."""
    dtype = u.dtype if dtype is None else dtype
    u, w = np.asarray(u, np.float64), np.asarray(w, np.float64)
    n, L, d = u.shape
    taps = w.shape[0]
    past = np.concatenate([np.zeros((n, taps - 1, d)), u], axis=1)
    conv = sum(w[j] * past[:, j:j + L] for j in range(taps))
    if b is not None:
        conv = conv + np.asarray(b, np.float64)
    y = np.asarray(jnp.asarray(conv / (1 + np.exp(-conv)), jnp.float32
                               ).astype(dtype), np.float64)
    if l2_head_dim:
        heads = y.reshape(n, L, -1, l2_head_dim)
        y = (heads / np.sqrt((heads * heads).sum(-1, keepdims=True)
                             + sc.L2_EPS)).reshape(n, L, d)
    return y


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 256 KiB for ``u`` and the result together: 128 rows of 256
    float32 channels, 192 or 256 of bfloat16, so that a thousand tokens cross
    several blocks' edges."""
    monkeypatch.setattr(sc, "_BLOCK_BYTES", 256 << 10)


def blocks_of(u, dtype=None):
    """``(T, C)`` the launch cuts ``u`` into."""
    C = sc._channel_block(u.shape[2])
    out = u.dtype if dtype is None else jnp.dtype(dtype)
    return sc._token_block(u.shape[1], C * (u.dtype.itemsize + out.itemsize)), C


def run_kernel(u, w, b, **kw):
    return sc.causal_conv_kernel(u, w, b, interpret=True, **kw)


def ulps(got, want, dtype):
    """|got − want| in units of ``dtype``'s last place at ``want``."""
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    eps = float(jnp.finfo(dtype).eps)
    return np.abs(got - want) / (np.maximum(np.abs(want), 2.0 ** -100) * eps)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("l2_head_dim", [None, 128], ids=["plain", "l2"])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("L", [1, 9, 513, 1025])
def test_the_launch_and_the_xla_form_agree(L, bias, l2_head_dim, dtype,
                                            small_blocks):
    """Two images of 256 channels: one token, less than a strip, and 513 and
    1,025 tokens in three to nine blocks whose last holds one to a few rows,
    each block's first rows reading what the block before left in the
    scratch, and image 2 starting from zeros again. float32: to 1e-6;
    bfloat16: within one unit of the last place."""
    u, w, b = operands(2, L, dtype, bias, seed=L)
    assert (blocks_of(u)[0] < L) == (L > 500)
    want = sc.causal_conv_xla(u, w, b, l2_head_dim=l2_head_dim)
    got = run_kernel(u, w, b, l2_head_dim=l2_head_dim)
    assert got.shape == want.shape == u.shape
    assert got.dtype == want.dtype == dtype
    exact = written_out(u, w, b, l2_head_dim)
    assert np.abs(exact).mean() > 0.02  # the comparison has a signal
    as_f32 = lambda a: np.asarray(a, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(as_f32(got), as_f32(want), rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(as_f32(want), exact, rtol=1e-5, atol=1e-6)
    else:
        assert ulps(got, want, dtype).max() <= 1.0
        # against float64 a rounding may fall the other way, here and again
        # under the norm
        assert ulps(want, exact, dtype).max() <= 2.0 + 2.0 * bool(l2_head_dim)


def test_an_images_first_tokens_see_nothing_of_the_image_before(small_blocks):
    """Image 2 of a call equals the same image sent alone, bitwise, across a
    block's edge; so does image 1 whatever image 2 holds."""
    u, w, b = operands(2, 200, jnp.float32, True, seed=4)
    assert blocks_of(u)[0] < 200  # two blocks an image
    both = np.asarray(run_kernel(u, w, b))
    for i in range(2):
        alone = np.asarray(run_kernel(u[i:i + 1], w, b))
        np.testing.assert_array_equal(both[i:i + 1], alone)
    # and the first taps − 1 rows are the sum's first terms alone
    first = np.asarray(u[1, 0]) * np.asarray(w[TAPS - 1]) + np.asarray(b)
    np.testing.assert_allclose(both[1, 0], first / (1 + np.exp(-first)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_convolution_is_causal(path, small_blocks):
    """Tokens after t do not move the output at t, across a block's edge."""
    u, w, b = operands(1, 200, jnp.float32, False, seed=2)
    run = sc.causal_conv_xla if path == "xla" else run_kernel
    base = np.asarray(run(u, w, b, l2_head_dim=128))
    t = blocks_of(u)[0] - 2  # the three tokens after it straddle the edge
    moved = np.asarray(run(u.at[:, t + 1:].add(1.0), w, b, l2_head_dim=128))
    np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    assert np.abs(moved[:, t + 1:t + TAPS] - base[:, t + 1:t + TAPS]).min() > 0


def test_the_result_takes_the_dtype_it_is_asked_for():
    """A float32 ``u`` into a bfloat16 module: float32 sums, one rounding."""
    u, w, b = operands(1, 40, jnp.float32, True, seed=5)
    want = sc.causal_conv_xla(u, w, b, dtype=jnp.bfloat16)
    got = run_kernel(u, w, b, dtype=jnp.bfloat16)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert ulps(got, want, jnp.bfloat16).max() <= 1.0
    np.testing.assert_array_equal(
        np.asarray(want, np.float32),
        np.asarray(sc.causal_conv_xla(u, w, b).astype(jnp.bfloat16),
                   np.float32))


def test_kernel_admits_whole_lane_groups_and_heads_inside_a_channel_block():
    assert sc.kernel_admits(4096, 4, 128)    # Kimi's q and k
    assert sc.kernel_admits(4096, 4)         # its v
    assert sc.kernel_admits(10240, 4)        # Nemotron's xBC
    assert sc.kernel_admits(5120, 4)         # Jamba's u
    assert not sc.kernel_admits(96, 4)       # part of a lane group
    assert not sc.kernel_admits(256, 4, 64)  # two heads a lane group
    assert not sc.kernel_admits(384, 4, 256)  # a head across channel blocks
    assert not sc.kernel_admits(256, sc._CARRY + 2)  # taps past the carry
    assert sc.kernel_admits(256, 1)


def test_a_shape_the_rule_refuses_takes_the_xla_form_and_says_so():
    """6 channels: the toy mixers' width. The launch refuses it by name, the
    dispatcher runs the XLA form and counts it as such."""
    u, w, b = operands(2, 9, jnp.float32, True, d=6)
    with pytest.raises(NotImplementedError, match="whole lane groups"):
        run_kernel(u, w, b)
    with pytest.raises(ValueError, match="heads that divide d"):
        sc.causal_conv_xla(u, w, b, l2_head_dim=4)
    with pytest.raises(ValueError, match=r"w \(taps, d\)"):
        sc.causal_conv(u, w[:, :5], b)
    metrics.reset()
    got = sc.causal_conv(u, w, b, l2_head_dim=3)
    np.testing.assert_allclose(got, written_out(u, w, b, 3), rtol=1e-5,
                               atol=1e-6)
    assert _by_key() == {"xla": 1}
    metrics.reset()


@pytest.mark.parametrize("shape,dtype,blocks", [
    ((1, 16385, 4096), jnp.bfloat16, (864, 1024)),    # Kimi's q, k, v
    ((1, 16385, 10240), jnp.bfloat16, (864, 1024)),   # Nemotron's xBC
    ((4, 1025, 5120), jnp.bfloat16, (544, 1024)),     # Jamba's u
    ((1, 16385, 4096), jnp.float32, (448, 1024)),     # the same in float32
    ((2, 9, 256), jnp.float32, (32, 256)),
    ((1, 16385, 384), jnp.bfloat16, (1824, 384))])
def test_blocks_are_chosen_from_the_shape(shape, dtype, blocks):
    """Whole strips and lane groups within ``_BLOCK_BYTES``, and the last
    block more than half full: 16,385 tokens are 19 blocks of 864 (31 rows
    past the end), not 33 of 512 (511 past it)."""
    T, C = blocks_of(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)))
    assert (T, C) == blocks
    assert T % sc.STRIP == 0 and C % 128 == 0 and shape[2] % C == 0
    assert 2 * T * C * jnp.dtype(dtype).itemsize <= sc._BLOCK_BYTES
    assert -(-shape[1] // T) * T - shape[1] < max(T // 2, sc.STRIP)


def _by_key():
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.causal_conv_schedule/by_key", {}))
    return by_key


@pytest.mark.parametrize("backend,key", [("cpu", "xla"), ("tpu", "kernel")])
def test_counter_says_which_path_a_trace_took(backend, key, monkeypatch):
    """Once a trace: ``xla`` off the TPU; ``kernel`` where the backend is a
    TPU and the rule admits the shape (the backend steered by the test, the
    launch interpreted)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(sc, "kernel_interpret", lambda: True)
    u, w, b = operands(1, 8, jnp.float32, False)
    metrics.reset()
    f = jax.jit(lambda u, w: sc.causal_conv(u, w, l2_head_dim=128))
    got = f(u, w)
    f(u, w)  # the second call traces nothing
    assert _by_key() == {key: 1}
    np.testing.assert_allclose(got, written_out(u, w, None, 128), rtol=1e-5,
                               atol=1e-6)
    metrics.reset()


@pytest.mark.parametrize("l2_head_dim", [None, 128], ids=["plain", "l2"])
def test_the_gradient_through_the_launch_is_the_xla_forms(l2_head_dim,
                                                          monkeypatch):
    """``jax.grad`` through the dispatcher on its kernel branch (the launch's
    ``custom_vjp``) against the XLA form's own gradient: u, w and b."""
    u, w, b = operands(2, 40, jnp.float32, True, seed=7)
    weight = jax.random.normal(jax.random.PRNGKey(8), u.shape)
    loss = lambda f: lambda u, w, b: (
        f(u, w, b, l2_head_dim=l2_head_dim) * weight).sum()
    want = jax.grad(loss(sc.causal_conv_xla), argnums=(0, 1, 2))(u, w, b)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sc, "kernel_interpret", lambda: True)
    metrics.reset()
    got = jax.grad(loss(sc.causal_conv), argnums=(0, 1, 2))(u, w, b)
    assert _by_key() == {"kernel": 1}
    for g, x in zip(got, want):
        assert np.abs(np.asarray(x)).max() > 1e-3
        np.testing.assert_allclose(g, x, rtol=1e-6, atol=1e-6)
    no_bias = jax.grad(lambda u: sc.causal_conv(u, w).sum())(u)
    np.testing.assert_allclose(
        no_bias, jax.grad(lambda u: sc.causal_conv_xla(u, w).sum())(u),
        rtol=1e-6, atol=1e-6)
    metrics.reset()
