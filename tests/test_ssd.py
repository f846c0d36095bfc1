"""ops/ssd.py: the chunked XLA form against the token-by-token recurrence,
the Pallas launch (interpreter mode here) against both, at lengths that end
on, one past and far inside a chunk; causality, the shapes the launch admits,
the counter of which path a trace took, and what it says when asked for a
gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import ssd

#: two groups of two heads of 64 channels (one lane group a group), 128
#: states, chunks of 128: the smallest shapes the launch tiles
H, P, N, G, Q = 4, 64, 128, 2, 128


def operands(n, L, dtype, seed=0, heads=H, head_dim=P, states=N, groups=G):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, L, heads * head_dim)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n, L, heads)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (heads,)))
    B = (jax.random.normal(ks[3], (n, L, groups * states))
         / np.sqrt(states)).astype(dtype)
    C = jax.random.normal(ks[4], (n, L, groups * states)).astype(dtype)
    D = 1.0 + 0.1 * jnp.arange(heads, dtype=jnp.float32)
    return x, dt, A, B, C, D


def recurrence(x, dt, A, B, C, D, groups):
    """The module docstring's equations, token by token, in float64."""
    x, dt, A, B, C, D = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, D))
    n, L, heads = dt.shape
    head_dim, states = x.shape[-1] // heads, B.shape[-1] // groups
    x = x.reshape(n, L, heads, head_dim)
    B, C = (np.repeat(a.reshape(n, L, groups, states), heads // groups, 2)
            for a in (B, C))
    S = np.zeros((n, heads, head_dim, states))
    y = np.zeros_like(x)
    for t in range(L):
        S = (np.exp(dt[:, t] * A)[..., None, None] * S
             + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, :, None, :])
        y[:, t] = np.einsum("bhpn,bhn->bhp", S, C[:, t]) + D[:, None] * x[:, t]
    return y.reshape(n, L, heads * head_dim)


#: bfloat16: products of operands rounded to 8 bits of mantissa over sums of
#: up to 128 + 128 terms of order one, against float64
TOLERANCE = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
             jnp.bfloat16: dict(rtol=3e-2, atol=6e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L", [1, Q, Q + 1, 2 * Q + 1])
def test_chunked_form_kernel_and_recurrence_agree(L, dtype):
    """Two rows, two groups; 129 and 257 end one token into a chunk."""
    args = operands(2, L, dtype, seed=L)
    want = recurrence(*args, G)
    xla = ssd.ssd_scan_xla(*args, groups=G, chunk=Q)
    got = ssd.ssd_scan_kernel(*args, groups=G, chunk=Q, interpret=True)
    assert xla.shape == got.shape == want.shape
    assert xla.dtype == got.dtype == dtype
    as_f32 = lambda a: np.asarray(a, np.float32)
    assert np.abs(want).mean() > 0.3  # the comparison has a signal
    np.testing.assert_allclose(as_f32(xla), want, **TOLERANCE[dtype])
    np.testing.assert_allclose(as_f32(got), want, **TOLERANCE[dtype])
    np.testing.assert_allclose(as_f32(got), as_f32(xla), **TOLERANCE[dtype])


def test_a_head_that_fills_its_lanes_and_four_side_by_side():
    """Heads of 128 (alone on a lane group) and of 32 (four on one)."""
    for heads, head_dim in ((2, 128), (8, 32)):
        args = operands(1, Q + 3, jnp.float32, seed=5, heads=heads,
                        head_dim=head_dim)
        want = recurrence(*args, G)
        got = ssd.ssd_scan_kernel(*args, groups=G, chunk=Q, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_the_xla_form_takes_any_shape_and_chunk():
    """3 heads of 5 channels, 7 states, one group, chunks of 4: nothing the
    launch tiles, everything the equations allow."""
    args = operands(2, 11, jnp.float32, seed=9, heads=3, head_dim=5, states=7,
                    groups=1)
    got = ssd.ssd_scan_xla(*args, groups=1, chunk=4)
    np.testing.assert_allclose(np.asarray(got), recurrence(*args, 1),
                               rtol=2e-5, atol=2e-5)
    assert not ssd.kernel_admits(3, 5, 7, 4)
    with pytest.raises(NotImplementedError, match="whole lane groups"):
        ssd.ssd_scan_kernel(*args, groups=1, chunk=4, interpret=True)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_scan_is_causal(path):
    """Tokens after t do not move the output at t, across a chunk's edge."""
    args = list(operands(1, Q + 40, jnp.float32, seed=2))
    run = (ssd.ssd_scan_xla if path == "xla" else
           lambda *a, **kw: ssd.ssd_scan_kernel(*a, interpret=True, **kw))
    base = np.asarray(run(*args, groups=G, chunk=Q))
    t = Q + 5
    for i in (0, 1, 3, 4):  # x, dt, B, C
        args[i] = args[i].at[:, t + 1:].add(1.0)
    moved = np.asarray(run(*args, groups=G, chunk=Q))
    np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    assert np.abs(moved[:, t + 1:] - base[:, t + 1:]).max() > 1e-3


def test_kernel_admits_whole_lane_groups_only():
    # the published mixer: 16 heads of 64 a group, 128 states, chunks of 128
    assert ssd.kernel_admits(16, 64, 128, 128)
    assert ssd.kernel_admits(2, 64, 128, 128) and ssd.kernel_admits(1, 128, 256, 256)
    assert not ssd.kernel_admits(1, 64, 128, 128)    # half a lane group
    assert not ssd.kernel_admits(2, 64, 64, 128)     # states
    assert not ssd.kernel_admits(2, 64, 128, 64)     # chunk
    assert not ssd.kernel_admits(1, 256, 128, 128)   # a head wider than the lanes
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_scan_xla(*operands(1, 4, jnp.float32), groups=3, chunk=4)


def test_counter_says_which_path_a_trace_took():
    metrics.reset()
    jax.jit(lambda *a: ssd.ssd_scan(*a, groups=G, chunk=Q))(
        *operands(1, 8, jnp.float32))
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.ssd_schedule/by_key", {}))
    assert by_key == {"xla": 1}  # off the TPU the XLA form runs
    metrics.reset()


def test_scan_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    args = operands(1, 12, jnp.float32)
    grads = jax.grad(lambda x, dt: ssd.ssd_scan(
        x, dt, *args[2:], groups=G, chunk=Q).sum(), argnums=(0, 1))(*args[:2])
    assert all(np.isfinite(np.asarray(g)).all() and np.abs(g).max() > 0
               for g in grads)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda x: ssd._kernel_no_vjp(x, *args[1:], G, Q).sum())(args[0])
