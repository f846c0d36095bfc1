"""ops/selective_scan.py: the Pallas kernel (interpreter mode here) against
the ``lax.scan`` oracle, the blocks it picks from the shape, causality, the
counter of which path a trace took, and what it says when asked for a
gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import selective_scan as ss


def operands(n, L, d, s, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (n, L, d)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (n, L, d)) - 2).astype(dtype)
    z = jax.random.normal(ks[2], (n, L, d)).astype(dtype)
    A = -jnp.exp(jnp.log(jnp.arange(1, s + 1, dtype=jnp.float32))
                 + 0.1 * jax.random.normal(ks[3], (d, s)))
    B = jax.random.normal(ks[4], (n, L, s)).astype(dtype)
    C = jax.random.normal(ks[5], (n, L, s)).astype(dtype)
    D = 1.0 + 0.1 * jnp.arange(d, dtype=jnp.float32) / d
    return u, delta, A, B, C, D, z


@pytest.mark.parametrize("n,L,d,s,dtype,blocks", [
    (2, 32, 128, 16, jnp.float32, (128, 32)),    # one chunk, tokens = chunk
    (2, 17, 128, 16, jnp.float32, None),         # tokens not a multiple
    (2, 17, 128, 16, jnp.float32, (128, 16)),    # the state crosses a chunk
    (1, 70, 256, 16, jnp.bfloat16, (128, 32)),   # three chunks, two blocks
    (2, 40, 256, 8, jnp.float32, (256, 16)),     # eight states
    (1, 33, 256, 16, jnp.bfloat16, None),        # blocks from the shape
])
def test_kernel_matches_the_scan(n, L, d, s, dtype, blocks):
    args = operands(n, L, d, s, dtype)
    want = ss.selective_scan_xla(*args)
    got = ss.selective_scan_kernel(*args, blocks=blocks, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_scan_follows_its_equations():
    """The oracle itself against the recurrence written out in numpy."""
    u, delta, A, B, C, D, z = (np.asarray(a, np.float64)
                               for a in operands(1, 9, 4, 3, jnp.float32))
    h = np.zeros((4, 3))
    want = np.zeros((9, 4))
    for t in range(9):
        h = np.exp(delta[0, t][:, None] * A) * h \
            + (delta[0, t] * u[0, t])[:, None] * B[0, t][None, :]
        y = h @ C[0, t] + D * u[0, t]
        want[t] = y * z[0, t] / (1 + np.exp(-z[0, t]))
    got = ss.selective_scan_xla(*operands(1, 9, 4, 3, jnp.float32))
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_scan_is_causal(path):
    """Tokens after t do not move the output at t."""
    args = list(operands(1, 24, 128, 16, jnp.float32))
    run = (ss.selective_scan_xla if path == "xla" else
           lambda *a: ss.selective_scan_kernel(*a, blocks=(128, 16),
                                               interpret=True))
    base = np.asarray(run(*args))
    t = 10
    for i in (0, 1, 3, 4, 6):  # u, delta, B, C, z
        args[i] = args[i].at[:, t + 1:].add(1.0)
    moved = np.asarray(run(*args))
    np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    assert np.abs(moved[:, t + 1:] - base[:, t + 1:]).max() > 1e-3


def test_blocks_come_from_the_shape():
    bd, chunk = ss._scan_blocks(1025, 5120, 16, jnp.bfloat16)
    assert 5120 % bd == 0 and bd % 128 == 0 and chunk % 16 == 0
    assert bd * 16 * 4 <= ss._STATE_BYTES
    chunks = -(-1025 // chunk)
    assert chunks * chunk - 1025 < 16 * chunks  # pads under a group a chunk
    # a narrow model keeps one lane tile; a short sequence one chunk
    assert ss._scan_blocks(17, 128, 16, jnp.float32) == (128, 32)
    # double-buffered blocks stay inside a quarter of the scoped VMEM
    for L, d, dtype in ((1025, 5120, jnp.bfloat16), (4096, 8192, jnp.float32)):
        bd, chunk = ss._scan_blocks(L, d, 16, dtype)
        assert 8 * chunk * bd * jnp.dtype(dtype).itemsize <= ss._SCOPED_VMEM_BYTES // 4


def test_kernel_admits_whole_tiles_only():
    assert ss.kernel_admits(5120, 16) and ss.kernel_admits(128, 8)
    assert not ss.kernel_admits(96, 16) and not ss.kernel_admits(128, 4)


def test_counter_says_which_path_a_trace_took():
    metrics.reset()
    jax.jit(ss.selective_scan)(*operands(1, 8, 128, 16, jnp.float32))
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.ssm_scan_schedule/by_key", {}))
    assert by_key == {"xla": 1}  # off the TPU the plain scan runs
    metrics.reset()


def test_scan_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    args = operands(1, 12, 128, 16, jnp.float32)
    grads = jax.grad(lambda u, dt: ss.selective_scan(
        u, dt, *args[2:]).sum(), argnums=(0, 1))(args[0], args[1])
    assert all(np.isfinite(np.asarray(g)).all() and np.abs(g).max() > 0
               for g in grads)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda u: ss._kernel_no_vjp(u, *args[1:]).sum())(args[0])
