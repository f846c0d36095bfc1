"""ops/kda.py: the chunked XLA form against the token-by-token delta rule, the
Pallas launch (interpreter mode here) against both, at lengths that end on,
one past and far inside a chunk, with the step size at 0 and near 1 and with
a decay of 20 a token and channel, where an exponent split across a product
overflows, and with keys that repeat inside a sub-block of 8, where the
diagonal blocks the launch inverts by elimination are far from the identity;
how many products one head's chunk hands the MXU; causality, the shapes the
launch admits, the counter of which path a trace took, and what it says when
asked for a gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import kda
from tests.test_flash_attention import _iter_eqns

#: two heads of 128 channels, one program of the launch, over chunks of 64:
#: half the chunk the program ships (its own, 128, has a case below)
H, D, C = 2, 128, 64
SCALE = 1.0  # the launch takes any; 1 keeps the outputs at order one


def operands(n, L, dtype, seed=0, heads=H, head_dim=D, g=None, beta=None,
             repeats=False):
    """q and k normed a head (k with a mean, as after a SiLU), v of order
    one, a decay of 0.03 to 3 a token by the channel, β spread over (0, 1);
    ``g`` and ``beta`` put one number everywhere instead; with ``repeats``
    every token of a sub-block of 8 has the sub-block's first key."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    wide = (n, L, heads * head_dim)
    q = l2(jax.random.normal(ks[0], (n, L, heads, head_dim))).reshape(wide)
    k = l2(0.5 + jax.random.normal(ks[1], (n, L, heads, head_dim))
           ).reshape(wide)
    if repeats:
        k = k[:, np.arange(L) - np.arange(L) % kda.SUB]
    v = 4.0 * jax.random.normal(ks[2], wide)
    if g is None:
        decay = -jnp.exp(jax.random.uniform(
            ks[3], (heads * head_dim,), minval=np.log(0.03), maxval=np.log(3.0))
        ) * jax.nn.softplus(jax.random.normal(ks[3], wide))
    else:
        decay = jnp.full(wide, g, jnp.float32)
    step = (jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (n, L, heads)))
            if beta is None else jnp.full((n, L, heads), beta, jnp.float32))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), decay, step)


def recurrence(q, k, v, g, beta, scale, state_dtype=None):
    """The module docstring's equations, token by token, in float64; with
    ``state_dtype``, the state rounded to it after every token."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    n, L, heads = beta.shape
    d = q.shape[-1] // heads
    q, k, v, g = (a.reshape(n, L, heads, d) for a in (q, k, v, g))
    S = np.zeros((n, heads, d, d))
    o = np.zeros_like(v)
    for t in range(L):
        S = np.exp(g[:, t])[..., None] * S
        miss = v[:, t] - np.einsum("bhcv,bhc->bhv", S, k[:, t])
        S = S + (beta[:, t, :, None] * k[:, t])[..., None] * miss[:, :, None, :]
        if state_dtype is not None:
            S = S.astype(state_dtype).astype(np.float64)
        o[:, t] = np.einsum("bhcv,bhc->bhv", S, q[:, t]) * scale
    return o.reshape(n, L, heads * d)


#: float32: sums of 128 + 64 products of order one in float32 against
#: float64, through a 64 x 64 inverse; bfloat16: the same products on
#: operands of 8 bits of mantissa (outputs are of order 0.3)
TOLERANCE = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
             jnp.bfloat16: dict(rtol=3e-2, atol=2e-2)}

CASES = {"beta_0": dict(beta=0.0), "beta_near_1": dict(beta=0.999),
         "g_minus_20": dict(g=-20.0)}
#: β near 1 and one key a sub-block: ``Diag(β) A`` reads 0.76 beside the
#: diagonal of its 8 x 8 blocks and 0.38 in their corners (the operands'
#: own decay is all that lowers it). At the chunks at which the launch
#: doubles once, three times and (its own) four times above those blocks
CASES.update({f"repeats_chunk_{c}": dict(beta=0.999, repeats=True, chunk=c)
              for c in (16, 64, 128)})


def run_kernel(*args, chunk=C):
    return kda.kda_scan_kernel(*args, chunk=chunk, interpret=True)


def run_xla(*args, chunk=C):
    return kda.kda_scan_xla(*args, chunk=chunk)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L", [1, C, C + 1, 2 * C + 1])
def test_chunked_form_kernel_and_recurrence_agree(L, dtype):
    """Two rows, two heads; 65 and 129 end one token into a chunk."""
    args = operands(2, L, dtype, seed=L)
    want = recurrence(*args, SCALE)
    xla = run_xla(*args, SCALE)
    got = run_kernel(*args, SCALE)
    assert xla.shape == got.shape == want.shape
    assert xla.dtype == got.dtype == dtype
    as_f32 = lambda a: np.asarray(a, np.float32)
    assert np.abs(want).mean() > 0.05  # the comparison has a signal
    np.testing.assert_allclose(as_f32(xla), want, **TOLERANCE[dtype])
    np.testing.assert_allclose(as_f32(got), want, **TOLERANCE[dtype])
    np.testing.assert_allclose(as_f32(got), as_f32(xla), **TOLERANCE[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_both_forms_hold_at_the_ends_of_the_step_and_of_the_decay(case, dtype):
    """β = 0 writes nothing (the output is zero); β near 1 replaces what the
    state says about a key; g = −20 a token and channel loses e^{−1280} over
    a chunk of 64, and ``exp(γ_r) · exp(−γ_i)`` would be 0 · inf: both forms stay
    finite and equal the recurrence, which is then all but the token's own
    ``β (q·k) v``. A key that repeats through its sub-block is written once
    and then found there: the seven later tokens correct almost nothing, which
    the chunked forms only get from an inverse that is right in every entry of
    its diagonal blocks (the sequence ends inside the second chunk's second
    sub-block)."""
    case = dict(CASES[case])
    chunk = case.pop("chunk", C)
    args = operands(2 if chunk == C else 1, chunk + kda.SUB + 1, dtype, seed=3,
                    **case)
    want = recurrence(*args, SCALE)
    for got in (run_xla(*args, SCALE, chunk=chunk),
                run_kernel(*args, SCALE, chunk=chunk)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, **TOLERANCE[dtype])
    if case.get("beta") == 0.0:
        assert not want.any()
    else:
        assert np.abs(want).mean() > 0.02


@pytest.mark.parametrize("chunk,dtype,products", [
    (128, jnp.bfloat16, 3 + 15 + 24 + 5), (64, jnp.bfloat16, 3 + 7 + 18 + 5),
    (16, jnp.bfloat16, 3 + 1 + 6 + 5), (128, jnp.float32, 3 + 15 + 8 + 5)],
    ids=["128_bfloat16", "64_bfloat16", "16_bfloat16", "128_float32"])
def test_one_head_launches_no_product_on_the_sub_blocks(chunk, dtype, products):
    """What one head's chunk hands the MXU, counted in its jaxpr: 3 for γ,
    one for each sub-block after the first, two a doubling level FROM 8 (in
    three bfloat16 passes each; float32 operands in one) and 5 at the end.
    PR 45's launch doubled from 2 (59 at 128 in bfloat16): a level put back
    under the sub-blocks fails here and not only in a benchmark."""
    rows = jax.ShapeDtypeStruct((chunk, D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: kda._one_head(*a, dtype=dtype))(
        rows, rows, rows, rows, jax.ShapeDtypeStruct((chunk, 1), jnp.float32),
        jax.ShapeDtypeStruct((D, D), jnp.float32))
    assert sum(eqn.primitive.name == "dot_general"
               for eqn in _iter_eqns(jaxpr.jaxpr)) == products


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_shipped_chunk_ends_one_token_into_its_second(dtype):
    """``kda.CHUNK`` + 1 tokens through both forms at their own defaults."""
    assert kda.CHUNK == 128
    args = operands(1, kda.CHUNK + 1, dtype, seed=6)
    want = recurrence(*args, SCALE)
    for got in (kda.kda_scan_xla(*args, SCALE),
                kda.kda_scan_kernel(*args, SCALE, interpret=True)):
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   **TOLERANCE[dtype])


def test_a_clamped_decay_or_a_bfloat16_state_would_not_pass():
    """The float32 tolerance is tight enough to tell: g clamped at −5 a
    token, or the state rounded to bfloat16 between tokens, moves the
    recurrence by far more than the two forms differ from it."""
    args = operands(1, C + 1, jnp.float32, seed=4, g=-8.0)
    want = recurrence(*args, SCALE)
    got = np.asarray(run_kernel(*args, SCALE))
    clamped = recurrence(*args[:3], np.maximum(np.asarray(args[3]), -5.0),
                         args[4], SCALE)
    ours, theirs = np.abs(got - want).max(), np.abs(clamped - want).max()
    assert ours < 2e-5 < 1e-3 < theirs
    args = operands(1, C + 1, jnp.float32, seed=5)
    want = recurrence(*args, SCALE)
    rounded = recurrence(*args, SCALE, state_dtype=jnp.bfloat16)
    got = np.asarray(run_kernel(*args, SCALE))
    assert np.abs(got - want).max() < 1e-4 < np.abs(rounded - want).max()


def test_the_xla_form_takes_any_shape_and_chunk():
    """3 heads of 5 channels over chunks of 4: nothing the launch tiles,
    everything the equations allow."""
    args = operands(2, 11, jnp.float32, seed=9, heads=3, head_dim=5)
    got = kda.kda_scan_xla(*args, 0.7, chunk=4)
    np.testing.assert_allclose(np.asarray(got), recurrence(*args, 0.7),
                               rtol=2e-5, atol=2e-5)
    assert not kda.kernel_admits(3, 5, 4)
    with pytest.raises(NotImplementedError, match="heads of 128 channels"):
        run_kernel(*args, 0.7, chunk=4)
    with pytest.raises(ValueError, match="one decay a key channel"):
        kda.kda_scan_xla(*args[:3], args[3][..., :5], args[4], 0.7)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_scan_is_causal(path):
    """Tokens after t do not move the output at t, across a chunk's edge."""
    args = list(operands(1, C + 20, jnp.float32, seed=2))
    run = run_xla if path == "xla" else run_kernel
    base = np.asarray(run(*args, SCALE))
    t = C + 5
    for i in range(3):  # q, k, v
        args[i] = args[i].at[:, t + 1:].add(1.0)
    args[3] = args[3].at[:, t + 1:].add(-1.0)
    moved = np.asarray(run(*args, SCALE))
    np.testing.assert_array_equal(moved[:, :t + 1], base[:, :t + 1])
    assert np.abs(moved[:, t + 1:] - base[:, t + 1:]).max() > 1e-3


def test_kernel_admits_heads_of_one_lane_group_in_whole_programs():
    assert kda.kernel_admits(32, 128)                # the published mixer
    assert kda.kernel_admits(2, 128, 128) and kda.kernel_admits(2, 128, 16)
    assert not kda.kernel_admits(32, 64)             # half a lane group
    assert not kda.kernel_admits(3, 128)             # half a program
    assert not kda.kernel_admits(2, 128, 8)          # half a bfloat16 tile
    assert not kda.kernel_admits(2, 128, 96)         # no power of two


def test_counter_says_which_path_a_trace_took():
    metrics.reset()
    jax.jit(lambda *a: kda.kda_scan(*a, SCALE))(*operands(1, 8, jnp.float32))
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.kda_schedule/by_key", {}))
    assert by_key == {"xla": 1}  # off the TPU the XLA form runs
    metrics.reset()


def test_scan_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    args = operands(1, 12, jnp.float32)
    grads = jax.grad(lambda k, g: kda.kda_scan(
        args[0], k, args[2], g, args[4], SCALE).sum(), argnums=(0, 1))(
        args[1], args[3])
    assert all(np.isfinite(np.asarray(g)).all() and np.abs(g).max() > 0
               for g in grads)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: kda._kernel_no_vjp(q, *args[1:], SCALE).sum())(
            args[0])
