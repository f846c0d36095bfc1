"""What the serving engine promises about a request's rows, written once.

A request of ``n`` rows has its start state drawn at its own ``n``
(``Engine._request_init``), is packed with its batchmates into a bucket of
``b`` rows, padded (``Engine._assemble``: zero rows; row-0 replicas under a
batch-coupled config) and run by the sampler's own scan compiled at batch
``b``. Two properties follow, and every engine-against-direct test states
both (``HOWS``):

* ``same_bucket`` — each served row is BITWISE the row of the direct sampler
  call at batch ``b`` on the same start rows padded the same way, for one of
  the buckets that may have served it. Rows are computed independently:
  where in the batch they sit and what the other rows hold changes nothing.
* ``own_n`` — against the direct call at the request's own ``n`` the rows
  agree to ``OWN_N_ATOL``. XLA owes nobody equal bits across batch sizes:
  float32 on the CPU differs by one unit in the last place of a [0, 1] image
  (5.96e-08, in 106 of 768 values of an n = 1 request served from buckets 4
  and 8); bfloat16 on the chip by 4.4e-3 (PERF.md section 6, PR 21).
"""

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.ops import sampling

HOWS = ("same_bucket", "own_n")

#: float32 on the CPU, images in [0, 1]: four units in the last place below
#: 1.0. The eight engine-against-direct tests show 0 or one unit (5.96e-08)
#: here; two XLA programs of the same arithmetic show two (tests/test_fusion.py)
OWN_N_ATOL = 2.4e-7


def pad_rows(x, rows: int, coupled: bool = False) -> np.ndarray:
    """``x`` padded to ``rows`` rows the way ``Engine._assemble`` pads."""
    x = np.asarray(x, np.float32)
    shape = (rows - x.shape[0],) + x.shape[1:]
    pad = np.broadcast_to(x[:1], shape) if coupled else np.zeros(shape, x.dtype)
    return np.concatenate([x, pad]) if shape[0] else x


def assert_served(how: str, served, direct, starts, buckets,
                  coupled: bool = False) -> None:
    """``served`` — the ticket's rows — against ``direct(*starts)``: the
    direct sampler call on arrays whose first axis is the batch (the start
    state, then whatever else the task takes a row: inpaint's known image
    and mask). ``buckets``: the batch sizes that may have served the rows."""
    served = np.asarray(served)
    starts = [np.asarray(s, np.float32) for s in starts]  # scans may donate
    n = served.shape[0]
    if how == "own_n":
        np.testing.assert_allclose(served, np.asarray(direct(*starts)),
                                   rtol=0, atol=OWN_N_ATOL)
        return
    assert how == "same_bucket", how
    matched = np.zeros(n, bool)
    for b in buckets:
        ref = np.concatenate([
            np.asarray(direct(*(pad_rows(s[lo:lo + b], b, coupled)
                                for s in starts)))[:n - lo]
            for lo in range(0, n, b)])
        matched |= (served == ref).reshape(n, -1).all(axis=1)
    assert matched.all(), (
        f"rows {np.flatnonzero(~matched).tolist()} of {n} are not bitwise "
        f"the direct call's at any batch size of {tuple(buckets)}")


def assert_sample_served(how: str, served, model, params, seed: int, k: int,
                         buckets, **sampler_kwargs) -> None:
    """:func:`assert_served` for a plain ``submit(seed=, n=)`` request:
    ``ddim_sample`` at stride ``k`` (and ``sampler_kwargs``: the step cache)
    from the noise the seed draws at the request's own n."""
    served = np.asarray(served)
    x = jax.random.normal(jax.random.PRNGKey(seed), served.shape, jnp.float32)
    assert_served(
        how, served,
        lambda x: sampling.ddim_sample(model, params, x_init=jnp.asarray(x),
                                       k=k, **sampler_kwargs),
        (x,), buckets)
