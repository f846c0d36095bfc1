"""ops/grouped_matmul.py: the ``moe_gmm`` kernel in interpreter mode against
``jax.lax.ragged_dot`` — empty, one-row and tile-straddling groups, rows past
the last group — its work list, its tiles, its counter and its refusal to
differentiate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.ops import grouped_matmul as gm


def _operands(M, sizes, K=32, N=64, dtype=jnp.float32, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (M, K), dtype),
            jax.random.normal(ks[1], (len(sizes), K, N), dtype),
            jnp.array(sizes, jnp.int32))


@pytest.mark.parametrize("sizes,M,tiles", [
    ([5, 0, 1, 20, 7], 40, None),            # empty, one-row, rows left over
    ([0, 0, 0], 16, None),                   # nothing routed here at all
    ([300, 0, 1, 127, 128, 3], 600, None),   # groups across tiles of 128
    ([16, 16], 32, (16, 64)),                # groups that end on tile edges
    ([3, 9, 30], 64, (8, 64)),               # a tile three groups share
    ([0, 130], 256, None),                   # a leading empty group
])
def test_kernel_matches_ragged_dot(sizes, M, tiles):
    rows, w, group_sizes = _operands(M, sizes)
    want = gm.grouped_matmul_xla(rows, w, group_sizes)
    got = gm.grouped_matmul_kernel(rows, w, group_sizes, tiles=tiles)
    assert got.shape == want.shape == (M, 64)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # rows past the last group: zeros, as ragged_dot leaves them
    assert not np.asarray(got[sum(sizes):]).any()


def test_kernel_in_bfloat16_accumulates_in_float32():
    rows, w, group_sizes = _operands(256, [100, 28, 60], K=256, N=128,
                                     dtype=jnp.bfloat16)
    got = gm.grouped_matmul_kernel(rows, w, group_sizes)
    want = gm.grouped_matmul_xla(rows, w, group_sizes)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=2e-2, atol=2e-2)


def test_work_list_visits_a_shared_tile_once_a_group_and_the_tail_once():
    """Tiles of 8 rows; groups of 3, 9, 0, 6 rows and 14 rows past them."""
    group, tile, read, bounds, used = (np.asarray(a) for a in gm._work_items(
        jnp.array([3, 9, 0, 6], jnp.int32), n_rows=32, tile_m=8))
    assert bounds.tolist() == [0, 3, 12, 12, 18, 32]
    n = int(used[0])
    # (group, tile): 0 on tile 0; 1 on tiles 0, 1; 3 on tiles 1, 2; the tail
    # (group 4: no weights) on tiles 2, 3
    assert list(zip(group[:n], tile[:n])) == [
        (0, 0), (1, 0), (1, 1), (3, 1), (3, 2), (4, 2), (4, 3)]
    assert read[:n].tolist() == [0, 0, 1, 1, 2, 2, 2]  # the tail reads nothing new
    # the list is as long as the worst case and its unused end repeats the last
    assert len(group) == 32 // 8 + 4
    assert set(zip(group[n:], tile[n:], read[n:])) == {(4, 3, 2)}


def test_tiles_come_from_the_shape():
    bf16 = jnp.bfloat16
    assert gm._tiles(163968, 3072, 1024, bf16) == (128, 512)   # gate, up
    assert gm._tiles(163968, 1024, 3072, bf16) == (128, 1536)  # down
    assert gm._tiles(40, 32, 64, jnp.float32) == (40, 64)      # a toy: whole
    with pytest.raises(NotImplementedError, match="whole contraction"):
        gm._tiles(1024, 65536, 1024, bf16)


def test_counter_says_which_path_a_trace_took_and_the_cpu_takes_ragged_dot():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    rows, w, group_sizes = _operands(40, [5, 0, 1, 20, 7])
    got = gm.grouped_matmul(rows, w, group_sizes)
    np.testing.assert_array_equal(got, gm.grouped_matmul_xla(rows, w, group_sizes))
    by_key = {}
    for series in metrics.snapshot().values():
        by_key.update(series.get("kernels.moe_gmm_schedule/by_key", {}))
    assert by_key == {"xla": 1}
    metrics.reset()


def test_product_differentiates_off_the_chip_and_the_kernel_says_it_cannot():
    rows, w, group_sizes = _operands(40, [5, 0, 1, 20, 7])
    grads = jax.grad(lambda r, w: jnp.sum(gm.grouped_matmul(r, w, group_sizes) ** 2),
                     argnums=(0, 1))(rows, w)
    assert all(np.isfinite(np.asarray(g)).all() and np.asarray(g).any()
               for g in grads)
    assert not np.asarray(grads[0][33:]).any()  # rows no group holds
    with pytest.raises(NotImplementedError, match="moe_gmm kernel has no "
                                                  "backward"):
        jax.grad(lambda r: jnp.sum(gm._kernel_no_vjp(r, w, group_sizes)))(rows)
