"""ops/grouped_matmul.py: the ``moe_gmm`` kernel in interpreter mode against
``jax.lax.ragged_dot`` — empty, one-row and tile-straddling groups, rows past
the last group — as the one product and as the expert MLP's first half (gate
and up in one launch, ``act(g) * u`` with ``act`` SiLU or ReLU; or ungated,
``relu(u)²`` of the one product); its work list, its tiles, its counters and
its refusal to differentiate."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddim_cold_tpu.ops import grouped_matmul as gm


def _operands(M, sizes, K=32, N=64, dtype=jnp.float32, seed=1, banks=1):
    """``rows, *weight banks, group_sizes``; two banks are gate and up."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 1 + banks)
    return (jax.random.normal(ks[0], (M, K), dtype),
            *(jax.random.normal(k, (len(sizes), K, N), dtype) for k in ks[1:]),
            jnp.array(sizes, jnp.int32))


def _first_half(rows, w_gate, w_up, group_sizes, dtype=jnp.float32,
                act=jax.nn.silu):
    """``act(ragged_dot(rows, w_gate)) * ragged_dot(rows, w_up)``, each step
    rounded to ``dtype``: in bfloat16 the three-step composition the expert
    layer ran before the launch was fused."""
    g, u = (jax.lax.ragged_dot(rows.astype(dtype), w.astype(dtype), group_sizes,
                               preferred_element_type=jnp.float32).astype(dtype)
            for w in (w_gate, w_up))
    return act(g) * u


GROUPS = [
    ([5, 0, 1, 20, 7], 40, None),            # empty, one-row, rows left over
    ([0, 0, 0], 16, None),                   # nothing routed here at all
    ([300, 0, 1, 127, 128, 3], 600, None),   # groups across tiles of 128
    ([16, 16], 32, (16, 64)),                # groups that end on tile edges
    ([3, 9, 30], 64, (8, 64)),               # a tile three groups share
    ([0, 130], 256, None),                   # a leading empty group
    ([20, 4], 64, (8, 64)),                  # a tail of whole tiles
]


@pytest.mark.parametrize("launch", ["product", "gate_up", "relu2"])
@pytest.mark.parametrize("sizes,M,tiles", GROUPS)
def test_kernel_matches_ragged_dot(sizes, M, tiles, launch):
    if launch == "product":
        rows, w, group_sizes = _operands(M, sizes)
        want = gm.grouped_matmul_xla(rows, w, group_sizes)
        got = gm.grouped_matmul_kernel(rows, w, group_sizes, tiles=tiles)
        tol = 1e-5
    elif launch == "relu2":
        # the ungated first half against its XLA form, written out
        rows, w_up, group_sizes = _operands(M, sizes)
        rows = rows * 0.25
        u = jax.lax.ragged_dot(rows, w_up, group_sizes)
        want = jnp.where(u > 0, u * u, 0.0)
        np.testing.assert_array_equal(
            gm.grouped_relu2_xla(rows, w_up, group_sizes), want)
        got = gm.grouped_relu2_kernel(rows, w_up, group_sizes, tiles=tiles)
        assert float(jnp.abs(want - u).max() if sum(sizes) else 1.0) > 0.1
        tol = 1e-5
    else:
        rows, w_gate, w_up, group_sizes = _operands(M, sizes, banks=2)
        rows = rows * 0.25  # |g|, |u| of order one: 1e-6 is then a few ulps
        want = _first_half(rows, w_gate, w_up, group_sizes)
        got = gm.grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes,
                                        tiles=tiles or gm._tiles(
                                            M, 32, 64, jnp.float32))
        np.testing.assert_array_equal(
            gm.grouped_gate_up_xla(rows, w_gate, w_up, group_sizes), want)
        tol = 1e-6
    assert got.shape == want.shape == (M, 64)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # rows past the last group: zeros, as ragged_dot leaves them
    assert not np.asarray(got[sum(sizes):]).any()


@pytest.mark.parametrize("launch", ["product", "gate_up", "relu2"])
def test_kernel_in_bfloat16_accumulates_in_float32(launch):
    bf16 = jnp.bfloat16
    if launch == "relu2":
        rows, w_up, group_sizes = _operands(256, [100, 28, 60], K=256, N=128,
                                            dtype=bf16)
        rows = rows * 0.0625
        got = gm.grouped_relu2_kernel(rows, w_up, group_sizes)
        assert got.dtype == bf16
        # the square is taken of the float32 product: one rounding, where
        # squaring a rounded product makes two
        exact = jnp.square(jax.nn.relu(jax.lax.ragged_dot(
            rows.astype(jnp.float32), w_up.astype(jnp.float32), group_sizes)))
        u = gm.grouped_matmul_xla(rows, w_up, group_sizes)
        steps = jnp.square(jax.nn.relu(u))
        err = lambda h: float(jnp.sqrt(jnp.mean(
            (h.astype(jnp.float32) - exact) ** 2)))
        assert err(got) < 0.75 * err(steps)
        np.testing.assert_allclose(got.astype(jnp.float32), exact,
                                   rtol=1e-2, atol=1e-2)
        return
    if launch == "product":
        rows, w, group_sizes = _operands(256, [100, 28, 60], K=256, N=128,
                                         dtype=bf16)
        got = gm.grouped_matmul_kernel(rows, w, group_sizes)
        want = gm.grouped_matmul_xla(rows, w, group_sizes)
        assert got.dtype == bf16
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32), rtol=2e-2, atol=2e-2)
        return
    rows, w_gate, w_up, group_sizes = _operands(
        256, [100, 28, 60], K=256, N=128, dtype=bf16, banks=2)
    rows = rows * 0.0625
    got = gm.grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes,
                                    tiles=(128, 128))
    assert got.dtype == bf16
    # g and u reach the SiLU unrounded: one rounding where the three steps
    # make three, so the launch lies closer to the float32 answer than they do
    exact = _first_half(rows, w_gate, w_up, group_sizes)
    steps = _first_half(rows, w_gate, w_up, group_sizes, bf16)
    err = lambda h: float(jnp.sqrt(jnp.mean((h.astype(jnp.float32) - exact) ** 2)))
    assert err(got) < 0.75 * err(steps)
    np.testing.assert_allclose(got.astype(jnp.float32), exact,
                               rtol=1e-2, atol=1e-2)


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


@pytest.mark.parametrize("M,K,F,groups", [
    (163968, 3072, 1024, 128),   # Laguna-S-2.1's expert layer on this chip
    (73856, 6144, 2048, 16),     # GLM-5.2's
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gate_up_tiles_come_from_the_shape_with_the_vmem_they_need(
        M, K, F, groups, dtype):
    """The widest column tile whose working set — both double-buffered weight
    blocks, the rows, the result, the two float32 products — fits the scoped
    VMEM the launch then asks for, which stays under the ceiling; and never
    more grid steps than ONE of the products it replaces."""
    isz = jnp.dtype(dtype).itemsize
    tm, tn, limit = gm._gate_up_tiles(M, K, F, dtype)
    assert tm == 128 and F % tn == 0 and tn % 128 == 0
    need = (2 * 2 * K * tn * isz + 2 * tm * K * isz + 2 * tm * tn * isz
            + 2 * tm * tn * 4)
    assert gm._SCOPED_VMEM_BYTES < need <= limit <= gm._VMEM_CEILING_BYTES
    assert 2 * tn > F or 2 * 2 * K * 2 * tn * isz > gm._VMEM_CEILING_BYTES
    one_product = (F // gm._tiles(M, K, F, dtype)[1]) * (M // 128 + groups)
    assert (F // tn) * (M // tm + groups) <= one_product
    if dtype == jnp.bfloat16:
        assert tn == 1024  # Laguna: the whole width, rows streamed once


def test_gate_up_tiles_of_a_toy_and_of_a_contraction_too_long():
    assert gm._gate_up_tiles(40, 32, 64, jnp.float32) == (40, 64, None)
    assert gm._gate_up_tiles(256, 256, 128, jnp.bfloat16) == (128, 128, None)
    with pytest.raises(NotImplementedError, match="whole contraction"):
        gm._gate_up_tiles(1024, 65536, 1024, jnp.bfloat16)


@pytest.mark.parametrize("sizes,M,tiles", GROUPS)
def test_the_two_launches_do_not_read_what_the_first_left_unwritten(
        sizes, M, tiles):
    """``grouped_mlp``'s first launch leaves ``h``'s row tiles past the last
    group unwritten (the interpreter leaves NaN there, the chip whatever lay
    in HBM); its second launch's result is the float32 composition's all the
    same, and does not move when those tiles are overwritten."""
    rows, w_gate, w_up, group_sizes = _operands(M, sizes, banks=2)
    rows = rows * 0.25
    w_down = jax.random.normal(jax.random.PRNGKey(5), (len(sizes), 64, 32)) / 8
    tiles = tiles or gm._tiles(M, 32, 64, jnp.float32)
    h = gm.grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes, tiles=tiles,
                                  zero_tail=False)
    whole = gm.grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes, tiles=tiles)
    written = -(-sum(sizes) // tiles[0]) * tiles[0]  # to the last real tile's end
    np.testing.assert_array_equal(h[:written], whole[:written])
    if written < M and sum(sizes):
        assert np.isnan(np.asarray(h[written:])).all()  # really left alone
    down = lambda h: gm.grouped_matmul_kernel(h, w_down, group_sizes,
                                              tiles=(tiles[0], 32))
    got = down(h)
    np.testing.assert_array_equal(got, down(whole))
    np.testing.assert_array_equal(got, down(h.at[written:].set(-7.0)))
    want = gm.grouped_matmul_xla(_first_half(rows, w_gate, w_up, group_sizes),
                                 w_down, group_sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[sum(sizes):]).any()


@pytest.mark.parametrize("banks,counted", [
    (1, {"kernels.moe_gmm_schedule": {"xla": 1}}),
    # the first half counts the two PRODUCTS it holds, and its own path
    (2, {"kernels.moe_gmm_schedule": {"xla": 2},
         "kernels.moe_gate_up_schedule": {"xla": 1}}),
    (3, {"kernels.moe_gmm_schedule": {"xla": 3},   # the whole MLP: + down
         "kernels.moe_gate_up_schedule": {"xla": 1}}),
    # the ungated MLP: up under its squared ReLU and down, no gated half
    (0, {"kernels.moe_gmm_schedule": {"xla": 2}}),
])
def test_counter_says_which_path_a_trace_took_and_the_cpu_takes_ragged_dot(
        banks, counted):
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    rows, *ws, group_sizes = _operands(40, [5, 0, 1, 20, 7], N=32,
                                       banks=banks or 2)
    if banks == 0:
        got = gm.grouped_mlp(rows, None, *ws, group_sizes)
        want = gm.grouped_matmul_xla(
            gm.grouped_relu2_xla(rows, ws[0], group_sizes), ws[1], group_sizes)
    elif banks == 1:
        got = gm.grouped_matmul(rows, *ws, group_sizes)
        want = gm.grouped_matmul_xla(rows, *ws, group_sizes)
    elif banks == 2:
        got = gm.grouped_gate_up(rows, *ws, group_sizes)
        want = gm.grouped_gate_up_xla(rows, *ws, group_sizes)
    else:
        got = gm.grouped_mlp(rows, *ws, group_sizes)
        want = gm.grouped_matmul_xla(
            gm.grouped_gate_up_xla(rows, *ws[:2], group_sizes), ws[2], group_sizes)
    np.testing.assert_array_equal(got, want)
    by_key = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.moe_gmm_schedule", "kernels.moe_gate_up_schedule"):
            if name + "/by_key" in series:
                by_key[name] = series[name + "/by_key"]
    assert by_key == counted
    metrics.reset()


@pytest.mark.parametrize("banks,ungated", [(1, False), (2, False), (1, True)])
def test_product_differentiates_off_the_chip_and_the_kernel_says_it_cannot(
        banks, ungated):
    rows, *ws, group_sizes = _operands(40, [5, 0, 1, 20, 7], banks=banks)
    public, launch = (
        (gm.grouped_relu2, gm._first_half_no_vjp[None, True]) if ungated
        else (gm.grouped_matmul, gm._kernel_no_vjp) if banks == 1
        else (gm.grouped_gate_up, gm._first_half_no_vjp["silu", True]))
    grads = jax.grad(lambda r, *ws: jnp.sum(public(r, *ws, group_sizes) ** 2),
                     argnums=tuple(range(1 + banks)))(rows, *ws)
    assert all(np.isfinite(np.asarray(g)).all() and np.asarray(g).any()
               for g in grads)
    assert not np.asarray(grads[0][33:]).any()  # rows no group holds
    with pytest.raises(NotImplementedError, match="moe_gmm kernel has no "
                                                  "backward"):
        jax.grad(lambda r: jnp.sum(launch(r, *ws, group_sizes)))(rows)


# ------------------------------------------------ the gate's activation

def _relu_first_half(rows, w_gate, w_up, group_sizes, dtype=jnp.float32):
    """:func:`_first_half` under a ReLU written as a select."""
    return _first_half(rows, w_gate, w_up, group_sizes, dtype,
                       act=lambda g: jnp.where(g > 0, g, 0.0).astype(dtype))


@pytest.mark.parametrize("sizes,M,tiles", GROUPS)
def test_relu_gated_launch_matches_its_xla_form(sizes, M, tiles):
    """``act="relu"`` is the same launch with ``relu`` in SiLU's place: the
    kernel in the interpreter against ``grouped_gate_up_xla(..., "relu")``,
    that against the composition written out, zeros past the groups — and it
    is not the SiLU launch's result."""
    rows, w_gate, w_up, group_sizes = _operands(M, sizes, banks=2)
    rows = rows * 0.25
    want = gm.grouped_gate_up_xla(rows, w_gate, w_up, group_sizes, "relu")
    np.testing.assert_array_equal(
        want, _relu_first_half(rows, w_gate, w_up, group_sizes))
    got = gm.grouped_gate_up_kernel(
        rows, w_gate, w_up, group_sizes, act="relu",
        tiles=tiles or gm._tiles(M, 32, 64, jnp.float32))
    assert got.shape == want.shape == (M, 64)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not np.asarray(got[sum(sizes):]).any()
    if sum(sizes):
        silu = gm.grouped_gate_up_xla(rows, w_gate, w_up, group_sizes)
        assert float(jnp.abs(silu - want).max()) > 0.05
        # a ReLU's zeros are written as zeros, where SiLU leaves a tail
        assert (np.asarray(got[:sum(sizes)]) == 0).mean() > 0.3
        # the default is SiLU, bit for bit
        np.testing.assert_array_equal(
            silu, gm.grouped_gate_up_xla(rows, w_gate, w_up, group_sizes, "silu"))


def test_relu_gated_launch_in_bfloat16_rounds_once():
    bf16 = jnp.bfloat16
    rows, w_gate, w_up, group_sizes = _operands(
        256, [100, 28, 60], K=256, N=128, dtype=bf16, banks=2)
    rows = rows * 0.0625
    got = gm.grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes,
                                    act="relu", tiles=(128, 128))
    assert got.dtype == bf16
    exact = _relu_first_half(rows, w_gate, w_up, group_sizes)
    steps = _relu_first_half(rows, w_gate, w_up, group_sizes, bf16)
    err = lambda h: float(jnp.sqrt(jnp.mean((h.astype(jnp.float32) - exact) ** 2)))
    assert err(got) < 0.75 * err(steps)
    np.testing.assert_allclose(got.astype(jnp.float32), exact,
                               rtol=1e-2, atol=1e-2)
    # the XLA form rounds once too: the two agree to a last bit of bfloat16
    # where their float32 sums were added in another order
    np.testing.assert_allclose(
        got.astype(jnp.float32), gm.grouped_gate_up_xla(
            rows, w_gate, w_up, group_sizes, "relu").astype(jnp.float32),
        rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_grouped_mlp_passes_the_gates_activation_on(act):
    """The whole MLP under either activation, off the chip the XLA forms:
    down of ``act(g) * u``; the traced program holds the activation asked
    for and not the other, and the first half's path is counted as before."""
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    rows, w_gate, w_up, group_sizes = _operands(40, [5, 0, 1, 20, 7], N=32,
                                                banks=2)
    w_down = jax.random.normal(jax.random.PRNGKey(5), (5, 32, 32)) / 8
    got = gm.grouped_mlp(rows, w_gate, w_up, w_down, group_sizes, act=act)
    half = (_relu_first_half if act == "relu" else _first_half)(
        rows, w_gate, w_up, group_sizes)
    np.testing.assert_allclose(
        got, gm.grouped_matmul_xla(half, w_down, group_sizes),
        rtol=1e-6, atol=1e-6)
    counted = {}
    for series in metrics.snapshot().values():
        for name in ("kernels.moe_gate_up_schedule",
                     "kernels.moe_gmm_schedule"):
            counted.update({name: series[name + "/by_key"]}
                           if name + "/by_key" in series else {})
    assert counted == {"kernels.moe_gate_up_schedule": {"xla": 1},
                       "kernels.moe_gmm_schedule": {"xla": 3}}
    metrics.reset()
    traced = str(jax.make_jaxpr(functools.partial(gm.grouped_mlp, act=act))(
        rows, w_gate, w_up, w_down, group_sizes))
    assert ("logistic" in traced, " max " in traced) == (
        act == "silu", act == "relu"), traced


def test_an_activation_the_launch_does_not_know_is_refused_by_name():
    from ddim_cold_tpu.obs import metrics

    metrics.reset()
    rows, w_gate, w_up, group_sizes = _operands(40, [5, 0, 1, 20, 7], banks=2)
    with pytest.raises(ValueError, match=r"gate activation 'gelu'.*'relu', 'silu'"):
        gm.grouped_gate_up(rows, w_gate, w_up, group_sizes, "gelu")
    counted = lambda: {name for series in metrics.snapshot().values()
                       for name in series if name.startswith("kernels.moe_")}
    assert counted() == set()  # refused before anything is counted
    # the ungated half counts its product and no gate
    gm.grouped_relu2(rows, w_up, group_sizes)
    assert counted() == {"kernels.moe_gmm_schedule",
                         "kernels.moe_gmm_schedule/by_key"}
    # a launch for each (activation or none, tail written or left)
    assert set(gm._first_half_no_vjp) == {
        (act, tail) for act in (None, "relu", "silu") for tail in (False, True)}
    metrics.reset()
