"""GRAFT-P self-tests: violating pallas_call fixtures per rule (the odd
block, the dynamic grid, the oversized scratch, the wasteful block), the
Mosaic legality sweep of ``ops/tiling.legal_block`` at the exact 200px
geometries, and the clean run over the first-class 200px kernel entries.

The fixtures trace on CPU — ``jax.make_jaxpr`` of a ``pallas_call`` never
lowers through Mosaic, which is precisely why the static pass exists: CPU
CI cannot reject these geometries at runtime, so graftcheck must."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddim_cold_tpu.analysis import entries, kernel_checks
from ddim_cold_tpu.analysis.findings import load_baseline, write_baseline
from ddim_cold_tpu.ops import tiling


def _rules_of(findings):
    return sorted({f.rule for f in findings})


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _trace(shape, dtype, block, grid):
    """A minimal one-operand pallas_call traced abstractly."""
    x = jax.ShapeDtypeStruct(shape, dtype)

    def f(x):
        return pl.pallas_call(
            _copy_kernel, out_shape=jax.ShapeDtypeStruct(shape, dtype),
            grid=grid,
            in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (i, 0)))(x)

    return jax.make_jaxpr(f)(x)


def _check(closed, **kw):
    return kernel_checks.check_program(closed, "fix", "fix.py", **kw)


# --------------------------------------------------------------- P001


def test_p001_odd_block_at_200px_token_count():
    # the r04 killer: a hand-tuned block that neither hits the f32 min
    # tile (8) nor divides the padded token axis — interpret mode runs it,
    # Mosaic rejects it on chip
    closed = _trace((2504, 128), jnp.float32, (100, 128), (26,))
    fs = _check(closed)
    assert _rules_of(fs) == ["GRAFT-P001"]
    assert {f.subject for f in fs} == {"fix:_copy_kernel#1:in0",
                                       "fix:_copy_kernel#1:out0"}
    assert "min-tile unit 8" in fs[0].message
    assert "not a multiple of block" in fs[0].message


def test_p001_sub16_sublane_block_on_bf16():
    closed = _trace((2504, 128), jnp.bfloat16, (8, 128), (313,))
    fs = _check(closed)
    assert _rules_of(fs) == ["GRAFT-P001"]
    assert "min-tile unit 16" in fs[0].message


def test_p001_non_static_grid():
    # np.int64 grid entries (np.gcd-promoted block arithmetic) become
    # DynamicGridDim at trace time — the in-tree legal_block bug this
    # pass's first run caught
    closed = _trace((2504, 128), jnp.float32, (8, 128), (np.int64(313),))
    fs = _check(closed)
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-P001", "fix:_copy_kernel#1:grid")]
    assert "non-static grid" in fs[0].message


def test_p001_whole_dim_span_is_legal():
    # a block spanning the whole array dim is exempt from the min-tile
    # multiple rule (Mosaic's whole-dim escape hatch)
    closed = _trace((4, 128), jnp.float32, (4, 128), (1,))
    assert _check(closed) == []


@pytest.mark.parametrize("name,shape,block,grid,rules", [
    # the flash forward's token axis ends inside the last block: legal
    ("fwd", (2501, 128), (512, 128), (5,), []),
    ("fwd", (2501, 128), (2560, 128), (1,), []),
    # ... only as a tile-multiple block, only on the sublane axis
    ("fwd", (2501, 128), (100, 128), (26,), ["GRAFT-P001"]),
    ("fwd", (2504, 320), (8, 128), (313,), ["GRAFT-P001"]),
    # the flash backward's too (dq: K/V resident; dkv: q/do resident)
    ("dq", (2501, 128), (512, 128), (5,), []),
    ("dq", (2501, 128), (2560, 128), (1,), []),
    ("dkv", (2501, 128), (512, 128), (5,), []),
    ("dkv", (2501, 128), (2560, 128), (1,), []),
    ("dkv", (2501, 128), (100, 128), (26,), ["GRAFT-P001"]),
    # ... and the one launch of both (K/V blocks; q, o, do resident)
    ("dqkv", (2501, 128), (512, 128), (5,), []),
    ("dqkv", (2501, 128), (2560, 128), (1,), []),
    ("dqkv", (2501, 128), (100, 128), (26,), ["GRAFT-P001"]),
    # the two token-wise kernels: row-wise, so the tail stays in its rows
    ("ln_qkv", (2501, 256), (1264, 256), (2,), []),
    ("block_tail", (2501, 256), (1264, 256), (2,), []),
    ("block_tail", (2501, 256), (1252, 256), (2,), ["GRAFT-P001"]),
    # ... and only for the kernels whose bodies mask the ragged tail
    ("mlp", (2501, 128), (512, 128), (5,), ["GRAFT-P001"]),
    (None, (2501, 128), (512, 128), (5,), ["GRAFT-P001"]),
])
def test_p001_partial_final_block_only_where_the_kernel_masks_it(
        name, shape, block, grid, rules):
    def f(x):
        return pl.pallas_call(
            _copy_kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            grid=grid, in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
            **({"name": name} if name else {}))(x)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct(shape, jnp.float32))
    assert _rules_of(_check(closed)) == rules
    assert kernel_checks.RAGGED_SUBLANE_OK == {"fwd", "dq", "dkv", "dqkv",
                                               "ln_qkv", "block_tail"}


# --------------------------------------------------------------- P002


def test_p002_oversized_vmem_scratch():
    def kernel(x_ref, o_ref, acc_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
            grid=(1,),
            in_specs=[pl.BlockSpec((256, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((256, 128), lambda i: (0, 0)),
            scratch_shapes=[pltpu.VMEM((4096, 4096), jnp.float32)])(x)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((256, 128), jnp.float32))
    fs = _check(closed)
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-P002", "fix:kernel#1:vmem")]
    assert "64.5 MiB" in fs[0].message
    # a roomier explicit budget clears it
    assert _check(closed, vmem_budget=128 << 20) == []


def test_p002_budget_counts_double_buffering():
    call = kernel_checks.KernelCall(
        name="k", path="fix.py", line=1, grid=(1,),
        blocks=[kernel_checks.BlockInfo("in", 0, (512, 128), (512, 128),
                                        np.dtype(np.float32))])
    assert call.vmem_bytes() == 2 * 512 * 128 * 4


# --------------------------------------------------------------- P003


def test_p003_wasteful_block_at_logical_tokens():
    # array pre-padded to the block multiple (P001-clean) but the block
    # charges 4096 rows of compute against 2501 logical tokens
    closed = _trace((4096, 128), jnp.float32, (2048, 128), (2,))
    fs = _check(closed, logical=2501)
    assert [(f.rule, f.subject) for f in fs] == [
        ("GRAFT-P003", "fix:_copy_kernel#1:pad")]
    assert "64%" in fs[0].message
    # without the registered logical extent the same geometry is exact
    assert _check(closed) == []


def test_p003_in_tree_worst_case_passes():
    # the streamed-kv sweep worst case: bkv=1024 pads 2504 → 3072 over
    # 2501 logical (1.228) — under the 1.25 threshold by design
    closed = _trace((2504, 128), jnp.float32, (1024, 128), (3,))
    fs = _check(closed, logical=2501)
    assert _rules_of(fs) == ["GRAFT-P001"]  # 2504 % 1024 only; no P003
    assert not [f for f in fs if f.rule == "GRAFT-P003"]


# ------------------------------------------------- legal_block vs Mosaic


def test_min_tile_table_matches_tiling():
    # the pass keeps an independent copy of the tile table so a legalizer
    # regression is caught — but the two must agree on the rule itself
    for dt in (np.float32, jnp.bfloat16, np.int8):
        sub, lane = kernel_checks.MIN_TILE[np.dtype(dt).itemsize]
        assert sub == tiling.sublane_unit(dt)
        assert lane == tiling.LANE


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int8])
@pytest.mark.parametrize("dim", [2501, 2504, 3072, 64, 128, 40016])
def test_legal_block_sweep_is_mosaic_legal(dtype, dim):
    """Exhaustive request sweep at the 200px shapes: every returned block
    is a Python int (np.int64 would make the grid dynamic — P001), a
    min-tile multiple, and pads the dim to a block multiple."""
    for lane in (False, True):
        unit = tiling.LANE if lane else tiling.sublane_unit(dtype)
        for req in (1, 7, 8, 100, 256, 511, 512, 2048, dim, 2 * dim):
            blk = tiling.legal_block(req, dim, dtype, lane=lane)
            assert type(blk) is int, (req, dim, blk)
            assert blk % unit == 0
            assert blk <= tiling.round_up(dim, unit)
            padded = tiling.round_up(dim, blk)
            assert padded % blk == 0 and padded >= dim


def test_legal_block_dual_dtype_min_unit():
    # the dequant K block: activation lane dim AND int8 weight sublane dim
    blk = tiling.legal_block(512, 256, jnp.bfloat16, lane=True,
                             min_unit=tiling.sublane_unit(np.int8))
    assert type(blk) is int and blk % 128 == 0 and blk % 32 == 0


# ------------------------------------------------- baseline + clean tree


def test_p_finding_keys_are_stable_and_round_trip(tmp_path):
    closed = _trace((2504, 128), jnp.float32, (100, 128), (26,))
    fs = _check(closed)
    base = tmp_path / "baseline.txt"
    write_baseline(str(base), fs)
    assert load_baseline(str(base)) == {f.key for f in fs}
    # identity survives a re-trace (line numbers are display-only)
    assert {f.key for f in _check(_trace((2504, 128), jnp.float32,
                                         (100, 128), (26,)))} == \
        {f.key for f in fs}


def test_kernel_entries_cover_the_northstar_geometry():
    names = [e.name for e in entries.kernel_entries()]
    for required in ("ns200_f32", "ns200_bf16", "ns200_w8a16"):
        assert required in names, required
    assert any(n.startswith("flash200_grad_") for n in names)
    assert any(n.startswith("dequant200_") for n in names)


def test_clean_in_tree_kernels(kernel_traces):
    """The acceptance gate: every in-tree pallas_call at the registered
    200px geometries (f32/bf16/w8a16 samplers, the flash fwd/grad block
    sweep, the dequant matmuls) proves tile-legal, VMEM-fitting, and
    waste-free — and some calls actually exist to prove it on."""
    fs = kernel_checks.run_kernel_checks(serve_traces={}, entry_traces={},
                                         kernel_traces=kernel_traces)
    assert [f.render() for f in fs] == []
    n_calls = sum(
        len(list(kernel_checks.iter_kernel_calls(c, e.path)))
        for e, c in kernel_traces.values())
    assert n_calls >= 10, n_calls
