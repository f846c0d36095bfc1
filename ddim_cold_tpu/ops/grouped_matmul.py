"""The grouped matrix product of an expert layer: rows sorted by expert, each
group of rows multiplied by its own expert's weights,

    out[r] = rows[r] @ w[g]      for  starts[g] ≤ r < starts[g] + group_sizes[g]
    out[r] = 0                   for  r ≥ Σ group_sizes

which is ``jax.lax.ragged_dot``'s contract. One function,
:func:`grouped_matmul`; the backend decides what runs. On the TPU a Pallas
kernel (``pallas_call(name="moe_gmm")``, ``%moe_gmm`` in a device trace);
anywhere else, and as the tests' oracle, ``ragged_dot`` itself (plain JAX,
differentiable).

The kernel: groups are ragged, row tiles are not, so the launch walks a list
of *work items* made by XLA from ``group_sizes`` and handed over by scalar
prefetch (:func:`_work_items`): one item for every (group, row tile) pair that
share a row, in row order, then one for every row tile past the last group.
Grid ``(column tiles, work items)``, items innermost:

* an item multiplies its ``(tile_m, K)`` row tile by its group's ``(K,
  tile_n)`` weight tile — the whole contraction, float32 accumulation — and
  stores only the rows that belong to the group; a tile two groups share is
  visited once by each, the rows of the other kept (the output block stays in
  VMEM between consecutive items on the same tile, and its first visitor
  starts it from zeros);
* consecutive items of one group address the same weight block, so an
  expert's weight tile is fetched once a column tile however many row tiles
  the expert has;
* tiles past the last group are written as zeros without a product, and the
  list's unused tail (it is as long as the worst case, row tiles + groups)
  re-addresses the last item's blocks and does nothing.

Tiles come from the shape (:func:`_tiles`): 128 rows (a group's ragged edge
costs at most one more tile of that height), all of K, and the widest column
tile whose double-buffered weight block fits three eighths of the scoped
VMEM. No tile argument in any config.

The kernel has no backward yet (ROADMAP Reach) and says so when asked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.flash_attention import (
    _SCOPED_VMEM_BYTES, kernel_interpret)

#: which path each trace of the product took (``kernels.moe_gmm_schedule``)
_kernels = metrics.scope("kernels")

_TILE_M = 128


def grouped_matmul_xla(rows, w, group_sizes):
    """``jax.lax.ragged_dot``, float32 accumulation, result in ``rows``'
    dtype. ``rows: (M, K)``; ``w: (G, K, N)``; ``group_sizes: (G,)`` int32."""
    return jax.lax.ragged_dot(rows, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)


def _tiles(M: int, K: int, N: int, dtype) -> tuple:
    """(tile_m, tile_n) for ``(M, K) @ (G, K, N)`` of ``dtype``; see the
    module docstring for the rule."""
    isz = jnp.dtype(dtype).itemsize
    tm = tiling.legal_block(_TILE_M, M, dtype)
    if N % tiling.LANE:
        return tm, N  # a narrow (toy) width: one column tile, the whole dim
    budget = _SCOPED_VMEM_BYTES * 3 // 8
    tn = tiling.LANE
    for cand in range(N, 0, -tiling.LANE):
        if N % cand == 0 and 2 * K * cand * isz <= budget:
            tn = cand
            break
    need = 2 * K * tn * isz + 2 * tm * K * isz + 3 * tm * tn * 4
    if need > _SCOPED_VMEM_BYTES:
        raise NotImplementedError(
            f"moe_gmm keeps the whole contraction ({K}) of a weight tile in "
            f"VMEM: {need} bytes at the narrowest column tile, more than the "
            f"{_SCOPED_VMEM_BYTES} a kernel may use")
    return tm, tn


def _work_items(group_sizes, *, n_rows: int, tile_m: int):
    """The launch's work list from ``group_sizes`` (G,): ``(group, row tile,
    row tile whose rows to read, group bounds (G + 2,), items in use)``. The
    rows past the last group are group ``G``, which has no weights: its items
    read no new rows and write zeros. Static length ``row tiles + G``."""
    G = group_sizes.shape[0]
    n_tiles = n_rows // tile_m
    sizes = group_sizes.astype(jnp.int32)
    sizes = jnp.concatenate([sizes, n_rows - jnp.sum(sizes, keepdims=True)])
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile_m
    tiles = jnp.where(sizes > 0, (ends - 1) // tile_m - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    used = item_end[-1]
    item = jnp.minimum(jnp.arange(n_tiles + G, dtype=jnp.int32), used - 1)
    group = jnp.searchsorted(item_end, item, side="right").astype(jnp.int32)
    tile = first[group] + item - (item_end - tiles)[group]
    # the tail's items re-address the rows of the last real item's tile
    last_real = jnp.maximum((ends[G - 1] - 1) // tile_m, 0)
    read = jnp.where(group < G, tile, last_real)
    bounds = jnp.concatenate([starts, ends[-1:]])
    return group, tile, read, bounds, used[None]


def _gmm_kernel(group_ref, tile_ref, read_ref, bounds_ref, used_ref,
                x_ref, w_ref, o_ref, *, n_groups: int):
    """One (column tile, work item) program; see the module docstring."""
    del read_ref  # the index maps' business
    item = pl.program_id(1)

    @pl.when(item < used_ref[0])
    def _work():
        g, t = group_ref[item], tile_ref[item]
        tm, tn = o_ref.shape
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= bounds_ref[g]) & (row < bounds_ref[g + 1])
        first_visit = (item == 0) | (tile_ref[jnp.maximum(item - 1, 0)] != t)
        kept = jnp.where(first_visit, jnp.zeros_like(o_ref), o_ref[...])

        @pl.when(g < n_groups)
        def _product():
            acc = jnp.dot(x_ref[...], w_ref[0],
                          preferred_element_type=jnp.float32)
            o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), kept)

        @pl.when(g >= n_groups)
        def _tail():
            o_ref[...] = jnp.where(mine, jnp.zeros_like(kept), kept)


def grouped_matmul_kernel(rows, w, group_sizes, *, tiles=None, interpret=None):
    """The Pallas path. ``tiles`` (tile_m, tile_n) and ``interpret`` are for
    the tests and a sweep on the chip; the program leaves both to the shape
    and the backend. Rows are padded to whole tiles when they are not (the
    expert layer sizes its buffer so that they are)."""
    M, K = rows.shape
    G, _, N = w.shape
    tm, tn = tiles or _tiles(M, K, N, rows.dtype)
    if interpret is None:
        interpret = kernel_interpret()
    m_pad = tiling.round_up(M, tm)
    if m_pad != M:
        rows = jnp.pad(rows, ((0, m_pad - M), (0, 0)))
    scalars = _work_items(group_sizes, n_rows=m_pad, tile_m=tm)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(N // tn, m_pad // tm + G),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda n, i, grp, tile, read, *_: (read[i], 0)),
                pl.BlockSpec((1, K, tn),
                             lambda n, i, grp, *_: (
                                 jnp.minimum(grp[i], G - 1), 0, n)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, grp, tile, *_: (tile[i], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm",
    )(*scalars, rows, w)
    return out[:M] if m_pad != M else out


@jax.custom_vjp
def _kernel_no_vjp(rows, w, group_sizes):
    return grouped_matmul_kernel(rows, w, group_sizes)


def _no_vjp_fwd(*args):
    raise NotImplementedError(
        "the moe_gmm kernel has no backward yet (ROADMAP Reach): "
        "differentiate ops.grouped_matmul.grouped_matmul_xla "
        "(jax.lax.ragged_dot), which is what grouped_matmul runs off the TPU")


_kernel_no_vjp.defvjp(_no_vjp_fwd, lambda res, g: None)


def grouped_matmul(rows, w, group_sizes):
    """``out`` of the module docstring's contract, ``(M, N)`` in ``rows``'
    dtype, float32 accumulation on either path."""
    use_kernel = jax.default_backend() == "tpu"
    _kernels.inc("kernels.moe_gmm_schedule",
                 key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("moe_gmm"):
            return _kernel_no_vjp(rows, w, group_sizes)
    return grouped_matmul_xla(rows, w, group_sizes)
