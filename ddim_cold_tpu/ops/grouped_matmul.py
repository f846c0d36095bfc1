"""The grouped matrix products of an expert layer: rows sorted by expert, each
group of rows multiplied by its own expert's weights,

    out[r] = rows[r] @ w[g]      for  starts[g] ≤ r < starts[g] + group_sizes[g]
    out[r] = 0                   for  r ≥ Σ group_sizes

which is ``jax.lax.ragged_dot``'s contract (:func:`grouped_matmul`), and the
first half of the experts' gated MLP as ONE such pass over the rows
(:func:`grouped_gate_up`),

    h[r] = act(rows[r] @ w_gate[g]) * (rows[r] @ w_up[g])      (0 past the groups)

``act`` the gate's activation, a static choice of the same launch: ``"silu"``
(the default) or ``"relu"``. Both products are accumulated, and the activation
and the product between them taken, in float32: one rounding, on the store. An
UNGATED expert's first half
(:func:`grouped_relu2`) is the one product under a squared ReLU,

    h[r] = relu(rows[r] @ w_up[g])²                            (0 past the groups)

in the same launch, the square taken of the float32 product.
:func:`grouped_mlp` is the whole MLP, ``h`` then ``h @ w_down[g]``. The
backend decides what runs. On the TPU a Pallas kernel
(``pallas_call(name="moe_gmm")``, ``%moe_gmm`` in a device trace: one launch
a product, ONE for gate and up, so two an expert layer);
anywhere else, and as the tests' oracle, ``ragged_dot`` itself (plain JAX,
differentiable).

The kernel: groups are ragged, row tiles are not, so the launch walks a list
of *work items* made by XLA from ``group_sizes`` and handed over by scalar
prefetch (:func:`_work_items`): one item for every (group, row tile) pair that
share a row, in row order, then one for every row tile past the last group.
Grid ``(column tiles, work items)``, items innermost:

* an item multiplies its ``(tile_m, K)`` row tile by its group's ``(K,
  tile_n)`` weight tile — the whole contraction, float32 accumulation; in the
  gate-up launch by the gate tile AND the up tile while the rows sit in VMEM —
  and stores only the rows that belong to the group; a tile two groups share
  is visited once by each, the rows of the other kept (the output block stays
  in VMEM between consecutive items on the same tile, and its first visitor
  starts it from zeros);
* consecutive items of one group address the same weight block, so an
  expert's weight tile is fetched once a column tile however many row tiles
  the expert has;
* tiles past the last group are written as zeros without a product, and the
  list's unused tail (it is as long as the worst case, row tiles + groups)
  re-addresses the last item's blocks and does nothing. Inside
  :func:`grouped_mlp`, where ``h`` has one reader that never looks past the
  last group's row tile, the gate-up launch leaves those tiles unwritten: its
  tail items address the last real item's output block as well.

Tiles come from the shape, no tile argument in any config: 128 rows (a
group's ragged edge costs at most one more tile of that height), all of K,
and for one product (:func:`_tiles`) the widest column tile whose
double-buffered weight block fits three eighths of the VMEM a kernel gets
unasked. The gate-up launch (:func:`_gate_up_tiles`) takes the widest column
tile whose whole working set fits half of the chip's VMEM and asks for that
much (``vmem_limit_bytes``; at an equal tile the raised limit costs nothing,
PERF.md section 6, PR 38): with two weight tiles a step, a wider tile means
fewer walks of the work list and fewer reads of the rows — at
Laguna-S-2.1's shape the whole width, the rows streamed once.

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

#: which path each traced product took (``kernels.moe_gmm_schedule``) and
#: each first half (``kernels.moe_gate_up_schedule``)
_kernels = metrics.scope("kernels")

_TILE_M = 128
#: the gate's activation in the gated first half, by its name in a
#: configuration: on the float32 product, in the launch and in XLA alike
GATE_ACTS = {"silu": jax.nn.silu, "relu": lambda g: jnp.maximum(g, 0.0)}
#: the most scoped VMEM a launch asks for (``vmem_limit_bytes``): half of the
#: 128 MiB a v5e core has; ``_SCOPED_VMEM_BYTES`` is what it gets unasked
_VMEM_CEILING_BYTES = 64 << 20


def grouped_matmul_xla(rows, w, group_sizes):
    """``jax.lax.ragged_dot``, float32 accumulation, result in ``rows``'
    dtype. ``rows: (M, K)``; ``w: (G, K, N)``; ``group_sizes: (G,)`` int32."""
    return jax.lax.ragged_dot(rows, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)


def grouped_gate_up_xla(rows, w_gate, w_up, group_sizes, act: str = "silu"):
    """Two ``ragged_dot``s and ``act(g) * u`` between them in float32, one
    rounding to ``rows``' dtype. ``w_gate``, ``w_up``: ``(G, K, F)``; ``act``
    of :data:`GATE_ACTS`."""
    g, u = (jax.lax.ragged_dot(rows, w, group_sizes.astype(jnp.int32),
                               preferred_element_type=jnp.float32)
            for w in (w_gate, w_up))
    return (GATE_ACTS[act](g) * u).astype(rows.dtype)


def grouped_relu2_xla(rows, w_up, group_sizes):
    """One ``ragged_dot`` and the square of its ReLU in float32, one rounding
    to ``rows``' dtype. ``w_up``: ``(G, K, F)``."""
    u = jax.lax.ragged_dot(rows, w_up, group_sizes.astype(jnp.int32),
                           preferred_element_type=jnp.float32)
    return jnp.square(jax.nn.relu(u)).astype(rows.dtype)


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


def _gate_up_tiles(M: int, K: int, F: int, dtype) -> tuple:
    """(tile_m, tile_n, scoped VMEM to ask for) of the gate-up launch; see
    the module docstring for the rule."""
    isz = jnp.dtype(dtype).itemsize
    tm = tiling.legal_block(_TILE_M, M, dtype)

    def limit(tn):  # both weight blocks, rows and result double-buffered,
        # the two float32 products and the epilogue's temporaries; an eighth
        # for what the compiler keeps besides
        need = (2 * 2 * K * tn * isz + 2 * tm * K * isz + 2 * tm * tn * isz
                + 4 * tm * tn * 4)
        return tiling.round_up(need * 9 // 8, 1 << 20)

    widths = range(F, 0, -tiling.LANE) if F % tiling.LANE == 0 else (F,)
    for tn in widths:
        ask = limit(tn)
        if F % tn == 0 and ask <= _VMEM_CEILING_BYTES:
            return tm, tn, ask if ask > _SCOPED_VMEM_BYTES else None
    raise NotImplementedError(
        f"moe_gmm keeps the whole contraction ({K}) of a gate and an up "
        f"weight tile in VMEM: {limit(widths[-1])} bytes at the narrowest "
        f"column tile, more than the {_VMEM_CEILING_BYTES} a launch asks for")


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
                x_ref, *refs, n_groups: int, zero_tail: bool, relu2: bool,
                act: str):
    """One (column tile, work item) program; see the module docstring. One
    weight operand: the product, or with ``relu2`` the square of its ReLU.
    Two, gate then up: ``act(g) * u`` of the two float32 products."""
    del read_ref  # the index maps' business
    *w_refs, o_ref = refs
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
            acc, *up = [jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)
                        for w_ref in w_refs]
            if up:
                acc = GATE_ACTS[act](acc) * up[0]
            elif relu2:
                acc = jnp.square(jnp.maximum(acc, 0.0))
            o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), kept)

        if zero_tail:
            @pl.when(g >= n_groups)
            def _tail():
                o_ref[...] = jnp.where(mine, jnp.zeros_like(kept), kept)


def _launch(rows, ws, group_sizes, *, tiles, vmem_limit=None, zero_tail=True,
            relu2=False, act="silu", interpret=None):
    """``pallas_call(name="moe_gmm")`` of ``rows (M, K)`` against the weight
    operands ``ws``, each ``(G, K, N)``, at ``tiles`` (tile_m, tile_n). Rows
    are padded to whole tiles when they are not (the expert layer sizes its
    buffer so that they are). ``zero_tail=False``: the tail's items address
    the last real item's OUTPUT block too and do nothing, so the row tiles
    past the last group are never written."""
    M, K = rows.shape
    G, _, N = ws[0].shape
    tm, tn = tiles
    if interpret is None:
        interpret = kernel_interpret()
    m_pad = tiling.round_up(M, tm)
    if m_pad != M:
        rows = jnp.pad(rows, ((0, m_pad - M), (0, 0)))
    scalars = _work_items(group_sizes, n_rows=m_pad, tile_m=tm)
    weights = pl.BlockSpec(
        (1, K, tn), lambda n, i, grp, *_: (jnp.minimum(grp[i], G - 1), 0, n))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_groups=G, zero_tail=zero_tail,
                          relu2=relu2, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(N // tn, m_pad // tm + G),
            in_specs=[
                pl.BlockSpec((tm, K),
                             lambda n, i, grp, tile, read, *_: (read[i], 0)),
                *[weights] * len(ws),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, grp, tile, read, *_: (
                    (tile if zero_tail else read)[i], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="moe_gmm",
    )(*scalars, rows, *ws)
    return out[:M] if m_pad != M else out


def grouped_matmul_kernel(rows, w, group_sizes, *, tiles=None, interpret=None):
    """The Pallas path. ``tiles`` (tile_m, tile_n) and ``interpret`` are for
    the tests and a sweep on the chip; the program leaves both to the shape
    and the backend."""
    M, K = rows.shape
    tiles = tiles or _tiles(M, K, w.shape[2], rows.dtype)
    return _launch(rows, (w,), group_sizes, tiles=tiles, interpret=interpret)


def grouped_gate_up_kernel(rows, w_gate, w_up, group_sizes, *, act="silu",
                           zero_tail=True, tiles=None, vmem_limit=None,
                           interpret=None):
    """The Pallas path of :func:`grouped_gate_up`: ONE launch, both products
    over the row tile while it sits in VMEM, ``act`` of :data:`GATE_ACTS` on
    the gate's. ``zero_tail=False`` leaves the
    row tiles past the last group unwritten (:func:`grouped_mlp`). ``tiles``,
    ``vmem_limit`` and ``interpret`` are for the tests and a sweep on the
    chip; the program leaves them to the shape and the backend."""
    if tiles is None:
        *tiles, vmem_limit = _gate_up_tiles(*rows.shape, w_gate.shape[2],
                                            rows.dtype)
    return _launch(rows, (w_gate, w_up), group_sizes, tiles=tiles,
                   vmem_limit=vmem_limit, zero_tail=zero_tail, act=act,
                   interpret=interpret)


def grouped_relu2_kernel(rows, w_up, group_sizes, *, zero_tail=True,
                         tiles=None, interpret=None):
    """The Pallas path of :func:`grouped_relu2`: the product's launch with the
    squared ReLU on its float32 result, at the product's tiles."""
    tiles = tiles or _tiles(*rows.shape, w_up.shape[2], rows.dtype)
    return _launch(rows, (w_up,), group_sizes, tiles=tiles,
                   zero_tail=zero_tail, relu2=True, interpret=interpret)


def _no_vjp(kernel, differentiable: str):
    """``kernel`` as a function that says by name why it will not
    differentiate."""
    @jax.custom_vjp
    def launch(*args):
        return kernel(*args)

    def fwd(*args):
        raise NotImplementedError(
            "the moe_gmm kernel has no backward yet (ROADMAP Reach): "
            f"differentiate ops.grouped_matmul.{differentiable} "
            "(jax.lax.ragged_dot), which is what runs off the TPU")

    launch.defvjp(fwd, lambda res, g: None)
    return launch


_kernel_no_vjp = _no_vjp(grouped_matmul_kernel, "grouped_matmul_xla")
#: the first half's launches by (the gate's activation, or None for the
#: ungated half; whether the tail is written)
_first_half_no_vjp = {
    (act, zero_tail): _no_vjp(
        functools.partial(grouped_relu2_kernel, zero_tail=zero_tail)
        if act is None else
        functools.partial(grouped_gate_up_kernel, act=act,
                          zero_tail=zero_tail),
        "grouped_relu2_xla" if act is None else "grouped_gate_up_xla")
    for act in (None, *GATE_ACTS) for zero_tail in (True, False)}


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


def _first_half(rows, ws, group_sizes, act, zero_tail=True):
    """The launch on the TPU, the XLA composition elsewhere, of the weight
    operands ``ws``: (gate, up) under the gate's ``act``, counted once as a
    gated first half and twice as a product, or (up,) alone with ``act``
    None, the ungated half: one product."""
    use_kernel = jax.default_backend() == "tpu"
    if act is not None:
        if act not in GATE_ACTS:
            raise ValueError(f"gate activation {act!r}: {sorted(GATE_ACTS)} "
                             "are written")
        _kernels.inc("kernels.moe_gate_up_schedule",
                     key="fused" if use_kernel else "xla")
    _kernels.inc("kernels.moe_gmm_schedule", len(ws),
                 key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("moe_gmm"):
            return _first_half_no_vjp[act, zero_tail](rows, *ws, group_sizes)
    if act is None:
        return grouped_relu2_xla(rows, *ws, group_sizes)
    return grouped_gate_up_xla(rows, *ws, group_sizes, act)


def grouped_gate_up(rows, w_gate, w_up, group_sizes, act: str = "silu"):
    """``h`` of the module docstring's contract, ``(M, F)`` in ``rows``'
    dtype: float32 accumulation, activation and product on either path, one
    launch on the TPU."""
    return _first_half(rows, (w_gate, w_up), group_sizes, act)


def grouped_relu2(rows, w_up, group_sizes):
    """The ungated ``h`` of the module docstring's contract, ``(M, F)`` in
    ``rows``' dtype: float32 accumulation, ReLU and square on either path, one
    launch on the TPU."""
    return _first_half(rows, (w_up,), group_sizes, None)


def grouped_mlp(rows, w_gate, w_up, w_down, group_sizes, act: str = "silu"):
    """The experts' whole MLP, ``out[r] = h[r] @ w_down[g]`` with ``h`` of
    the module docstring's contract, the gated one under the gate's ``act``
    or, where ``w_gate`` is None, the ungated: ``(M, K)``, zero past the last
    group. Two launches on
    the TPU, and because ``h`` lives only between them, the first leaves the
    row tiles past the last group unwritten: the second reads no row tile
    beyond the last group's (:func:`_work_items`)."""
    if w_gate is None:
        h = _first_half(rows, (w_up,), group_sizes, None, zero_tail=False)
    else:
        h = _first_half(rows, (w_gate, w_up), group_sizes, act,
                        zero_tail=False)
    return grouped_matmul(h, w_down, group_sizes)
