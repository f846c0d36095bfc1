"""The token-wise half of a ViT block as two weight-resident Pallas kernels.

Outside attention a pre-LN block is row-wise: LayerNorm → qkv GEMM before it,
proj GEMM + residual → LayerNorm → fc1 → GELU → fc2 + residual after it. XLA
runs that as separate passes over the ``(B, N, C)`` activation (at the 200px
sampler's n = 288 one pass is 369 MB: PERF.md section 6, PR 32). Here each
half is ONE launch whose weights stay in VMEM for the whole launch and whose
row blocks stream through once, so every activation outside attention
crosses HBM once:

* :func:`ln_qkv` (``pallas_call(name="ln_qkv")``): x → ``norm1`` → ``· W_qkv
  (+ b_qkv)`` → the packed ``(B, N, 3C)`` projection, q, k, v at column
  offsets 0, C, 2C, where ``flash_attention_qkv`` reads them.
* :func:`block_tail` (``pallas_call(name="block_tail")``): the context and x
  → ``ctx · W_proj + b_proj + x`` → ``norm2`` → ``· W_fc1 + b_fc1`` →
  :func:`~ddim_cold_tpu.ops.quant.gelu_exact` → ``· W_fc2 + b_fc2`` → ``+ x``.
  The ``(rows, hidden)`` activation never exists in HBM.

Both read and write the ``(B, N, C)`` arrays where they lie, in ``(1, rows,
C)`` blocks: nothing is reshaped (a ``(B·N, C)`` view of a token axis that is
no multiple of the sublane tile is a copy on the chip) and nothing is padded;
the last row block of an image may end past the array, and since every step is
row-wise what it reads there stays in rows that are never written.

Precision is the XLA composition's (``models/vit.Block``): LayerNorm's
statistics and affine in float32 (ε as given, flax's fast variance), GEMM
operands in the model's dtype with float32 accumulation, GELU in float32, the
residual stream in the model's dtype; a Dense's bias is added on the float32
accumulator before the one cast, as ``ops/quant.mlp_pallas`` does. The result
differs from the composition's by rounding order at most.

Inference only: :func:`~ddim_cold_tpu.models.vit.Block` takes this path on
``deterministic=True`` alone. Differentiating through it is total all the
same — a ``custom_vjp`` whose backward is the VJP of the XLA reference of the
same function (:func:`ln_qkv_reference`, :func:`block_tail_reference`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.flash_attention import (
    _SCOPED_VMEM_BYTES, _sds, kernel_interpret, per_device, rows_spec)
from ddim_cold_tpu.ops.quant import gelu_exact, gelu_exact_newton

#: the largest row block tried; halved until the VMEM model admits it
_MAX_ROWS = 4096

#: the shortest image the kernels take. A block never spans images, so a short
#: image is one short program, and a program's fixed cost then outweighs its
#: work. Device time of a whole bf16 forward through the kernels over the same
#: through XLA's composition, 100–160 k rows in flight on a v5e (PERF.md
#: section 6, PR 32), by tokens an image, at widths 256 / 384:
#:
#:   tokens          65    197   257   401   577   626   1,025  2,501
#:   dense, C 256     -    1.05  0.98  0.95  1.04   -      -      -
#:   dense, C 384   1.15   1.11  1.01  0.97  1.02  0.99    -      -
#:   flash, C 256     -     -     -    0.75  0.87   -     0.97   0.95
#:   flash, C 384     -     -     -    0.94  0.96  0.93    -      -
#:
#: (65: the 64px sampler at n = 1,024 by the host's clock; 626: the 200px/p8
#: model, width 384, 12 heads; 2,501: the sampler cell). The crossover lies
#: between 197 and 401 tokens; from 401 up the kernels win wherever the flash
#: kernel reads their packed projection in place, and stay within ±4 % under
#: dense attention, whose transposes of a packed projection XLA's own GEMM
#: avoids. 512 keeps every configuration of the repo on its measured side
#: (65 and 257 tokens: XLA; 626 and 2,501: the kernels); nothing was measured
#: with the flash kernel between 65 and 401.
_MIN_ROWS = 512


def _vmem_bytes(rows: int, C: int, hidden: int, itemsize: int) -> int:
    """Scoped VMEM the wider of the two kernels needs at ``rows`` rows a
    block: the weights (double-buffered like every pipelined operand, though
    their block index never moves), the double-buffered row blocks in and out
    and the float32 tiles the compiler keeps alive — for ``ln_qkv`` two of
    ``(rows, C)`` (it casts the accumulator as it stores), for ``block_tail``
    three of ``(rows, hidden)`` and one of ``(rows, C)``. An upper bound,
    within 0.4 KB a row of what the v5e compiler reports where it refuses
    (bf16, C 256, hidden 256 and 1024, 2,048 to 4,096 rows: 7,048 and 15,421
    bytes a row against 7,168 and 16,384 here) —
    tests/test_chip_compile.py compiles what this admits."""
    ln_qkv = 2 * 3 * C * C * itemsize + rows * C * (8 * itemsize + 8)
    tail = (2 * (C + 2 * hidden) * C * itemsize
            + rows * (C * (6 * itemsize + 4) + 12 * hidden))
    return max(ln_qkv, tail) + (1 << 19)


def _mesh_admits() -> bool:
    """Whether the ambient mesh is one ``per_device`` can place a Mosaic
    launch on with the weights whole: no mesh; a region manual over EVERY
    axis of its mesh (the bare launch, on local rows); or, outside any manual
    region, a mesh whose only axis of more than one device is ``data`` (every
    device launches on its own images, weights replicated). What is left is
    GSPMD's to partition, and it cannot partition a Mosaic kernel: inside
    ``parallel/pipeline.py``'s shard_map, manual over ``pipe`` (and ``data``,
    ``seq``) with ``model`` / ``expert`` left automatic, lowering refuses the
    launch even where that axis has one device; on a tensor-parallel mesh
    shard-mapping it would all-gather the Megatron-sharded qkv, proj, fc1 and
    fc2 and recompute them on every model shard, where the composition's
    GEMMs are partitioned."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return True
    if mesh.manual_axes:
        return set(mesh.manual_axes) == set(mesh.axis_names)
    return all(name == "data" or size == 1
               for name, size in mesh.shape.items())


def row_block(tokens: int, C: int, hidden: int, dtype) -> int | None:
    """Rows of one block for an image of ``tokens`` rows, or ``None`` where
    the kernels do not apply: ``C`` and ``hidden`` must fill whole lanes, the
    image must have :data:`_MIN_ROWS` rows, the ambient mesh must leave the
    launch and its weights whole on each device (:func:`_mesh_admits`), and
    the weights plus one row block must fit the scoped VMEM. The image is cut
    into the fewest blocks the VMEM model admits (fewer programs: a program's
    fixed cost is a few tenths of a microsecond against a few microseconds of
    work), of equal size rounded up to the dtype's sublane tile, so the ragged
    edge wastes less than one tile a block (2,501 tokens in bf16 at width 256:
    2 × 1,264)."""
    if (C % tiling.LANE or hidden % tiling.LANE or tokens < _MIN_ROWS
            or not _mesh_admits()):
        return None
    isz = jnp.dtype(dtype).itemsize
    unit = tiling.sublane_unit(dtype)
    most = _MAX_ROWS
    while most >= unit and _vmem_bytes(most, C, hidden, isz) > _SCOPED_VMEM_BYTES:
        most //= 2
    if most < unit:
        return None
    return tiling.round_up(pl.cdiv(tokens, pl.cdiv(tokens, most)), unit)


# --- the mathematics, once: the kernels' bodies and the XLA reference -------

def _layer_norm(x, scale, bias, eps, dtype):
    """``nn.LayerNorm(epsilon=eps, dtype=dtype)`` on the last axis: float32
    statistics (mean of squares less the squared mean, floored at 0), float32
    affine, one cast."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean, 0.0)
    y = (xf - mean) * (jax.lax.rsqrt(var + eps) * scale) + bias
    return y.astype(dtype)


def _dense(x, w, b):
    """``nn.Dense(dtype=x.dtype)``: operands in ``x``'s dtype, float32
    accumulation, the bias on the accumulator, one cast."""
    y = jax.lax.dot_general(x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def _add(a, b):
    """``a + b`` in ``a``'s dtype, spelled through float32 (the v5e's vector
    unit has no bfloat16 arithmetic; the sum rounds the same)."""
    return (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(a.dtype)


def ln_qkv_reference(x, scale, bias, w, b=None, *, eps):
    """XLA composition of :func:`ln_qkv`: the tests' oracle and, through
    ``jax.vjp``, the kernel's backward."""
    return _dense(_layer_norm(x, scale, bias, eps, x.dtype), w, b)


def block_tail_reference(ctx, x, w_proj, b_proj, scale, bias, w_fc1, b_fc1,
                         w_fc2, b_fc2, *, eps, gelu=gelu_exact):
    """XLA composition of :func:`block_tail` (whose body passes its own
    spelling of the same GELU, ``ops/quant.gelu_exact_newton``)."""
    x = _add(x, _dense(ctx, w_proj, b_proj))
    h = gelu(_dense(_layer_norm(x, scale, bias, eps, x.dtype), w_fc1, b_fc1))
    return _add(x, _dense(h, w_fc2, b_fc2))


def _ln_qkv_kernel(x_ref, *refs, eps):
    *consts, o_ref = refs  # scale, bias, w and, where the Dense has one, b
    o_ref[...] = ln_qkv_reference(x_ref[...], *(r[...] for r in consts),
                                  eps=eps)


def _block_tail_kernel(ctx_ref, x_ref, *refs, eps):
    *consts, o_ref = refs
    o_ref[...] = block_tail_reference(
        ctx_ref[...], x_ref[...], *(r[...] for r in consts), eps=eps,
        gelu=gelu_exact_newton)


# --- the launches -----------------------------------------------------------

def _launch(kernel, name, acts, consts, out_width, rows, reuse=None):
    """One launch over ``(B, N, ·)`` activations ``acts`` in ``(1, rows, ·)``
    blocks, grid (images, row blocks); ``consts`` (weights as ``(K, N)``,
    vectors as ``(1, N)``) ride whole-array blocks whose index never moves,
    so the pipeline fetches them once. ``reuse`` names the activation whose
    buffer the result may take (a program reads its block of it before it
    writes the same block). Under a multi-device ambient mesh every device
    launches on its own images (``per_device``)."""
    B, N, _ = acts[0].shape

    def call(*arrays):
        like = arrays[0]  # this device's images
        row = lambda width: pl.BlockSpec(  # noqa: E731
            (None, rows, width), lambda b, i: (b, i, 0))
        whole = lambda a: pl.BlockSpec(a.shape, lambda b, i: (0, 0))  # noqa: E731
        return pl.pallas_call(
            kernel,
            grid=(like.shape[0], pl.cdiv(N, rows)),
            in_specs=[row(a.shape[-1]) for a in arrays[:len(acts)]]
            + [whole(a) for a in arrays[len(acts):]],
            out_specs=row(out_width),
            out_shape=_sds((like.shape[0], N, out_width), like.dtype, like),
            input_output_aliases={} if reuse is None else {reuse: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=kernel_interpret(),
            name=name,
        )(*arrays)

    spec = rows_spec(B)
    return per_device(call, (spec,) * len(acts) + (P(),) * len(consts),
                      spec)(*acts, *consts)


def _vec(v):
    return v.astype(jnp.float32)[None, :]


def _ln_qkv(x, scale, bias, w, b, eps, rows):
    """``norm1`` then the qkv projection of ``x (B, N, C)`` → ``(B, N, 3C)``
    in ``x``'s dtype, ``rows`` (:func:`row_block`) rows a block. ``scale``,
    ``bias`` are the LayerNorm's, ``w (C, 3C)`` and ``b (3C,)`` or ``None``
    the Dense's, as the parameter tree holds them."""
    consts = [_vec(scale), _vec(bias), w.astype(x.dtype)]
    if b is not None:
        consts.append(_vec(b))
    return _launch(functools.partial(_ln_qkv_kernel, eps=eps),
                   "ln_qkv", (x,), consts, w.shape[1], rows)


ln_qkv = jax.custom_vjp(_ln_qkv, nondiff_argnums=(5, 6))


def _ln_qkv_fwd(*args):
    return _ln_qkv(*args), args[:5]


def _ln_qkv_bwd(eps, rows, res, g):
    return jax.vjp(functools.partial(ln_qkv_reference, eps=eps), *res)[1](g)


ln_qkv.defvjp(_ln_qkv_fwd, _ln_qkv_bwd)


def _block_tail(ctx, x, w_proj, b_proj, scale, bias, w_fc1, b_fc1, w_fc2,
               b_fc2, eps, rows):
    """Everything of a block after attention: the context ``ctx (B, N, C)``
    and the block's input ``x`` → the block's output, ``rows`` rows a block.
    ``scale``, ``bias`` are ``norm2``'s; the three Denses' kernels and biases
    as the parameter tree holds them."""
    dt = x.dtype
    consts = [w_proj.astype(dt), _vec(b_proj), _vec(scale), _vec(bias),
              w_fc1.astype(dt), _vec(b_fc1), w_fc2.astype(dt), _vec(b_fc2)]
    return _launch(functools.partial(_block_tail_kernel, eps=eps),
                   "block_tail", (ctx, x), consts, x.shape[-1], rows, reuse=1)


block_tail = jax.custom_vjp(_block_tail, nondiff_argnums=(10, 11))


def _block_tail_fwd(*args):
    return _block_tail(*args), args[:10]


def _block_tail_bwd(eps, rows, res, g):
    return jax.vjp(functools.partial(block_tail_reference, eps=eps), *res)[1](g)


block_tail.defvjp(_block_tail_fwd, _block_tail_bwd)
