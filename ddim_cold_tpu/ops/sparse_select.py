"""The learned per-query key selection of DeepSeek-style sparse attention
(DSA): an *indexer* scores every visible key for every query, and the query
attends only to the ``top`` best of them.

With index queries ``q (n, L, J, D)`` (J heads), ONE index key a token ``k (n,
L, D)``, per-head weights ``w (n, L, J)`` float32, positions in raster order:

    I[t, s] = Σ_j w[t, j] · ReLU(q[t, j] · k[s])              float32
    τ[t]    = the ``top``-th largest of {I[t, s] : s ≤ t}, with multiplicity
              (−∞ where t sees fewer than ``top`` keys)
    S[t]    = {s ≤ t : I[t, s] ≥ τ[t]}

so a query that sees at most ``top`` keys keeps them all, any other exactly
``top`` of them — but for exact ties at τ, which are ALL kept (the one
departure from a ``topk``, whose tie order is an implementation's; −0.0 ties
with +0.0 as floats compare).

One function, :func:`select`, gives S as what the attention kernel reads
(``ops.flash_attention.selected_attention``): an int8 mask ``(n, L⁺, L⁺)``, 1
where s ∈ S[t], L⁺ the token count rounded up to whole attention blocks
(:func:`mask_length`), zeros past the sequence. The backend decides what runs
(``kernels.dsa_select_schedule``, ``kernel`` | ``xla``, +1 a trace):

* on the TPU two launches and NO SORT. ``pallas_call(name="dsa_index")``:
  the scores by q block and key chunk on the MXU, one ``(bq, D) × (D, bkv)``
  product an index head, ReLU, weight and sum on the VPU in float32; chunks
  above the diagonal are neither fetched, computed nor written.
  ``pallas_call(name="dsa_select")``: a block of rows' scores resident in
  VMEM, each taken to the order-preserving int32 image of its float (``b ^
  ((b >> 31) & 0x7fffffff)``; keys a row does not see to INT_MIN, which is no
  number's image), and τ's image built bit by bit from the top — 32 passes of
  compare-and-count over the resident block, each deciding one bit: the
  largest T with ``#{key ≥ T} ≥ top`` is exactly the ``top``-th largest key.
  The float32 scores cross HBM once out and once in (340 MB each way at
  9,217 tokens: under a millisecond), the mask once out.
* anywhere else :func:`select_xla`, plain ``jax.numpy`` with ``lax.top_k`` for
  τ: the tests' oracle for the kernels. The set is piecewise constant in its
  inputs, so nothing differentiates through it on either path.
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
    _masked_blocks, _sds, kernel_interpret, per_device, rows_spec)
from ddim_cold_tpu.utils import profiling

#: which path each trace of the selection took (``kernels.dsa_select_schedule``)
_kernels = metrics.scope("kernels")

_INT_MIN = -2 ** 31
#: rows of scores resident at a time in ``dsa_select``: int8's sublane tile
_SELECT_ROWS = 32


def mask_length(n_tokens: int, dtype) -> int:
    """L⁺: the token count in whole blocks of the selected attention forward
    (which reads the mask in ``(block_q, block_kv)`` tiles)."""
    block, _ = _masked_blocks(n_tokens, dtype)
    return tiling.round_up(n_tokens, block)


def index_scores_xla(q, k, w):
    """I ``(n, L, L)`` float32 of the module docstring, every pair."""
    dots = jnp.einsum("btjd,bsd->bjts", q, k,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bjts,btj->bts", jnp.maximum(dots, 0.0),
                      w.astype(jnp.float32))


def threshold_mask_xla(scores, top: int):
    """S as a bool ``(n, L, L)`` from I ``(n, L, L)``: τ by ``lax.top_k``."""
    L = scores.shape[-1]
    sees = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]
    seen = jnp.where(sees, scores, -jnp.inf)
    if top >= L:
        return jnp.broadcast_to(sees, scores.shape)
    tau = jax.lax.top_k(seen, top)[0][..., -1:]
    return (seen >= tau) & sees


def select_xla(q, k, w, top: int, length: int):
    """The mask of :func:`select` in plain ``jax.numpy``."""
    L = q.shape[1]
    keep = threshold_mask_xla(index_scores_xla(q, k, w), top)
    return jnp.pad(keep.astype(jnp.int8),
                   ((0, 0), (0, length - L), (0, length - L)))


def _index_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, dim: int,
                  bq: int, bkv: int, n_valid: int):
    """One (image, q block, key chunk) program of the index scores; chunks
    above the diagonal (whose K index map re-addresses the diagonal chunk)
    do nothing and leave their tile of the result unwritten."""
    i, c = pl.program_id(1), pl.program_id(2)
    last = jnp.minimum(i * bq + bq - 1, n_valid - 1) // bkv

    @pl.when(c <= last)
    def _scores():
        k, w = k_ref[0], w_ref[0]
        acc = jnp.zeros((bq, bkv), jnp.float32)
        for j in range(heads):
            dots = jax.lax.dot_general(
                q_ref[0, :, j * dim:(j + 1) * dim], k,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            acc += w[:, j:j + 1] * jnp.maximum(dots, 0.0)
        o_ref[0] = acc


def _index_call(q, k, w, *, heads, dim, block, length, interpret):
    """``q (rows, L, J·D)``, ``k (rows, L, D)``, ``w (rows, L, J)`` float32,
    each where its projection wrote it, the token axis ending inside the last
    block; the scores ``(rows, L⁺, L⁺)`` float32, tiles above the diagonal
    unspecified."""
    rows, n_valid, _ = q.shape
    blocks = length // block

    def diagonal(i):
        return jnp.minimum(i * block + block - 1, n_valid - 1) // block

    with profiling.scope("sparse_select/dsa_index"):
        return pl.pallas_call(
            functools.partial(_index_kernel, heads=heads, dim=dim, bq=block,
                              bkv=block, n_valid=n_valid),
            grid=(rows, blocks, blocks),
            in_specs=[
                pl.BlockSpec((1, block, heads * dim), lambda b, i, c: (b, i, 0)),
                pl.BlockSpec((1, block, dim),
                             lambda b, i, c: (b, jnp.minimum(c, diagonal(i)), 0)),
                pl.BlockSpec((1, block, heads), lambda b, i, c: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block, block),
                lambda b, i, c: (b, i, jnp.minimum(c, diagonal(i)))),
            out_shape=_sds((rows, length, length), jnp.float32, q),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="dsa_index",
        )(q, k, w)


def _select_kernel(s_ref, o_ref, key_ref, *, top: int, rows: int,
                   n_valid: int):
    """One block of ``rows`` query rows: their scores to keys, τ's key by 32
    passes of compare-and-count, the mask out."""
    x = s_ref[0]
    row = (pl.program_id(1) * rows
           + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
    sees = ((jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) <= row)
            & (row < n_valid))
    x = jnp.where(x == 0.0, 0.0, x)  # −0.0 ties with +0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key_ref[...] = jnp.where(sees, bits ^ ((bits >> 31) & 0x7FFFFFFF),
                             _INT_MIN)

    def enough(candidate):  # (rows, 1): at least ``top`` keys reach it
        reach = jnp.where(key_ref[...] >= candidate, 1, 0)
        return jnp.sum(reach, axis=-1, keepdims=True) >= top

    # the sign bit first (INT_MIN + 2^31 is 0), then bits 30 … 0
    t = jnp.where(enough(jnp.zeros((rows, 1), jnp.int32)), 0, _INT_MIN)
    for bit in range(30, -1, -1):
        t = jnp.where(enough(t + (1 << bit)), t + (1 << bit), t)
    o_ref[0] = jnp.where((key_ref[...] >= t) & sees, 1, 0).astype(jnp.int8)


def _select_call(scores, *, top, n_valid, interpret):
    """``scores (rows, L⁺, L⁺)`` float32, of which ``n_valid`` tokens are the
    sequence → the int8 mask of the same shape."""
    n, length, _ = scores.shape
    rows = _SELECT_ROWS if length % _SELECT_ROWS == 0 else length
    block = pl.BlockSpec((1, rows, length), lambda b, r: (b, r, 0))
    with profiling.scope("sparse_select/dsa_select"):
        return pl.pallas_call(
            functools.partial(_select_kernel, top=top, rows=rows,
                              n_valid=n_valid),
            grid=(n, length // rows),
            in_specs=[block],
            out_specs=block,
            out_shape=_sds(scores.shape, jnp.int8, scores),
            scratch_shapes=[pltpu.VMEM((rows, length), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="dsa_select",
        )(scores)


def select_kernel(q, k, w, top: int, length: int):
    """The mask of :func:`select` by the two launches, whatever the backend
    (interpreted off the TPU)."""
    n, L, heads, dim = q.shape
    block, _ = _masked_blocks(L, q.dtype)
    interpret = kernel_interpret()
    spec = rows_spec(n)

    def launches(q, k, w):
        scores = _index_call(q, k, w, heads=heads, dim=dim, block=block,
                             length=length, interpret=interpret)
        return _select_call(scores, top=top, n_valid=L, interpret=interpret)

    return per_device(launches, (spec, spec, spec), spec)(
        q.reshape(n, L, heads * dim), k, w.astype(jnp.float32))


def select(q, k, w, top: int):
    """S of the module docstring as the int8 mask ``(n, L⁺, L⁺)`` that
    ``selected_attention`` reads. ``q (n, L, J, D)``, ``k (n, L, D)``, ``w (n,
    L, J)``. The kernels on the TPU, :func:`select_xla` anywhere else."""
    length = mask_length(q.shape[1], q.dtype)
    on_chip = jax.default_backend() == "tpu"
    _kernels.inc("kernels.dsa_select_schedule",
                 key="kernel" if on_chip else "xla")
    run = select_kernel if on_chip else select_xla
    return run(q, k, w, top, length)
