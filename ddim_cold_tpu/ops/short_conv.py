"""The short convolution of a mixer (Mamba-1 and Mamba-2's ``conv1d``, a Kimi
delta layer's three): depthwise and causal over ``u (n, L, d)``, ``taps``
weights a channel, then SiLU, and for a delta layer's q and k the L2 norm of
each head's channels behind it:

    c_t = b + Σ_j w_j ⊙ u_{t−taps+1+j}      u_s = 0 for s < 0, j = 0 … taps−1
    y_t = round(SiLU(c_t))
    out = y_t,  or  round(y_t,h · rsqrt(Σ_c y_t,h,c² + 1e-6))  a head h of
    ``l2_head_dim`` channels

float32 inside, ``round`` to the result's dtype: the sum in the order j = 0,
1, …, the norm taken of the ROUNDED activation (a delta layer norms what the
convolution's module returned).

* :func:`causal_conv_xla` is those lines in plain JAX: a zero-padded float32
  copy of ``u`` and ``taps`` shifted slices of it. Any shape, differentiable;
  what runs off the TPU and the tests' oracle. On the TPU XLA copies the
  padded and shifted float32 arrays out before it fuses the rest, and the
  projection before it writes float32 for them (sixteen ``(16,385, 4,096)``
  copies a forward of Kimi's five layers: PERF.md, PR 46).
* the launch (``pallas_call(name="causal_conv")``, ``%causal_conv`` in a
  device trace) reads a ``(T, C)`` block of ``u`` in ``u``'s dtype and writes
  the block of the result: one read and one write of the array. A program is
  one (image, channel block, token block) and walks the token blocks in
  order; inside, strips of :data:`STRIP` rows are cast to float32 in
  registers, each shifted copy a sublane rotation of the strip behind the
  eight rows before it, which the loop carries from strip to strip and a
  float32 VMEM scratch from block to block (zeros at an image's first). The
  norm is one lane-group sum a row and head. A sequence that ends inside the
  last block is left to the pipeline (rows past the end are computed from
  whatever lies there and never written back; the convolution looks only
  backwards, so they reach no row that is); nothing is padded in HBM.

Differentiation: the XLA form is plain JAX. The launch carries a
``custom_vjp`` whose backward is the XLA form's (elementwise, cheap).
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
    kernel_interpret, per_device, rows_spec)

#: under the root of a head's sum of squares (Kimi Linear's ``l2norm``)
L2_EPS = 1e-6
#: rows one strip of the launch holds in registers: two bfloat16 tiles
STRIP = 32
#: rows before a strip that it carries: one float32 sublane tile, which
#: bounds the taps the launch takes at ``_CARRY + 1``
_CARRY = 8
#: the most channels a block, and the most bytes a block of ``u`` and one of
#: the result together (the pipeline holds two of each: half of the 16 MiB a
#: launch may use); 1,024 rows of 1,024 channels in bfloat16. Measured on the
#: chip inside each stack's own mixer (PERF.md, PR 46), strip × rows ×
#: channels, ms a mixer at the published shape, the XLA form first:
#:
#:               XLA     32 × 1,024 × 1,024   64 × 1,024 × 1,024   32 × 1,024 × 512
#:   Kimi       32.27         21.75                21.66                22.27
#:   Nemotron   35.50         31.94                32.15                31.92
#:   Jamba       3.315         3.305                3.330                3.281
#:
#: (the norm wants many rows and heads in flight, the taps few enough to stay
#: in registers). Jamba's ``(4, 1,025, 5,120)``, level inside its mixer
#: alone, gains in its cell (174.4 → 167.5 ms a step), so no bound on ``L``
#: or ``d`` keeps a shape off the launch.
_MAX_CHANNELS = 1024
_BLOCK_BYTES = 4 << 20
#: what starting a program costs, in rows of a block (≈ 0.35 µs a grid step
#: against ≈ 5 ns for a row's 1,024 channels in and out)
_STEP_ROWS = 64

#: which path each trace of the convolution took
#: (``kernels.causal_conv_schedule``)
_kernels = metrics.scope("kernels")


def _check(u, w, b, l2_head_dim) -> tuple:
    """``(n, L, d, taps)`` of ``u (n, L, d)``, ``w (taps, d)``, ``b (d,)`` or
    None."""
    if (u.ndim != 3 or w.ndim != 2 or w.shape[1] != u.shape[2]
            or (b is not None and b.shape != u.shape[2:])
            or (l2_head_dim and u.shape[2] % l2_head_dim)):
        raise ValueError(
            f"u {u.shape}, w {w.shape}, b {None if b is None else b.shape}, "
            f"l2_head_dim {l2_head_dim}: the short convolution takes u (n, L, "
            "d), w (taps, d), b (d,) or none, and heads that divide d")
    return (*u.shape, w.shape[0])


def causal_conv_xla(u, w, b=None, *, l2_head_dim: int | None = None,
                    dtype=None):
    """The module docstring's equations in plain JAX. ``u: (n, L, d)``;
    ``w: (taps, d)``; ``b: (d,)`` or None. Returns ``(n, L, d)`` in ``dtype``
    (``u``'s unless given)."""
    n, L, d, taps = _check(u, w, b, l2_head_dim)
    dtype = u.dtype if dtype is None else dtype
    w = w.astype(jnp.float32)
    past = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[j] * past[:, j:j + L] for j in range(taps))
    if b is not None:
        conv = conv + b.astype(jnp.float32)
    y = jax.nn.silu(conv).astype(dtype)
    if l2_head_dim:
        heads = y.astype(jnp.float32).reshape(n, L, -1, l2_head_dim)
        heads = heads * jax.lax.rsqrt(
            jnp.sum(heads * heads, -1, keepdims=True) + L2_EPS)
        y = heads.reshape(n, L, d).astype(dtype)
    return y


def _channel_block(d: int) -> int:
    """The widest block of whole lane groups, at most :data:`_MAX_CHANNELS`,
    that divides ``d`` (a multiple of the lane width)."""
    return max(c for c in range(tiling.LANE, _MAX_CHANNELS + 1, tiling.LANE)
               if d % c == 0)


def _token_block(L: int, row_bytes: int) -> int:
    """Rows a block, in whole strips, for a sequence of ``L`` whose rows take
    ``row_bytes`` a block in and out: the size within :data:`_BLOCK_BYTES`
    that costs least, a block's rows and :data:`_STEP_ROWS` more for each,
    counted whether they hold tokens or lie past the end (16,385 = 32 × 512 +
    1 in blocks of 512 leaves the last one row), the larger of two that
    tie."""
    most = max(STRIP, min(_BLOCK_BYTES // row_bytes // STRIP * STRIP,
                          tiling.round_up(L, STRIP)))
    return min(range(STRIP, most + 1, STRIP),
               key=lambda T: (-(-L // T) * (T + _STEP_ROWS), -T))


def kernel_admits(d: int, taps: int, l2_head_dim: int | None = None) -> bool:
    """Shapes the kernel tiles: channels in whole lane groups, the taps
    within the rows a strip carries, heads of whole lane groups inside one
    channel block. Others take the XLA path (and count as such)."""
    return (d % tiling.LANE == 0 and 1 <= taps <= _CARRY + 1
            and (not l2_head_dim or (l2_head_dim % tiling.LANE == 0
                                     and _channel_block(d) % l2_head_dim == 0)))


def _strip(prev, cur, w, b, *, l2_head_dim, dtype):
    """One strip's rows of the result, ``(R, C)`` in ``dtype``. ``prev``:
    ``(_CARRY, C)`` float32, the rows before it; ``cur``: ``(R, C)`` float32;
    ``w``: ``(taps, C)``; ``b``: ``(1, C)`` or None."""
    taps = w.shape[0]
    both = jnp.concatenate([prev, cur], axis=0)
    conv = None
    for j in range(taps):
        back = taps - 1 - j
        rows = pltpu.roll(both, back, 0)[_CARRY:] if back else cur
        conv = w[j:j + 1] * rows if conv is None else conv + w[j:j + 1] * rows
    if b is not None:
        conv = conv + b
    y = jax.nn.silu(conv).astype(dtype)
    if not l2_head_dim:
        return y
    heads = []
    for at in range(0, y.shape[1], l2_head_dim):
        head = y[:, at:at + l2_head_dim].astype(jnp.float32)
        heads.append((head * jax.lax.rsqrt(
            jnp.sum(head * head, -1, keepdims=True) + L2_EPS)).astype(dtype))
    return jnp.concatenate(heads, axis=1)


def _kernel(u_ref, w_ref, *rest, l2_head_dim):
    """One (image, channel block, token block) program. ``u_ref``/``o_ref``:
    (1, T, C); ``w_ref``: (taps, C) float32; ``b_ref``, where the mixer has a
    bias: (1, C) float32; scratch ``carry_ref`` (_CARRY, C) float32: the last
    rows of the token block before."""
    *b_ref, o_ref, carry_ref = rest
    T = u_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    w = w_ref[...]
    b = b_ref[0][...] if b_ref else None

    def step(s, prev):
        at = pl.ds(pl.multiple_of(s * STRIP, STRIP), STRIP)
        cur = u_ref[0, at, :].astype(jnp.float32)
        o_ref[0, at, :] = _strip(prev, cur, w, b, l2_head_dim=l2_head_dim,
                                 dtype=o_ref.dtype)
        return cur[STRIP - _CARRY:]

    carry_ref[...] = jax.lax.fori_loop(0, T // STRIP, step, carry_ref[...])


def _conv_call(u, w, *b, l2_head_dim, dtype, interpret):
    n, L, d = u.shape
    C = _channel_block(d)
    T = _token_block(L, C * (u.dtype.itemsize + jnp.dtype(dtype).itemsize))
    act = pl.BlockSpec((1, T, C), lambda i, c, t: (i, t, c))
    cols = lambda rows: pl.BlockSpec((rows, C), lambda i, c, t: (0, c))
    return pl.pallas_call(
        functools.partial(_kernel, l2_head_dim=l2_head_dim),
        grid=(n, d // C, pl.cdiv(L, T)),
        in_specs=[act, cols(w.shape[0])] + [cols(1)] * len(b),
        out_specs=act,
        out_shape=jax.ShapeDtypeStruct(u.shape, dtype),
        scratch_shapes=[pltpu.VMEM((_CARRY, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="causal_conv",
    )(u, w, *b)


def causal_conv_kernel(u, w, b=None, *, l2_head_dim: int | None = None,
                       dtype=None, interpret=None):
    """The Pallas path, arguments as :func:`causal_conv_xla`. ``interpret``
    is for the tests; the program leaves it to the backend."""
    _, _, d, taps = _check(u, w, b, l2_head_dim)
    if not kernel_admits(d, taps, l2_head_dim):
        raise NotImplementedError(
            f"causal_conv tiles channels in whole lane groups of "
            f"{tiling.LANE}, at most {_CARRY + 1} taps, and heads of whole "
            f"lane groups: {d} channels, {taps} taps, heads of {l2_head_dim}")
    if interpret is None:
        interpret = kernel_interpret()
    f32 = jnp.float32
    bias = () if b is None else (b.astype(f32).reshape(1, d),)
    spec = rows_spec(u.shape[0])
    whole = jax.sharding.PartitionSpec()
    return per_device(
        functools.partial(_conv_call, l2_head_dim=l2_head_dim,
                          dtype=u.dtype if dtype is None else dtype,
                          interpret=interpret),
        (spec, whole) + (whole,) * len(bias), spec,
    )(u, w.astype(f32), *bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_vjp(u, w, b, l2_head_dim, dtype):
    return causal_conv_kernel(u, w, b, l2_head_dim=l2_head_dim, dtype=dtype)


def _vjp_fwd(u, w, b, l2_head_dim, dtype):
    return _kernel_vjp(u, w, b, l2_head_dim, dtype), (u, w, b)


def _vjp_bwd(l2_head_dim, dtype, operands, ct):
    return jax.vjp(functools.partial(
        causal_conv_xla, l2_head_dim=l2_head_dim, dtype=dtype), *operands
    )[1](ct)


_kernel_vjp.defvjp(_vjp_fwd, _vjp_bwd)


def causal_conv(u, w, b=None, *, l2_head_dim: int | None = None, dtype=None):
    """``out`` of the module docstring's equations, ``(n, L, d)`` in
    ``dtype`` (``u``'s unless given); float32 sums, SiLU and norm on either
    path."""
    _, _, d, taps = _check(u, w, b, l2_head_dim)
    dtype = u.dtype if dtype is None else dtype
    use_kernel = (jax.default_backend() == "tpu"
                  and kernel_admits(d, taps, l2_head_dim))
    _kernels.inc("kernels.causal_conv_schedule",
                 key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("causal_conv"):
            return _kernel_vjp(u, w, b, l2_head_dim, dtype)
    return causal_conv_xla(u, w, b, l2_head_dim=l2_head_dim, dtype=dtype)
