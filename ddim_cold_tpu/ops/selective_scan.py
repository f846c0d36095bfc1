"""The selective scan of a Mamba-1 mixer (Gu & Dao 2023, eq. 2 with the
zero-order-hold discretisation of its reference code), gated:

    h_t = exp(Δ_t ⊗ A) ⊙ h_{t-1} + (Δ_t ⊙ u_t) ⊗ B_t        h_0 = 0
    y_t = (h_t C_t + D ⊙ u_t) ⊙ SiLU(z_t)

over ``d`` channels × ``s`` states, sequential in the ``L`` tokens: no GEMM in
it, so it rides the VPU. One function, :func:`selective_scan`; the backend
decides what runs. On the TPU a Pallas kernel (``pallas_call(name=
"ssm_scan")``, ``%ssm_scan`` in a device trace) whose grid is images × blocks
of channels × chunks of tokens: the ``(s, block)`` float32 state stays on the
chip — in registers across a chunk's tokens, in VMEM scratch from one chunk
to the next — and u, Δ, z are read and y written exactly once. Anywhere else,
and as the tests' oracle, :func:`selective_scan_xla`: a plain ``lax.scan``
over tokens (written as ``lax.associative_scan`` it would hold ``(n, L, d,
s)`` float32 operands: 1.3 GB each at 4 × 1,025 × 5,120 × 16).

Layout in the kernel: channels on the lanes, states on the sublanes, so the
recurrence is dense over ``(s, block)`` tiles. A token's Δ and u are one row,
broadcast down the sublanes; its B and C are one column, broadcast along the
lanes — for that B and C arrive transposed in groups of 16 tokens
(``(n, L/16, s, 16)``, a few KB a launch, made by XLA). Tokens are taken 16
at a time (one packed bfloat16 tile): the rows' loads, Δ·u, the gate and the
store run on whole tiles, only the recurrence itself goes token by token.

Blocks come from the shape (:func:`_scan_blocks`): the channel block is the
widest whose state fits a quarter of the vector registers, the token chunk
the longest whose double-buffered blocks fit a quarter of the scoped VMEM,
then evened out so that the last chunk pads as little as 16-token groups
allow (1,025 tokens: 3 chunks of 352). No block argument in any config.

Differentiation: the XLA path is plain JAX and differentiates as such, which
is what training on the CPU at toy sizes uses. The kernel has no backward
yet (ROADMAP Reach) and says so when asked for one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.flash_attention import (
    _SCOPED_VMEM_BYTES, kernel_interpret, per_device, rows_spec)

#: which path each trace of the scan took (``kernels.ssm_scan_schedule``)
_kernels = metrics.scope("kernels")

#: tokens the kernel takes at a time: one packed bfloat16 tile of rows
_GROUP = 16
#: float32 bytes of state a channel block may hold: a quarter of the 64 × 4 KiB
#: vector registers, so the state, exp(Δ·A) and a product stay in registers
_STATE_BYTES = 16 * 4096


def selective_scan_xla(u, delta, A, B, C, D, z):
    """The equations as a ``lax.scan`` over tokens, float32 inside.
    ``u, delta, z: (n, L, d)``; ``A: (d, s)``; ``B, C: (n, L, s)``;
    ``D: (d,)``. Returns ``(n, L, d)`` in ``u``'s dtype."""
    f32 = jnp.float32
    uf, df, zf = (x.astype(f32) for x in (u, delta, z))
    Af = A.astype(f32)

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[..., None] * Af) * h
             + (d_t * u_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros((u.shape[0],) + A.shape, f32)
    tokens_first = lambda x: jnp.swapaxes(x.astype(f32), 0, 1)
    _, ys = jax.lax.scan(step, h0, (tokens_first(uf), tokens_first(df),
                                    tokens_first(B), tokens_first(C)))
    y = jnp.swapaxes(ys, 0, 1) + D.astype(f32) * uf
    return (y * jax.nn.silu(zf)).astype(u.dtype)


def _scan_blocks(L: int, d: int, s: int, dtype) -> tuple:
    """(channel block, token chunk) for ``L`` tokens × ``d`` channels × ``s``
    states of ``dtype``; see the module docstring for the rule."""
    bd = tiling.LANE
    while (d % (2 * bd) == 0 and 2 * bd * s * 4 <= _STATE_BYTES):
        bd *= 2
    isz = jnp.dtype(dtype).itemsize
    # u, Δ, z, y double-buffered, and B, C: a lane-padded (s, 128) float32
    # slab per group of tokens, double-buffered
    per_token = 8 * bd * isz + 4 * s * tiling.LANE * 4 // _GROUP
    longest = max(_GROUP, (_SCOPED_VMEM_BYTES // 4 // per_token)
                  // _GROUP * _GROUP)
    chunks = -(-L // min(longest, 512))
    return bd, tiling.round_up(-(-L // chunks), _GROUP)


def _kernel(u_ref, dt_ref, z_ref, bt_ref, ct_ref, at_ref, d_ref, y_ref,
            h_ref, ys_ref, *, groups: int):
    """One (image, channel block, token chunk) program. ``u/dt/z/y_ref``:
    (1, chunk, block); ``bt/ct_ref``: (1, chunk/16, s, 16) float32;
    ``at_ref``: (s, block) = Aᵀ; ``d_ref``: (1, block); scratch ``h_ref``
    (s, block) carries the state to the next chunk, ``ys_ref`` (16, block)
    gathers a group's rows of h·C."""
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    a_t = at_ref[...]
    d_row = d_ref[...]

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
        u16 = u_ref[0, rows, :].astype(f32)
        dt16 = dt_ref[0, rows, :].astype(f32)
        du16 = dt16 * u16
        bt = bt_ref[0, g]
        ct = ct_ref[0, g]
        for j in range(_GROUP):
            h = (jnp.exp(dt16[j:j + 1, :] * a_t) * h
                 + du16[j:j + 1, :] * bt[:, j:j + 1])
            ys_ref[j:j + 1, :] = jnp.sum(h * ct[:, j:j + 1], axis=0,
                                         keepdims=True)
        z16 = z_ref[0, rows, :].astype(f32)
        y16 = (ys_ref[...] + d_row * u16) * (z16 * jax.nn.sigmoid(z16))
        y_ref[0, rows, :] = y16.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, groups, group, h_ref[...])


def _scan_call(u, delta, z, bt, ct, at, d_row, *, bd, chunk, interpret):
    n, L, d = u.shape
    s = at.shape[0]
    groups = chunk // _GROUP
    act = pl.BlockSpec((1, chunk, bd), lambda i, c, t: (i, t, c))
    col = pl.BlockSpec((1, groups, s, _GROUP), lambda i, c, t: (i, t, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, groups=groups),
        grid=(n, d // bd, bt.shape[1] // groups),
        in_specs=[act, act, act, col, col,
                  pl.BlockSpec((s, bd), lambda i, c, t: (0, c)),
                  pl.BlockSpec((1, bd), lambda i, c, t: (0, c))],
        out_specs=act,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((s, bd), jnp.float32),
                        pltpu.VMEM((_GROUP, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",
    )(u, delta, z, bt, ct, at, d_row)


def kernel_admits(d: int, s: int) -> bool:
    """Shapes the kernel tiles: whole lanes of channels, whole float32
    sublane tiles of states. Others take the XLA path (and count as such)."""
    return d % tiling.LANE == 0 and s % 8 == 0


def _columns(x, groups_total: int):
    """``(n, L, s)`` → ``(n, groups, s, 16)`` float32, tokens padded with
    zeros to whole groups: a token's B (or C) as a column."""
    n, L, s = x.shape
    x = jnp.pad(x.astype(jnp.float32),
                ((0, 0), (0, groups_total * _GROUP - L), (0, 0)))
    return jnp.swapaxes(x.reshape(n, groups_total, _GROUP, s), -1, -2)


def selective_scan_kernel(u, delta, A, B, C, D, z, *, blocks=None,
                          interpret=None):
    """The Pallas path. ``blocks`` (channel block, token chunk) and
    ``interpret`` are for the tests and a sweep on the chip; the program
    leaves both to the shape and the backend."""
    n, L, d = u.shape
    s = A.shape[1]
    bd, chunk = blocks or _scan_blocks(L, d, s, u.dtype)
    if interpret is None:
        interpret = kernel_interpret()
    groups = -(-L // chunk) * (chunk // _GROUP)
    bt, ct = _columns(B, groups), _columns(C, groups)
    at = A.astype(jnp.float32).T
    d_row = D.astype(jnp.float32)[None, :]
    rows, whole = rows_spec(n), P()
    return per_device(
        functools.partial(_scan_call, bd=bd, chunk=chunk, interpret=interpret),
        (rows, rows, rows, rows, rows, whole, whole), rows,
    )(u, delta, z, bt, ct, at, d_row)


@jax.custom_vjp
def _kernel_no_vjp(u, delta, A, B, C, D, z):
    return selective_scan_kernel(u, delta, A, B, C, D, z)


def _no_vjp_fwd(*args):
    raise NotImplementedError(
        "the ssm_scan kernel has no backward yet (ROADMAP Reach): "
        "differentiate ops.selective_scan.selective_scan_xla, which is what "
        "selective_scan runs off the TPU")


_kernel_no_vjp.defvjp(_no_vjp_fwd, lambda res, g: None)


def selective_scan(u, delta, A, B, C, D, z):
    """``y`` of the module docstring's equations, ``(n, L, d)`` in ``u``'s
    dtype; float32 state and sums on either path."""
    use_kernel = (jax.default_backend() == "tpu"
                  and kernel_admits(u.shape[-1], A.shape[1]))
    _kernels.inc("kernels.ssm_scan_schedule",
                 key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("ssm_scan"):
            return _kernel_no_vjp(u, delta, A, B, C, D, z)
    return selective_scan_xla(u, delta, A, B, C, D, z)
