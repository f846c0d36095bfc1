"""The state-space-dual scan of a Mamba-2 mixer (Dao & Gu 2024): ``H`` heads
of ``P`` channels, each with a ``P × N`` matrix state and ONE decay a head,
B and C shared by the ``H / G`` heads of a group. For head h in group g,

    S_t = exp(Δ_t,h a_h) · S_{t-1} + Δ_t,h · x_t,h ⊗ B_t,g          S_0 = 0
    y_t,h = S_t C_t,g + D_h x_t,h

sequential in the ``L`` tokens as written; computed here over chunks of ``Q``
tokens, where it is matrix products. With ``cum_t = Σ_{r ≤ t} Δ_r a`` counted
from a chunk's first token, ``X̃ = Δ ⊙ X`` and ``S`` the state the chunk is
handed:

    Y      = ((C Bᵀ) ∘ Λ) X̃ + (C Sᵀ) ∘ exp(cum)     Λ_ts = exp(cum_t − cum_s), s ≤ t
    S_next = exp(cum_Q) · S + (X̃ ∘ exp(cum_Q − cum))ᵀ B

the same numbers as the recurrence (every exponent is ≤ 0: nothing is
factored into a growing and a shrinking part). One function,
:func:`ssd_scan`; the backend decides what runs. On the TPU a Pallas launch
(``pallas_call(name="ssd_chunk")``, ``%ssd_chunk`` in a device trace) whose
grid is images × groups × chunks: the float32 states of a group's heads stay
in VMEM scratch from chunk to chunk, x is read and y written once, B and C
once a GROUP (``C Bᵀ`` and ``Bᵀ`` are formed once a program and serve all its
heads), and a sequence that ends inside a chunk is masked in the kernel, not
padded in HBM. Anywhere else, and as the tests' oracle,
:func:`ssd_scan_xla`: the same chunked form in plain JAX, float32 inside.

Layout in the kernel: x, y token-major ``(n, L, H·P)`` as the projections
leave and read them, a program taking its group's ``H/G · P`` columns; heads
narrower than the 128 lanes sit side by side in a lane group (two at P = 64)
and share the products that do not depend on the head — ``C Sᵀ`` and the
state update run on the whole lane group, since B and C are the group's —
while the ``Q × Q`` decay mask, the one thing a head has to itself, is built
and multiplied head by head and the lanes of each kept. The per-token scalars
(Δ, cum, cum_Q) come twice, made by XLA from Δ (a few MB a launch): down the
sublanes as ``(n, G, L⁺, 3·H/G)`` and, for the mask's other axis, along the
lanes as ``(n, G, H/G, L⁺)``; ``L⁺`` whole chunks, zeros past the sequence, so
a token past the end neither decays nor feeds the state. Products take their
operands in x's dtype and accumulate in float32; the state is float32.

Differentiation: the XLA path is plain JAX and differentiates as such. The
kernel has no backward yet (ROADMAP Reach) and says so when asked for one.
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
    kernel_interpret, per_device, rows_spec)

#: which path each trace of the scan took (``kernels.ssd_schedule``)
_kernels = metrics.scope("kernels")


def _sizes(x, dt, B, groups: int) -> tuple:
    """(H, P, N, heads a group) of ``x (n, L, H·P)``, ``dt (n, L, H)``,
    ``B (n, L, G·N)``."""
    H = dt.shape[-1]
    if x.shape[-1] % H or H % groups or B.shape[-1] % groups:
        raise ValueError(f"x {x.shape}, dt {dt.shape}, B {B.shape}: channels "
                         f"divide into heads, heads and B into {groups} groups")
    return H, x.shape[-1] // H, B.shape[-1] // groups, H // groups


def _chunked_decays(dt, A, chunk: int):
    """``(Δ, cum, cum_Q)``, each ``(n, chunks, Q, H)`` float32, tokens padded
    with zeros to whole chunks: ``cum`` the running sum of ``Δ·a`` from a
    chunk's first token, ``cum_Q`` its value at the chunk's last."""
    n, L, H = dt.shape
    chunks = -(-L // chunk)
    delta = jnp.pad(dt.astype(jnp.float32),
                    ((0, 0), (0, chunks * chunk - L), (0, 0)))
    delta = delta.reshape(n, chunks, chunk, H)
    cum = jnp.cumsum(delta * A.astype(jnp.float32), axis=2)
    return delta, cum, jnp.broadcast_to(cum[:, :, -1:], cum.shape)


def ssd_scan_xla(x, dt, A, B, C, D, *, groups: int, chunk: int):
    """The chunked form in plain JAX, float32 inside. ``x: (n, L, H·P)``;
    ``dt: (n, L, H)``, Δ after its softplus; ``A: (H,)``, negative; ``B, C:
    (n, L, G·N)``; ``D: (H,)``. Returns ``(n, L, H·P)`` in ``x``'s dtype."""
    H, Pd, N, hg = _sizes(x, dt, B, groups)
    n, L, _ = x.shape
    f32 = jnp.float32
    delta, cum, last = _chunked_decays(dt, A, chunk)
    chunks = delta.shape[1]
    chunked = lambda a, *tail: jnp.pad(
        a.astype(f32), ((0, 0), (0, chunks * chunk - L), (0, 0))
    ).reshape(n, chunks, chunk, *tail)
    xf = chunked(x, H, Pd)
    # a head reads its group's B and C
    Bh, Ch = (jnp.repeat(chunked(a, groups, N), hg, axis=3) for a in (B, C))
    xt = delta[..., None] * xf                                  # X̃
    sees = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (n,c,t,s,H)
    lam = jnp.where(sees[..., None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    scores = jnp.einsum("bcthn,bcshn->bctsh", Ch, Bh) * lam
    y = jnp.einsum("bctsh,bcshp->bcthp", scores, xt)
    fed = jnp.einsum("bcshp,bcshn->bchpn",
                     xt * jnp.exp(last - cum)[..., None], Bh)

    def step(S, xs):
        keep, add = xs
        return keep[..., None, None] * S + add, S

    first = lambda a: jnp.moveaxis(a, 1, 0)
    _, handed = jax.lax.scan(
        step, jnp.zeros((n, H, Pd, N), f32),
        (first(jnp.exp(last[:, :, 0])), first(fed)))
    y = y + (jnp.einsum("bcthn,bchpn->bcthp", Ch, first(handed))
             * jnp.exp(cum)[..., None])
    y = y + D.astype(f32)[:, None] * xf
    return y.reshape(n, chunks * chunk, H * Pd)[:, :L].astype(x.dtype)


def kernel_admits(heads_a_group: int, head_dim: int, N: int, chunk: int) -> bool:
    """Shapes the kernel tiles: heads that fill whole lane groups (alone or
    side by side), a group's heads whole lane groups, states and chunks whole
    lane tiles. Others take the XLA path (and count as such)."""
    return (head_dim <= tiling.LANE and tiling.LANE % head_dim == 0
            and (heads_a_group * head_dim) % tiling.LANE == 0
            and N % tiling.LANE == 0 and chunk % tiling.LANE == 0)


def _kernel(x_ref, b_ref, c_ref, col_ref, row_ref, d_ref, y_ref, s_ref, *,
            hg: int, head_dim: int, n_chunks: int, valid_last: int):
    """One (image, group, chunk) program. ``x/y_ref``: (1, Q, hg·P);
    ``b/c_ref``: (1, Q, N); ``col_ref``: (1, 1, Q, 3·hg) float32, [Δ | cum |
    cum_Q] of the group's heads; ``row_ref``: (1, 1, hg, Q) float32, cum with
    the tokens on the lanes; ``d_ref``: (1, hg·P) float32, D on each head's
    lanes; scratch ``s_ref`` (lane groups, N, 128) float32: the states, a
    head's ``(N, P)`` on its lanes."""
    f32 = jnp.float32
    lane_w = tiling.LANE
    Q = x_ref.shape[1]
    dtype = x_ref.dtype
    side = lane_w // head_dim          # heads side by side on a lane group
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    b, c = b_ref[0], c_ref[0]
    live = None
    if valid_last != Q:  # the sequence ends inside the last chunk
        valid = jnp.where(chunk == n_chunks - 1, valid_last, Q)
        live = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) < valid
        b = jnp.where(live, b, jnp.zeros_like(b))
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)         # (Q, Q)
    bt = b.astype(f32).T.astype(dtype)                           # (N, Q)
    sees = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
            <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lane_w), 1)
    cols, rows = col_ref[0, 0], row_ref[0, 0]

    def on_lanes(which: int, first_head: int):
        """Column ``which`` (0 Δ, 1 cum, 2 cum_Q) of the lane group's heads,
        each on its own lanes: (Q, 128), or (Q, 1) for a head that fills
        them."""
        at = which * hg + first_head
        out = cols[:, at:at + 1]
        for j in range(1, side):
            out = jnp.where(lane >= j * head_dim, cols[:, at + j:at + j + 1],
                            out)
        return out

    for p in range(hg // side):
        lanes = slice(p * lane_w, (p + 1) * lane_w)
        xs = x_ref[0, :, lanes].astype(f32)
        if live is not None:
            xs = jnp.where(live, xs, 0.0)
        delta, cum, last = (on_lanes(k, p * side) for k in range(3))
        xt = xs * delta
        xt_c = xt.astype(dtype)
        y = None
        for j in range(side):
            h = p * side + j
            diff = cols[:, hg + h:hg + h + 1] - rows[h:h + 1, :]
            scores = jnp.where(sees, cb * jnp.exp(jnp.minimum(diff, 0.0)),
                               0.0).astype(dtype)
            mine = jnp.dot(scores, xt_c, preferred_element_type=f32)
            y = mine if y is None else jnp.where(lane >= j * head_dim, mine, y)
        S = s_ref[p]
        y = y + (jnp.dot(c, S.astype(dtype), preferred_element_type=f32)
                 * jnp.exp(cum))
        y = y + d_ref[:, lanes] * xs
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        fed = (xt * jnp.exp(last - cum)).astype(dtype)
        s_ref[p] = (jnp.exp(last[0:1, :]) * S
                    + jnp.dot(bt, fed, preferred_element_type=f32))


def _scan_call(x, B, C, cols, rows, d_lanes, *, hg, head_dim, chunk,
               interpret):
    n, L, _ = x.shape
    groups, N = cols.shape[1], B.shape[-1] // cols.shape[1]
    n_chunks = cols.shape[2] // chunk
    width = hg * head_dim
    act = pl.BlockSpec((1, chunk, width), lambda i, g, t: (i, t, g))
    shared = pl.BlockSpec((1, chunk, N), lambda i, g, t: (i, t, g))
    return pl.pallas_call(
        functools.partial(_kernel, hg=hg, head_dim=head_dim,
                          n_chunks=n_chunks,
                          valid_last=L - (n_chunks - 1) * chunk),
        grid=(n, groups, n_chunks),
        in_specs=[act, shared, shared,
                  pl.BlockSpec((1, 1, chunk, 3 * hg),
                               lambda i, g, t: (i, g, t, 0)),
                  pl.BlockSpec((1, 1, hg, chunk),
                               lambda i, g, t: (i, g, 0, t)),
                  pl.BlockSpec((1, width), lambda i, g, t: (0, g))],
        out_specs=act,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((width // tiling.LANE, N, tiling.LANE),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",
    )(x, B, C, cols, rows, d_lanes)


def ssd_scan_kernel(x, dt, A, B, C, D, *, groups: int, chunk: int,
                    interpret=None):
    """The Pallas path, arguments as :func:`ssd_scan_xla`. ``interpret`` is
    for the tests; the program leaves it to the backend."""
    H, Pd, N, hg = _sizes(x, dt, B, groups)
    if not kernel_admits(hg, Pd, N, chunk):
        raise NotImplementedError(
            f"ssd_chunk tiles whole lane groups: {hg} heads of {Pd} a group, "
            f"{N} states, chunks of {chunk}")
    if interpret is None:
        interpret = kernel_interpret()
    n = x.shape[0]
    delta, cum, last = _chunked_decays(dt, A, chunk)
    long = delta.shape[1] * chunk
    # (n, L⁺, kind, G, hg) → (n, G, L⁺, kind·hg); cum again, tokens on lanes
    cols = jnp.stack([delta, cum, last], axis=3).reshape(n, long, 3, groups, hg)
    cols = jnp.moveaxis(cols, 3, 1).reshape(n, groups, long, 3 * hg)
    rows = jnp.moveaxis(cum.reshape(n, long, groups, hg), 1, 3)
    d_lanes = jnp.repeat(D.astype(jnp.float32), Pd)[None, :]
    spec, whole = rows_spec(n), P()
    return per_device(
        functools.partial(_scan_call, hg=hg, head_dim=Pd, chunk=chunk,
                          interpret=interpret),
        (spec, spec, spec, spec, spec, whole), spec,
    )(x, B, C, cols, rows, d_lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_no_vjp(x, dt, A, B, C, D, groups, chunk):
    return ssd_scan_kernel(x, dt, A, B, C, D, groups=groups, chunk=chunk)


def _no_vjp_fwd(*args):
    raise NotImplementedError(
        "the ssd_chunk kernel has no backward yet (ROADMAP Reach): "
        "differentiate ops.ssd.ssd_scan_xla, which is what ssd_scan runs off "
        "the TPU")


_kernel_no_vjp.defvjp(_no_vjp_fwd, lambda *a: None)


def ssd_scan(x, dt, A, B, C, D, *, groups: int, chunk: int):
    """``y`` of the module docstring's equations, ``(n, L, H·P)`` in ``x``'s
    dtype; float32 state and sums on either path."""
    _, Pd, N, hg = _sizes(x, dt, B, groups)
    use_kernel = (jax.default_backend() == "tpu"
                  and kernel_admits(hg, Pd, N, chunk))
    _kernels.inc("kernels.ssd_schedule", key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("ssd_chunk"):
            return _kernel_no_vjp(x, dt, A, B, C, D, groups, chunk)
    return ssd_scan_xla(x, dt, A, B, C, D, groups=groups, chunk=chunk)
