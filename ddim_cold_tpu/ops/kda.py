"""The gated delta-rule scan of a Kimi delta-attention mixer (Kimi Linear,
2025): ``H`` heads, each with a ``d × d`` matrix state (key channel × value
channel) that every token first DECAYS by a vector, one factor a key channel,
then CORRECTS by what it already says about the token's key. For one head,
``S_0 = 0``, in float32:

    S′  = Diag(α_t) S_{t−1}                        α_t = exp(g_t) ∈ (0, 1]^d
    S_t = S′ + β_t k_t (v_t − S′ᵀ k_t)ᵀ            β_t ∈ [0, 1]
    o_t = S_tᵀ q_t · scale

(the same as ``S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ``),
sequential in the ``L`` tokens as written; computed here over chunks of ``C``
tokens, where it is matrix products and one triangular solve. With ``γ_r =
Σ_{i ≤ r} g_i`` counted from a chunk's first token (per channel, decreasing),
``S`` the state the chunk is handed, and for the pairs of a chunk

    A_ri = Σ_c k_rc k_ic exp(γ_rc − γ_ic)   (i < r)
    B_ri = Σ_c q_rc k_ic exp(γ_rc − γ_ic)   (i ≤ r):

    Ũ      = (I + Diag(β) A)⁻¹ Diag(β) (V − (exp(γ) ⊙ K) S)
    O      = ((exp(γ) ⊙ Q) S + B Ũ) · scale
    S_next = Diag(exp(γ_C)) S + (exp(γ_C − γ) ⊙ K)ᵀ Ũ

the same numbers as the recurrence. **Every exponent above is ≤ 0 as
written; split across a product, ``exp(γ_r) · exp(−γ_i)``, the second factor
is unbounded** (a head that loses 20 a token has e^{+2560} over 128 tokens),
and g is never clamped, which would be another model. Both forms below
therefore keep every exponent they take ≤ 0:

* :func:`kda_scan_xla` forms ``exp(γ_rc − γ_ic)`` pair by pair and channel
  by channel, a ``(C, C, d)`` array a head and chunk inside a ``lax.scan``
  over the chunks, and solves with ``solve_triangular``: plain JAX, float32
  inside, any shape, differentiable. It is what runs off the TPU and the
  tests' oracle beside the token-by-token recurrence.
* the launch (``pallas_call(name="kda_chunk")``, ``%kda_chunk`` in a device
  trace) cuts a chunk into sub-blocks of 8 tokens, one sublane tile. A pair
  in two DIFFERENT sub-blocks takes its reference point at the later
  block's first row, ``exp(γ_r − γ_ref) · exp(γ_ref − γ_i)`` with both
  factors ≤ 1, and is a product on the MXU (the rows of a sub-block against
  the keys before it, scaled for that sub-block). A pair inside ONE
  sub-block is formed channel by channel on the vector unit, the eight
  keys of a sub-block one after the other against its rows, a column of
  the chunk each.
  ``(I + Diag(β) A)⁻¹`` is built in float32 in two stages, both exact for a
  triangular matrix (no series, nothing that grows). The sixteen 8 × 8
  diagonal blocks by elimination on the vector unit, from those columns as
  they come: starting from the identity, ``X[r] −= N[r, i] · X[i]`` for the
  rows r after key i of the same sub-block, seven steps. Above them by
  doubling: the inverse of the ``2b``-blocks from that of the ``b``-blocks,
  ``X ← X − X N_b X`` with ``N_b`` the part of ``Diag(β) A`` that joins the
  two halves, four levels from 8 to the chunk's 128, each product in three
  bfloat16 passes (both operands' leading pieces and each one's remainder
  against the other's lead: 16 bits of product; float32 operands multiply
  as float32), each matrix split into those pieces once: doubling from any
  lower would multiply the zeros between smaller blocks at the price of
  whole 128³ passes. γ is a product with a triangle of ones, g taken in
  three bfloat16 pieces that add up to its float32, so the sums are
  float32's.

Layout in the launch: q, k, v and the result token-major ``(n, L, H·d)`` as
the projections leave and read them, g the same in float32, β ``(n, L, H)``
float32 of which a program picks its heads' columns; a program is one
(image, ``HEADS_A_PROGRAM`` heads, chunk) and walks the chunks in order with
its heads' states, TRANSPOSED (value channel × key channel, so that a
channel's decay lies along the lanes), in float32 VMEM scratch. q, k, v, g, β
are read and o is written once; a sequence that ends inside a chunk is masked
in the kernel (a token past the end neither decays nor feeds the state), not
padded in HBM. Products take their operands in q's dtype and accumulate in
float32; γ, the inverse and the state are float32.

Differentiation: the XLA path is plain JAX and differentiates as such. The
kernel has no backward yet (ROADMAP Reach) and says so when asked for one.
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

#: tokens a chunk: the launch's choice and no part of the model. On the chip
#: (PERF.md, PR 47) a launch at the published shape took 11.7 ms at 64, 8.3
#: at 128 and 16.4 at 256: a chunk's fixed work (the doubling levels, the
#: products with the state) is shared by more tokens, until its pairs and
#: its levels outgrow that
CHUNK = 128
#: tokens a sub-block of the launch: one float32 sublane tile, and the
#: diagonal blocks that the inverse eliminates on the vector unit
SUB = 8
#: heads one program of the launch walks, their chains of small products
#: independent of one another for the scheduler to interleave (8.7, 8.3 and
#: 8.0 ms a launch at 1, 2 and 4; four compile three times as long)
HEADS_A_PROGRAM = 2

#: which path each trace of the scan took (``kernels.kda_schedule``)
_kernels = metrics.scope("kernels")
_HIGHEST = jax.lax.Precision.HIGHEST


def _sizes(q, k, v, g, beta) -> tuple:
    """``(n, L, H, d)`` of ``q, k, v, g (n, L, H·d)`` and ``beta (n, L,
    H)``."""
    n, L, H = beta.shape
    if (q.shape[-1] % H or q.shape[:2] != (n, L)
            or not q.shape == k.shape == v.shape == g.shape):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta "
            f"{beta.shape}: the delta-rule scan takes q, k, v and g (n, L, "
            "H·d), one decay a key channel, and beta (n, L, H)")
    return n, L, H, q.shape[-1] // H


def kda_scan_xla(q, k, v, g, beta, scale: float, *, chunk: int = CHUNK):
    """The chunked form in plain JAX, float32 inside. ``q, k, v: (n, L,
    H·d)``, q and k as the mixer normed them; ``g: (n, L, H·d)``, ≤ 0;
    ``beta: (n, L, H)``. Returns ``(n, L, H·d)`` in ``q``'s dtype."""
    n, L, H, d = _sizes(q, k, v, g, beta)
    f32 = jnp.float32
    chunks = -(-L // chunk)
    # (chunks, n, H, C, ·), zeros past the sequence: no decay, no key, no step
    chunked = lambda a, *tail: jnp.moveaxis(jnp.pad(
        a.astype(f32), ((0, 0), (0, chunks * chunk - L), (0, 0))
    ).reshape(n, chunks, chunk, H, *tail), (1, 3), (0, 2))
    sees = jnp.tril(jnp.ones((chunk, chunk), bool))
    before = jnp.tril(sees, -1)
    eye = jnp.eye(chunk, dtype=f32)

    def step(S, xs):
        qc, kc, vc, gc, bc = xs                       # (n, H, C, d); bc (…, 1)
        gam = jnp.cumsum(gc, axis=2)
        # every pair's own exponent, channel by channel: never above 0
        decay = jnp.exp(jnp.minimum(
            gam[:, :, :, None, :] - gam[:, :, None, :, :], 0.0))
        A = jnp.einsum("bhrc,bhic,bhric->bhri", kc, kc, decay)
        B = jnp.einsum("bhrc,bhic,bhric->bhri", qc, kc, decay)
        eg, last = jnp.exp(gam), gam[:, :, -1:, :]
        rhs = bc * (vc - jnp.einsum("bhrc,bhcv->bhrv", kc * eg, S))
        U = jax.scipy.linalg.solve_triangular(
            eye + bc * jnp.where(before, A, 0.0), rhs, lower=True)
        o = (jnp.einsum("bhrc,bhcv->bhrv", qc * eg, S)
             + jnp.einsum("bhri,bhiv->bhrv", jnp.where(sees, B, 0.0), U))
        S = (jnp.swapaxes(jnp.exp(last), 2, 3) * S
             + jnp.einsum("bhrc,bhrv->bhcv", kc * jnp.exp(last - gam), U))
        return S, o * scale

    _, out = jax.lax.scan(
        step, jnp.zeros((n, H, d, d), f32),
        (chunked(q, d), chunked(k, d), chunked(v, d), chunked(g, d),
         chunked(beta, 1)))
    out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(n, chunks * chunk, H * d)
    return out[:, :L].astype(q.dtype)


def kernel_admits(heads: int, head_dim: int, chunk: int = CHUNK) -> bool:
    """Shapes the kernel tiles: heads of one lane group, in whole programs,
    chunks of whole sub-blocks that double up to the chunk and fill whole
    bfloat16 tiles. Others take the XLA path (and count as such)."""
    return (head_dim == tiling.LANE and heads % HEADS_A_PROGRAM == 0
            and chunk % (2 * SUB) == 0 and chunk & (chunk - 1) == 0)


def _one_head(q, k, v, g, beta, St, *, dtype):
    """One head's chunk. ``q`` (scaled), ``k``, ``v``, ``g``: ``(C, d)``
    float32, zeros past the sequence; ``beta``: ``(C, 1)``; ``St``: ``(d,
    d)`` float32, the state handed in, value channel × key channel. Returns
    ``(o (C, d) float32, the state handed on)``. MXU products at ``C`` 128 in
    bfloat16: 3 for γ, 15 for the pairs in two sub-blocks, 24 for the
    inverse's four levels, 5 at the end (``tests/test_kda.py`` counts them)."""
    f32 = jnp.float32
    C, d = q.shape
    nb = C // SUB
    # float32 operands (a float32 model) multiply as float32; Mosaic refuses
    # that precision on bfloat16 ones, which are exact in one pass anyway
    exact = _HIGHEST if dtype == f32 else None
    dot = lambda a, b: jnp.dot(a.astype(dtype), b.astype(dtype),
                               precision=exact, preferred_element_type=f32)
    dot_t = lambda a, b: jax.lax.dot_general(      # a @ b.T
        a.astype(dtype), b.astype(dtype), (((1,), (1,)), ((), ())),
        precision=exact, preferred_element_type=f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

    # γ: the running sum of g down the chunk, as a product with a triangle of
    # ones; g in three bfloat16 pieces, each product exact, summed in float32
    tri = (row >= col).astype(jnp.bfloat16)
    gam, rest = jnp.zeros((C, d), f32), g
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        gam = gam + jnp.dot(tri, piece, preferred_element_type=f32)
        rest = rest - piece.astype(f32)

    gam3, k3, q3 = (a.reshape(nb, SUB, d) for a in (gam, k, q))
    # each row against its own sub-block's first row: ≤ 0
    lead = jnp.exp(gam3 - gam3[:, 0:1, :])
    ke, qe = (k3 * lead).reshape(C, d), (q3 * lead).reshape(C, d)
    # pairs in two sub-blocks: the rows of sub-block R against the keys before
    # it (in whole bfloat16 tiles of 16; the rest are zeros), scaled from R's
    # first row back: ≤ 0 for every key before it
    rows_a, rows_b = [jnp.zeros((SUB, C), f32)], [jnp.zeros((SUB, C), f32)]
    for R in range(1, nb):
        at = slice(R * SUB, (R + 1) * SUB)
        upto = min(C, tiling.round_up(R * SUB, 2 * SUB))
        back = jnp.exp(jnp.minimum(
            gam[R * SUB:R * SUB + 1, :] - gam[:upto], 0.0))
        keys = k[:upto] * back
        if upto < C:
            keys = jnp.concatenate([keys, jnp.zeros((C - upto, d), f32)], 0)
        both = dot_t(jnp.concatenate([ke[at], qe[at]], axis=0), keys)
        rows_a.append(both[:SUB])
        rows_b.append(both[SUB:])
    first = row - row % SUB          # a row's sub-block starts here
    A = jnp.where(col < first, jnp.concatenate(rows_a, axis=0), 0.0)
    B = jnp.where(col < first, jnp.concatenate(rows_b, axis=0), 0.0)
    # pairs inside one sub-block: its i-th key against its rows, channel by
    # channel, a ``(C, 1)`` column: row r's pair with key ``first + i``. q's go
    # into column ``first + i`` of B. k's, times β, are column i of every
    # sub-block's part of ``Diag(β) A``, and eliminate it at once: from X = I,
    # ``X[r] −= N[r, first + i] · X[first + i]`` for the rows after the key
    # (row i of each sublane tile broadcast down it), seven steps to the
    # exact float32 inverse of the sixteen 8 × 8 diagonal blocks (forward
    # elimination applied to the identity: no series, nothing that grows).
    # What a column holds at the key's own row and the rows before it (an
    # exponent above 0, maybe inf) is cut by a select before any multiply
    B_in = jnp.zeros((C, C), f32)
    X = (row == col).astype(f32)
    place = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) % SUB
    for i in range(SUB):
        pair = (jnp.exp(gam3 - gam3[:, i:i + 1, :])
                * k3[:, i:i + 1, :]).reshape(C, d)
        B_in = jnp.where(col - first == i,
                         jnp.sum(pair * q, axis=-1, keepdims=True), B_in)
        if i < SUB - 1:  # no row comes after a sub-block's last key
            n_i = jnp.where(
                place > i,
                beta * jnp.sum(pair * k, axis=-1, keepdims=True), 0.0)
            X3 = X.reshape(nb, SUB, C)
            X = X - n_i * jnp.broadcast_to(
                X3[:, i:i + 1, :], X3.shape).reshape(C, C)
    B = jnp.where((col >= first) & (col <= row), B_in, B)

    # X = (I + Diag(β) A)⁻¹ from the sub-blocks' by doubling, in float32: with
    # X the inverse of the b-blocks and N the pairs in two sub-blocks (all
    # that is left of Diag(β) A), ``X − (X N restricted to where two b-blocks
    # join into one 2b-block) X`` is the inverse of the 2b-blocks. X being
    # block-diagonal, restricting the product is restricting N, term for
    # term. Each float32 matrix is split into its bfloat16 pieces once: N a
    # chunk, X a level (it is an operand of both products), X·N where used
    if dtype == f32:
        pieces = lambda a: (a,)
        times = lambda a, b: jnp.dot(a[0], b[0], precision=_HIGHEST,
                                     preferred_element_type=f32)
    else:
        def pieces(a):
            lead = a.astype(jnp.bfloat16)
            return lead, (a - lead.astype(f32)).astype(jnp.bfloat16)

        def times(a, b):
            # three bfloat16 passes: both operands' leading pieces and each
            # one's remainder against the other's lead (16 bits of product)
            d3 = lambda x, y: jnp.dot(x, y, preferred_element_type=f32)
            return d3(a[0], b[0]) + (d3(a[0], b[1]) + d3(a[1], b[0]))
    N = pieces(beta * A)
    apart = row ^ col     # in [b, 2b): one 2b-block, two b-blocks
    b = SUB
    while b < C:
        Xp = pieces(X)
        XN = jnp.where((apart & -b) == b, times(Xp, N), 0.0)
        X = X - times(pieces(XN), Xp)
        b *= 2

    eg, last = jnp.exp(gam), gam[C - 1:C, :]
    U = dot(X, beta * (v - dot_t(k * eg, St)))
    o = dot_t(q * eg, St) + dot(B, U)
    return o, St * jnp.exp(last) + dot(U.T, k * jnp.exp(last - gam))


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, *, scale: float,
            n_chunks: int, valid_last: int):
    """One (image, heads, chunk) program. ``q/k/v/o_ref``: (1, C, hb·d);
    ``g_ref``: the same, float32; ``b_ref``: (1, C, H) float32, every head's
    β; scratch ``s_ref`` (hb, d, d) float32: the heads' states, value channel
    × key channel."""
    f32 = jnp.float32
    C, d = q_ref.shape[1], tiling.LANE
    hb = q_ref.shape[2] // d
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    live = None
    if valid_last != C:  # the sequence ends inside the last chunk
        valid = jnp.where(chunk == n_chunks - 1, valid_last, C)
        live = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) < valid
    betas = b_ref[0]
    head = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    for j in range(hb):
        lanes = slice(j * d, (j + 1) * d)
        q, k, v, g = (ref[0, :, lanes].astype(f32)
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        beta = jnp.sum(
            jnp.where(head == pl.program_id(1) * hb + j, betas, 0.0),
            axis=-1, keepdims=True)
        if live is not None:  # what lies past the end may be anything
            q, k, v, g, beta = (jnp.where(live, a, 0.0)
                                for a in (q, k, v, g, beta))
        o, s_ref[j] = _one_head(q * scale, k, v, g, beta, s_ref[j],
                                dtype=q_ref.dtype)
        o_ref[0, :, lanes] = o.astype(o_ref.dtype)


def _scan_call(q, k, v, g, beta, *, scale, chunk, interpret):
    n, L, width = q.shape
    H = beta.shape[-1]
    hb, d = HEADS_A_PROGRAM, width // H
    n_chunks = -(-L // chunk)
    act = pl.BlockSpec((1, chunk, hb * d), lambda i, h, t: (i, t, h))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, n_chunks=n_chunks,
                          valid_last=L - (n_chunks - 1) * chunk),
        grid=(n, H // hb, n_chunks),
        in_specs=[act, act, act, act,
                  pl.BlockSpec((1, chunk, H), lambda i, h, t: (i, t, 0))],
        out_specs=act,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(q, k, v, g, beta)


def kda_scan_kernel(q, k, v, g, beta, scale: float, *, chunk: int = CHUNK,
                    interpret=None):
    """The Pallas path, arguments as :func:`kda_scan_xla`. ``interpret`` is
    for the tests; the program leaves it to the backend."""
    _, _, H, d = _sizes(q, k, v, g, beta)
    if not kernel_admits(H, d, chunk):
        raise NotImplementedError(
            f"kda_chunk tiles heads of {tiling.LANE} channels, "
            f"{HEADS_A_PROGRAM} a program, over chunks of whole sub-blocks "
            f"of {SUB}: {H} heads of {d}, chunks of {chunk}")
    if interpret is None:
        interpret = kernel_interpret()
    spec = rows_spec(q.shape[0])
    return per_device(
        functools.partial(_scan_call, scale=scale, chunk=chunk,
                          interpret=interpret),
        (spec,) * 5, spec,
    )(q, k.astype(q.dtype), v.astype(q.dtype), g.astype(jnp.float32),
      beta.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel_no_vjp(q, k, v, g, beta, scale):
    return kda_scan_kernel(q, k, v, g, beta, scale)


def _no_vjp_fwd(*args):
    raise NotImplementedError(
        "the kda_chunk kernel has no backward yet (ROADMAP Reach): "
        "differentiate ops.kda.kda_scan_xla, which is what kda_scan runs off "
        "the TPU")


_kernel_no_vjp.defvjp(_no_vjp_fwd, lambda *a: None)


def kda_scan(q, k, v, g, beta, scale: float):
    """``o`` of the module docstring's equations, ``(n, L, H·d)`` in ``q``'s
    dtype; float32 decays, solve and state on either path."""
    _, _, H, d = _sizes(q, k, v, g, beta)
    use_kernel = jax.default_backend() == "tpu" and kernel_admits(H, d)
    _kernels.inc("kernels.kda_schedule", key="kernel" if use_kernel else "xla")
    if use_kernel:
        with jax.named_scope("kda_chunk"):
            return _kernel_no_vjp(q, k, v, g, beta, scale)
    return kda_scan_xla(q, k, v, g, beta, scale)
