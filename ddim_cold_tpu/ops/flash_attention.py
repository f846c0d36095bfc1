"""Fused softmax-attention Pallas kernels for TPU (the long-sequence hot op).

The reference's attention is three separate cuDNN GEMMs with an O(N²) f32
attention matrix materialized in HBM (ViT.py:110-114). Here the whole
``softmax(q·kᵀ·scale)·v`` is one Pallas kernel: each program folds one K/V
chunk into a running (max, denominator, accumulator) triple — the classic
flash-attention online softmax; the logits never round-trip to HBM and the MXU
sees two GEMMs per chunk and head. The K/V grid axis is innermost: TPU grids
execute sequentially, so the VMEM scratch accumulators carry across the chunks
of one q block and are re-initialized when the chunk index wraps to 0.

**Layout: the kernels read their operands where the model's GEMMs wrote them,
forward and backward.**
The model holds q, k, v token-major — ``(B, N, H·D)``, or all three as the one
``(B, N, 3·H·D)`` result of its qkv GEMM — and wants the context as
``(B, N, H·D)`` for ``proj``. The forward's ``BlockSpec``s address exactly
that: a block is ``(1, block, 128)`` at column block ``offset + g``, where
``g`` is a *lane group* of ``128 // D`` whole heads (two at head size 64, one
at 128, four at 32) and ``offset`` is 0, C/128, 2C/128 for q, k, v inside the
packed projection; grid ``(B, H·D/128, q blocks, K/V chunks)``. Inside a
program the heads of the group are told apart by lane masks, not lane slices
(:func:`_fwd_kernel`), and the context goes back through the same blocks, so
nothing is transposed, padded or sliced in HBM on either side of the kernel.
The token axis is not padded either: it ends inside the last block, whose
stale K columns are masked and V rows zeroed in the kernel (what a program
reads past the edge is unspecified, what it writes there is dropped). Which
shapes take this path is decided from the shape alone
(:func:`_heads_per_lane_group`): ``128 % D == 0`` and ``H·D % 128 == 0``. Any
other (head size 80 or 256; a single local head of 64 under Ulysses) is first
laid out head-major by XLA — ``(B·H, N⁺, D⁺)``, one grid row a head, head size
zero-padded to the lanes (:func:`_to_heads`) — and runs the SAME launch with
one head a group, bit for bit the same context. ``kernels.flash_fwd_layout``
counts which, ``in_place`` or ``head_major``, once a trace. The backward
launches (``dq``, ``dkv``) take the same addressing by the same rule
(``kernels.flash_bwd_layout``): q, k, v, the context and the cotangent
``(B, N, H·D)`` are read in those blocks and dq, dk, dv written back through
them, into three ``(B, N, H·D)`` arrays or — the packed entry — into the one
``(B, N, 3·H·D)`` gradient the qkv GEMM's backward reads, which ``dq`` begins
(column blocks of q) and ``dkv``, taking it aliased to its own result,
completes: no transpose, pad, slice, broadcast or concatenate on either side.

The forward picks its blocks from what it can see — padded sequence length,
lanes, dtype, heads a lane group (:func:`_fwd_blocks`); no model name, no flag:

* **K/V resident** — wherever a lane group's K and V and its (block_q, N) f32
  score tiles fit the scoped VMEM (:func:`_fwd_vmem_bytes`; the 200px/p4
  trunk's 2,501 tokens do, at block_q 512 in bf16) the whole padded sequence
  is ONE chunk. The K/V block index then does not change across a lane
  group's q blocks, so its K and V are fetched once, and a launch is
  images × lane groups × query blocks programs. On the v5e at 288 images × 4
  heads × 2,501 tokens × head size 64 in bf16 (one launch of the
  ``flower200_sample_k20`` cell) that is 2,880 programs of two heads each
  (PERF.md section 6, PR 25 and PR 27, has each part's time on the chip).
* **streamed** — explicit blocks with more than one K/V chunk, and every
  sequence too long for the above, at (256, 512). VMEM is bounded by the
  block sizes, not the sequence length.

A power-of-two ``scale`` (head size 64) is folded into q, (block_q, 128)
multiplies in place of (block_q, block_kv), bit for bit the same scores.

Autodiff: ONE custom VJP (:func:`_attention`) under both entries,
:func:`flash_attention` (q, k, v apart) and :func:`flash_attention_qkv` (the
packed projection), flash all the way through. The VJP's forward additionally
emits the per-row log-sum-exp (the undifferentiated call, which is all a
sampler makes, launches the kernel without that result and its write); the
backward rebuilds probabilities from the saved lse tile by tile, so the
O(N²) matrix never exists in HBM in either direction: ONE more Pallas kernel,
``dqkv``, wherever the sequence is resident for it — K/V blocks along the
steps, q, the context, the cotangent and the statistics one chunk in VMEM,
each tile's scores, p, dp and ds formed once and all three gradients taken
from them, five GEMMs a head — and two where it is too long for that, dq (grid
like the forward) and dk/dv (grid transposed: K/V blocks outer, q chunks
innermost), seven GEMMs a head. The scores of ``dqkv`` and dk/dv are computed
transposed, ``k·qᵀ``, so that ``pᵀ·do`` and ``dsᵀ·q`` need no (bq, bkv)
transpose. Residuals
are the operands as the forward read them (the packed projection stays
packed), the context and lse: O(N·D) — the whole train-step memory story for
long sequences is bounded. (The forward emits lse 128-lane-replicated because
TPU tiling rejects (1, bq) row blocks, and the replication is cut off outside
the kernel so the residual stays one lane a row and head. The backward reads
that residual as it is: rows of ``(8, bq)`` blocks, a head a sublane, tokens
on the lanes — :func:`_lse_rows` — which dkv's transposed tiles broadcast
directly and dq turns into columns in VMEM; delta = Σ o·do is computed
inside the kernels from the context and cotangent blocks they hold — by
``dqkv`` into VMEM scratch, never written to HBM; by dq, handed to dkv in the
same form as lse. Nothing is spread over 128 lanes in HBM.) The backward picks
its launches and blocks from the shape as the forward does
(:func:`_bwd_blocks`): ``dqkv`` wherever its row of the VMEM model admits the
whole padded sequence — (2560, 512) at the 200px trunk in bf16 — else dq
keeping K and V, dkv keeping q and do, as ONE resident chunk wherever theirs
do, and both streaming at (256, 512) where nothing fits;
``kernels.flash_bwd_schedule`` counts which. Explicit blocks are honoured,
and ask for dq and dk/dv.

Beside the unmasked pair above, three forward launches of their own, for the
decoder stacks under ``models/hybrid.py`` (sampled only: none has a backward
yet, and each says so by name): ``fwd_masked`` (causal and window masks on
shared K/V heads, the chunks outside a q block's mask skipped in the grid;
ONE program folds a fetched chunk into several query heads of its K/V head,
as independent chains — grid ``(rows, groups of heads, q blocks, visited
chunks)``, the heads a program and its q block chosen from the shape under a
budget on the compiled body, the launch's trace kept for the layers that
launch the same shapes: :func:`_masked_fold`, :func:`_fwd_masked_call`),
``fwd_selected`` (the same body under a per-query key selection) and
``fwd_latent`` (the same body over a score that is the SUM of two products,
the second over a key part all the heads share, with the value head at its
own width: :func:`flash_attention_latent`).

On the CPU backend the kernels run in interpreter mode, so tests exercise
the identical code paths; any other non-TPU backend is an error — a caller
that asked for the kernel never gets a different computation in its place.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.rotary import Rotary, rotary_tables
from ddim_cold_tpu.utils import flops, profiling

_NEG_INF = -1e30
_LANE = 128  # TPU lane width: last dim of VMEM tiles

#: which schedule and which operand layout each trace of the forward and of
#: the backward engaged (``kernels.flash_fwd_schedule``,
#: ``kernels.flash_fwd_layout``, ``kernels.flash_bwd_schedule``,
#: ``kernels.flash_bwd_layout``)
_kernels = metrics.scope("kernels")

#: names the GEMM dtype contract of the kernels (operands in the input dtype,
#: f32 MXU accumulation; v3 added :func:`fused_trunk_attention`):
#: ``tests/test_flash_attention.py`` pins that contract to this string, so a
#: change of contract has to change both
KERNEL_REV = "fused-trunk-v3"

#: (block_q, block_kv) the fused trunk path falls back to where
#: ``tuning.attn_blocks`` has no tuned row; a block_kv ≥ N is clamped to the
#: padded sequence, i.e. one chunk. The unfused ``flash_attention`` picks its
#: blocks from the shape (``_fwd_blocks``) and does not read it
NS_FLASH_BLOCKS = (512, 4096)

#: the block geometries ``analysis/entries.kernel_entries`` and
#: ``tests/test_flash_attention.py`` pre-check against Mosaic's tile rules
#: at 2,501 tokens
FLASH_BLOCK_SWEEP = ((512, 512), (256, 1024), (256, 4096), (512, 4096))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _scale_folds_into_q(scale: float) -> bool:
    """A power-of-two ``scale`` (head size 64: 2⁻³) multiplies q exactly in
    any float dtype, and every product and partial sum of q·kᵀ with it: the
    (bq, D) multiply then gives bit for bit what scaling the (bq, bkv) f32
    scores gives. Any other scale would round in q's dtype: scores scaled."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale: float, n_valid: int,
                block_kv: int, n_kv: int, heads: int, zero_v_tail: bool):
    """One (row, lane group, q-block, kv-block) program: fold this K/V chunk
    into the running softmax state of each of the ``heads`` heads that share
    the block's lanes (one head on the head-major path); emit o = acc/l and
    (where the launch has that result: ``rest`` is then lse, acc, m, l)
    lse = m + log l on the last chunk.

    Heads of a lane group are told apart without slicing lanes: head ``h``'s
    q tile has the other heads' lanes zeroed, so the contraction over all the
    lanes adds exact zeros to its scores (as the head-major path's zero
    padding does), and ``p_h · v`` over the whole V tile holds head ``h``'s
    context in its own lanes, which alone are selected into the accumulator.
    One (bq, bkv) f32 score tile is live at a time, heads in sequence."""
    lse_ref = rest[0] if len(rest) == 4 else None
    acc_ref, m_ref, l_ref = rest[-3:]
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # GEMMs run in the INPUT dtype with f32 MXU accumulation
    # (preferred_element_type): for bf16 models this is the native-speed MXU
    # path (an explicit f32 upcast here costs ~4× MXU throughput on v5e and
    # doubles VMEM traffic); for f32 inputs it is bit-identical to the old
    # explicit-upcast form. Softmax stays f32 either way.
    q = q_ref[0]  # (bq, lanes)
    k = k_ref[0]  # (bkv, lanes)
    v = v_ref[0]
    fold = _scale_folds_into_q(scale)
    if fold:
        q = q * scale  # (bq, lanes) multiplies instead of (bq, bkv)
    if zero_v_tail:
        # a ragged last chunk: rows past the sequence hold whatever the
        # buffer held (K's are masked below; p there is an exact 0, and
        # 0 × garbage is NaN)
        row = kv_i * block_kv + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(row < n_valid, v, jnp.zeros_like(v))
    head_dim = q.shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)

    def own(h):  # the lanes of head h, (bq, lanes)
        return (lane >= h * head_dim) & (lane < (h + 1) * head_dim)

    for h in range(heads):
        q_h = q if heads == 1 else jnp.where(own(h), q, jnp.zeros_like(q))
        logits = jax.lax.dot_general(
            q_h, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bkv) f32
        if not fold:
            logits = logits * scale
        col = kv_i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(col < n_valid, logits, _NEG_INF)

        # online softmax update (the same math the ring-attention steps use,
        # parallel/ring_attention.py:62-71, here per VMEM chunk)
        m_prev = jnp.max(m_ref[h], axis=-1, keepdims=True)  # (bq, 1) replicated
        l_prev = jnp.max(l_ref[h], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)  # (bq, bkv)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # p rounds to v's dtype for the MXU (f32 accumulate); exact for f32
        # v, ≤1 bf16 ulp per product for bf16 v — inside the model's own
        # precision
        acc = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_ref[...] = acc if heads == 1 else jnp.where(own(h), acc,
                                                        acc_ref[...])
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(kv_i == n_kv - 1)
    def _emit():
        def per_lane(stat):  # head h's (bq, 1) statistic on head h's lanes
            tile = jnp.broadcast_to(stat(0), lane.shape)
            for h in range(1, heads):
                tile = jnp.where(own(h), stat(h), tile)
            return tile

        m = lambda h: jnp.max(m_ref[h], axis=-1, keepdims=True)  # noqa: E731
        l = lambda h: jnp.max(l_ref[h], axis=-1, keepdims=True)  # noqa: E731
        o_ref[0] = (acc_ref[...] / per_lane(l)).astype(o_ref.dtype)
        if lse_ref is not None:
            # lane-replicated (bq, LANE), head h's value on head h's lanes
            # (the lanes of an accumulator wider than LANE hold one head):
            # a (1, bq) row block would violate the TPU (8, 128) tile rule —
            # Mosaic rejects sublane-dim-1 blocks unless they equal the
            # array dim (hit at N=2501 on real hardware)
            lse_ref[0, 0] = per_lane(lambda h: m(h) + jnp.log(l(h)))[:, :_LANE]


def _sds(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes type — needed
    when the kernel runs inside a ``shard_map`` (e.g. as Ulysses' local
    attention) where ``check_vma`` requires outputs to declare their vma."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _to_heads(x, B, N, H, D):
    """(B, N, H, D) → (B·H, N⁺, D⁺): one grid row per head's sequence,
    lane-aligned head dim (zero columns are inert in q·kᵀ and produce zero
    output columns, sliced off at the end), sublane-aligned N. The layout of
    the shapes the kernels cannot address in place
    (:func:`_heads_per_lane_group`), forward and backward: every call is a
    transpose and a pad in HBM."""
    x = x.transpose(0, 2, 1, 3).reshape(B * H, N, D)
    x = _pad_to(x, 2, _LANE)
    return _pad_to(x, 1, 8)


def _heads_per_lane_group(num_heads: int, head_dim: int) -> int | None:
    """How many heads share one 128-lane column block of the token-major
    ``(B, N, H·D)`` layout, where the kernels can address q, k, v, the context
    and their gradients in place: whole heads fill the lanes (head sizes 32,
    64, 128) and the heads fill whole column blocks. ``None`` for every other
    shape (head size 80 or 256; one local head of 64 under Ulysses): those
    are laid out head-major first (:func:`_to_heads`), forward and
    backward."""
    if _LANE % head_dim == 0 and (num_heads * head_dim) % _LANE == 0:
        return _LANE // head_dim
    return None


def _unpack(operands, num_heads: int):
    """The ``(B, N, H, D)`` views of q, k, v: of three ``(B, N, H·D)`` arrays,
    or of one ``(B, N, 3·H·D)`` projection packed ``(3, heads, head_dim)``
    along its columns (models/vit.Attention's qkv)."""
    if len(operands) == 1:
        B, N, W = operands[0].shape
        qkv = operands[0].reshape(B, N, 3, num_heads, W // (3 * num_heads))
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    return tuple(x.reshape(*x.shape[:2], num_heads, -1) for x in operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _attention(operands, num_heads, scale, block_q, block_kv):
    """The one differentiable attention both entries call: ``operands`` is
    ``(q, k, v)``, each ``(B, N, H·D)``, or the packed ``(qkv,)``; the
    context comes back ``(B, N, H·D)``."""
    return _flash_forward(operands, num_heads, scale, block_q, block_kv,
                          with_lse=False)[0]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: float,
    block_q: int | None = None,
    block_kv: int | None = None,
    *,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Fused multi-head attention; non-causal, one K/V head a query head,
    unless said (``causal``, ``window``, fewer heads in ``k`` and ``v`` than
    in ``q``: :func:`flash_attention_masked`, another launch).

    q/k/v: ``(B, N, H, D)`` (the model's head layout, ViT.py:104-107), three
    arrays of their own; returns ``(B, N, H, D)`` in q's dtype. Softmax runs
    in float32 regardless of input dtype, matching the einsum path
    bit-for-bit up to GEMM precision. A ``(B, N, H, D)`` array IS the
    token-major ``(B, N, H·D)`` the forward reads in place (head sizes 32,
    64, 128 with ``H·D`` a multiple of 128); any other shape is transposed
    and padded to head-major first, with the same result. A caller that
    holds q, k, v as one projection ``(B, N, 3·H·D)`` calls
    :func:`flash_attention_qkv` and never slices it.

    Blocks left ``None`` are chosen from the shape (:func:`_fwd_blocks`): the
    forward takes one head's whole K and V as a single VMEM-resident chunk
    wherever that fits, and streams K/V chunks (VMEM ≈ (block_q +
    2·block_kv)·128-lane input tiles plus the f32 accumulator, independent of
    N) where it does not; the backward's two kernels choose theirs by the
    same rule (:func:`_bwd_blocks`: K and V resident for dq, q and the
    cotangent resident for dkv, else (256, 512)). Explicit blocks are
    honoured, forward and backward. This undifferentiated call writes no
    log-sum-exp; under ``jax.grad`` the forward of the VJP does.
    """
    B, N, H, D = q.shape
    if causal or window is not None or k.shape[2] != H:
        if block_q is not None or block_kv is not None:
            raise ValueError("the masked forward picks its blocks from the "
                             "shape: leave block_q and block_kv unset")
        return flash_attention_masked(q, k, v, scale, causal=causal,
                                      window=window)
    out = _attention(tuple(x.reshape(B, N, H * D) for x in (q, k, v)), H,
                     scale, block_q, block_kv)
    return out.reshape(B, N, H, D)


def flash_attention_qkv(
    qkv: jax.Array,
    num_heads: int,
    scale: float,
    block_q: int | None = None,
    block_kv: int | None = None,
) -> jax.Array:
    """:func:`flash_attention` on the projection as the qkv GEMM wrote it.

    qkv: ``(B, N, 3·C)`` whose columns unpack as ``(3, heads, head_dim)``
    (q's heads, then k's, then v's: models/vit.Attention); returns the
    context ``(B, N, C)``, which is what ``proj`` reads. Where the forward
    addresses its operands in place it is handed the one array three times
    with column-block offsets 0, C/128 and 2C/128, so q, k and v are never
    cut out of it; the same values, blocks and VJP as :func:`flash_attention`
    on the three slices, and the gradient comes back as one ``(B, N, 3·C)``
    array that the two backward launches fill in place.
    """
    return _attention((qkv,), num_heads, scale, block_q, block_kv)


def kernel_interpret() -> bool:
    """``interpret=`` for every Pallas call in ops/: the TPU compiles the
    kernel, the CPU interprets it (so tests run the identical code path), and
    any other backend raises — computing the same maths some other way would
    hide that the kernel the caller asked for never ran."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas TPU kernels run on the tpu backend (compiled) or the cpu "
        f"backend (interpreted), not on {backend!r} — build the model with "
        "use_flash=False / quant='xla' there")


def rows_spec(n_rows: int) -> P:
    """Spec for an array whose leading dim is a batch of independent rows:
    split over the ambient mesh's ``data`` axis when it divides, else whole."""
    size = jax.sharding.get_abstract_mesh().shape.get("data", 1)
    return P("data") if size > 1 and n_rows % size == 0 else P()


def per_device(call, in_specs, out_specs):
    """``call(*arrays)`` launches ONE Mosaic kernel. jit cannot partition such
    a kernel over a mesh — lowering refuses it ("wrap the call in a
    shard_map") — so under a multi-device ambient mesh (``jax.set_mesh``, which
    the trainer, the samplers and the serving engine enter around their
    programs) every device launches it on its own block per the specs. No
    mesh, one device, or already inside a shard_map: ``call`` as it is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return call
    return jax.shard_map(call, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _fwd_call(q, k, v, *, offsets, groups, heads, lanes, out_tokens, scale,
              n_valid, bq, bkv, with_lse, interpret):
    """The forward launch. q, k, v are ``(rows, tokens, columns)`` arrays (one
    array handed over three times when the projection is packed), read in
    ``(1, block, lanes)`` blocks at column block ``offsets[i] + g`` for lane
    group ``g`` of ``groups``, each holding ``heads`` heads; the token axis
    may end inside the last block. Results: the context ``(rows, out_tokens,
    groups·lanes)``, written through the same blocks, then (``with_lse``) the
    log-sum-exp ``(rows, groups, padded tokens, 128)``, head ``h`` of the
    group on its own lanes. With one K/V chunk the K/V block index is the
    same for every q block of a lane group, so the pipeline fetches its K and
    V once: that is the resident schedule."""
    rows = q.shape[0]
    n_q, n_kv = pl.cdiv(out_tokens, bq), pl.cdiv(k.shape[1], bkv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, n_valid=n_valid, block_kv=bkv, n_kv=n_kv,
        heads=heads, zero_v_tail=k.shape[1] % bkv != 0)
    q_off, k_off, v_off = offsets
    out_specs = [pl.BlockSpec((1, bq, lanes), lambda b, g, i, j: (b, i, g))]
    out_shape = [_sds((rows, out_tokens, groups * lanes), q.dtype, q)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, bq, _LANE),
                                      lambda b, g, i, j: (b, g, i, 0)))
        out_shape.append(_sds((rows, groups, n_q * bq, _LANE), jnp.float32, q))
    with profiling.scope("flash_attention/fwd"):
        return tuple(pl.pallas_call(
            kernel,
            grid=(rows, groups, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((1, bq, lanes),
                             lambda b, g, i, j: (b, i, q_off + g)),
                pl.BlockSpec((1, bkv, lanes),
                             lambda b, g, i, j: (b, j, k_off + g)),
                pl.BlockSpec((1, bkv, lanes),
                             lambda b, g, i, j: (b, j, v_off + g)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((bq, lanes), jnp.float32),         # output accumulator
                pltpu.VMEM((heads, bq, _LANE), jnp.float32),  # running max
                pltpu.VMEM((heads, bq, _LANE), jnp.float32),  # running denominator
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary"),
            ),
            interpret=interpret,
            name="fwd",
        )(q, k, v))


def _flash_forward(operands, num_heads, scale, block_q, block_kv, *, with_lse):
    """``(context (B, N, H·D), lse)`` of ``operands`` as :func:`_attention`
    takes them; ``lse`` is ``None`` unless ``with_lse`` (the VJP's forward),
    and then ``(B·H, padded tokens)``, one lane: O(N) across the backward,
    not O(N·128)."""
    interpret = kernel_interpret()
    packed = len(operands) == 1
    B, N, W = operands[0].shape
    C = W // 3 if packed else W
    H, D = num_heads, C // num_heads
    in_place = _heads_per_lane_group(H, D)
    _kernels.inc("kernels.flash_fwd_layout",
                 key="in_place" if in_place else "head_major")
    if in_place:
        # where the projection wrote them: nothing is moved, and the token
        # axis ends inside the last block. Blocks as the head-major path
        # picks them, so the two are bit for bit each other's
        arrays = operands * 3 if packed else operands
        offsets = tuple(i * C // _LANE for i in range(3)) if packed else (0,) * 3
        rows, groups, heads, lanes, out_tokens = B, C // _LANE, in_place, _LANE, N
        bq, bkv = _fwd_blocks(block_q, block_kv, tiling.round_up(N, 8), lanes,
                              arrays[0].dtype, heads)
    else:
        qh, kh, vh = (_to_heads(x, B, N, H, D) for x in _unpack(operands, H))
        bq, bkv = _fwd_blocks(block_q, block_kv, *qh.shape[1:], qh.dtype)
        arrays = (_pad_to(qh, 1, bq), _pad_to(kh, 1, bkv), _pad_to(vh, 1, bkv))
        offsets = (0,) * 3
        rows, groups, heads = B * H, 1, 1
        out_tokens, lanes = arrays[0].shape[1:]
    _kernels.inc("kernels.flash_fwd_schedule",
                 key="resident" if N <= bkv else "streamed")
    _kernels.inc("kernels.flash_fwd_mask", key="none")
    spec = rows_spec(rows)
    out, *lse = per_device(
        functools.partial(
            _fwd_call, offsets=offsets, groups=groups, heads=heads,
            lanes=lanes, out_tokens=out_tokens, scale=scale, n_valid=N, bq=bq,
            bkv=bkv, with_lse=with_lse, interpret=interpret),
        (spec, spec, spec), (spec,) * (1 + with_lse))(*arrays)

    if not in_place:
        out = out[:, :N, :D].reshape(B, H, N, D).transpose(0, 2, 1, 3)
        out = out.reshape(B, N, C)
    if with_lse:
        # (rows, groups, tokens⁺, 128), head h on its lanes → (B·H, tokens⁺)
        lse = lse[0][..., ::_LANE // heads].transpose(0, 1, 3, 2)
        return out, lse.reshape(B * H, -1)
    return out, None


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

#: sublanes of the row-statistics blocks (lse, delta): a head a sublane,
#: rounded up to the f32 tile
_STAT_ROWS = 8

#: lse of a token past the sequence in the row statistics: exp(s − this) is
#: an exact 0 for every finite score
_LSE_PAST_END = 1e30


def _head_picker(shape: tuple, heads: int):
    """``pick(h, x, other=None)`` for (rows, lanes) tiles of ``shape`` holding
    ``heads`` heads side by side on the lanes: ``x`` on head ``h``'s lanes and
    ``other`` (zeros if ``None``) on the rest; ``x`` itself where one head
    has the lanes to itself."""
    if heads == 1:
        return lambda h, x, other=None: x
    head_dim = shape[-1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def pick(h, x, other=None):
        own = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
        return jnp.where(own, x, jnp.zeros_like(x) if other is None else other)

    return pick


def _bwd_dq_kernel(lse_ref, q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref,
                   delta_ref, acc_ref, *, scale: float, n_valid: int,
                   block_kv: int, n_kv: int, heads: int, ragged_kv: bool):
    """One (row, lane group, q-block, kv-block) program of
    dq_i = scale · Σ_j ds_ij·k_j, K/V chunks innermost (one chunk: K and V
    resident across the lane group's q blocks, as in the forward).

    The ``heads`` heads on the block's lanes are told apart as in
    :func:`_fwd_kernel`: head ``h``'s q and do tiles have the other heads'
    lanes zeroed, so both score-shaped GEMMs add exact zeros for them, and
    ``ds_h · k`` over the whole K tile holds head ``h``'s dq in its own
    lanes, which alone are selected into the accumulator. lse arrives as rows
    (:func:`_lse_rows`); the (8, bq) block is turned once a program into the
    columns the (bq, bkv) tiles need. delta_i = Σ_d o_id·do_id is a head's
    lane sum of the o and do blocks the program holds, and goes out as rows,
    ``(8, bq)`` with head ``h`` on sublane ``h``, for the dkv launch. A q row
    past the sequence feeds only its own, dropped, dq row and its delta,
    which the dkv launch does not take (it selects what it reads there);
    K/V rows past it (``ragged_kv``) hold whatever the buffer held, so their
    ds columns are selected to an exact 0 and their K rows zeroed (0 ×
    garbage is NaN)."""
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # input-dtype GEMMs, f32 accumulation — see _fwd_kernel
    q = q_ref[0]    # (bq, lanes)
    k = k_ref[0]    # (bkv, lanes)
    v = v_ref[0]
    do = do_ref[0]  # (bq, lanes)
    fold = _scale_folds_into_q(scale)
    if fold:
        q = q * scale  # (bq, lanes) multiplies instead of (bq, bkv)
    if ragged_kv:
        row = kv_i * block_kv + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
        k = jnp.where(row < n_valid, k, jnp.zeros_like(k))
        col_ok = kv_i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], k.shape[0]), 1) < n_valid
    lse = lse_ref[0, 0].T  # (bq, 8): head h's in column h
    o_do = o_ref[0].astype(jnp.float32) * do.astype(jnp.float32)
    pick = _head_picker(acc_ref.shape, heads)  # on (bq, lanes) tiles

    deltas = []
    for h in range(heads):  # unrolled: the compiler overlaps the heads
        deltas.append(jnp.sum(pick(h, o_do), axis=-1, keepdims=True))  # (bq, 1)
        logits = jax.lax.dot_general(
            pick(h, q), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bkv) f32
        if not fold:
            logits = logits * scale
        p = jnp.exp(logits - lse[:, h:h + 1])
        dp = jax.lax.dot_general(
            pick(h, do), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bkv) f32
        ds = p * (dp - deltas[h])
        if ragged_kv:
            ds = jnp.where(col_ok, ds, 0.0)
        acc = acc_ref[...] + jnp.dot(ds.astype(k.dtype), k,
                                     preferred_element_type=jnp.float32)
        acc_ref[...] = pick(h, acc, acc_ref[...])

    @pl.when(kv_i == 0)
    def _emit_delta():  # columns → the rows dkv reads: head h on sublane h
        at = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], _LANE), 1)
        tile = jnp.zeros(at.shape, jnp.float32)
        for h in range(heads):
            tile = jnp.where(at == h, deltas[h], tile)
        delta_ref[0, 0] = tile.T[:_STAT_ROWS]

    @pl.when(kv_i == n_kv - 1)
    def _emit():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(lse_ref, delta_ref, q_ref, k_ref, v_ref, do_ref, *rest,
                    scale: float, n_valid: int, block_q: int, n_q: int,
                    heads: int, ragged_q: bool, packed: bool):
    """One (row, lane group, kv-block, q-chunk) program of
    dv_j = Σ_i p_ij·do_i and dk_j = scale · Σ_i ds_ij·q_i — grid transposed:
    one K/V block per (outer) program, q chunks innermost (one chunk: q and
    do resident across the lane group's K/V blocks).

    The scores are computed TRANSPOSED, ``sᵀ = k·qᵀ`` (bkv, bq), so that
    ``pᵀ·do`` and ``dsᵀ·q`` contract over the last axis of their left
    operand — no (bq, bkv) transposes — and lse and delta broadcast as the
    (1, bq) rows they arrive as. Heads as in :func:`_bwd_dq_kernel`, with K
    and V the masked side. A K/V row past the sequence feeds only its own,
    dropped, dk and dv rows; q and do rows past it (``ragged_q``) are zeroed
    and their statistics selected to lse ``_LSE_PAST_END`` and delta 0 — here,
    where they are read: the dq launch tiles the tokens with its own q block
    and writes delta only as far as that reaches, so part of this chunk's may
    be memory nobody wrote — and p and ds there are an exact 0.

    ``packed``: dk and dv are two column blocks of ONE array, the projection's
    gradient that the dq launch began (``rest[0]``, aliased to the result and
    never read). A program can hold one block of a result, so the innermost
    axis has one step more: the result's block is dk's through the q chunks
    and dv's at the extra step, where nothing is computed; the pipeline
    writes each back when the block index moves on."""
    if packed:
        _, dk_ref, dk_acc, dv_acc = rest
        dv_ref = dk_ref
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    q_i = pl.program_id(3)

    @pl.when(q_i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _fold_chunk():
        # input-dtype GEMMs, f32 accumulation — see _fwd_kernel
        q = q_ref[0]    # (bq, lanes)
        k = k_ref[0]    # (bkv, lanes)
        v = v_ref[0]
        do = do_ref[0]  # (bq, lanes)
        fold = _scale_folds_into_q(scale)
        if fold:
            k = k * scale  # the same products as q · scale, bit for bit
        if ragged_q:
            row = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, q.shape, 0)
            q = jnp.where(row < n_valid, q, jnp.zeros_like(q))
            do = jnp.where(row < n_valid, do, jnp.zeros_like(do))
        lse = lse_ref[0, 0]      # (8, bq): head h's on sublane h
        delta = delta_ref[0, 0]  # (8, bq)
        if ragged_q:
            tok = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, lse.shape, 1)
            lse = jnp.where(tok < n_valid, lse, _LSE_PAST_END)
            delta = jnp.where(tok < n_valid, delta, 0.0)
        pick = _head_picker(dk_acc.shape, heads)  # on (bkv, lanes) tiles

        for h in range(heads):  # unrolled: the compiler overlaps the heads
            logits = jax.lax.dot_general(
                pick(h, k), q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bkv, bq) f32
            if not fold:
                logits = logits * scale
            p = jnp.exp(logits - lse[h:h + 1])
            dv = dv_acc[...] + jnp.dot(p.astype(do.dtype), do,
                                       preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                pick(h, v), do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bkv, bq) f32
            ds = p * (dp - delta[h:h + 1])
            dk = dk_acc[...] + jnp.dot(ds.astype(q.dtype), q,
                                       preferred_element_type=jnp.float32)
            dv_acc[...] = pick(h, dv, dv_acc[...])
            dk_acc[...] = pick(h, dk, dk_acc[...])

    if packed:
        pl.when(q_i < n_q)(_fold_chunk)
    else:
        _fold_chunk()

    @pl.when(q_i == n_q - 1)
    def _emit():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        if not packed:
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if packed:
        @pl.when(q_i == n_q)
        def _emit_dv():
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dqkv_kernel(lse_ref, q_ref, k_ref, v_ref, o_ref, do_ref, *rest,
                     scale: float, n_valid: int, block_kv: int, n_kv: int,
                     heads: int, ragged_q: bool, ragged_kv: bool, packed: bool):
    """One (row, lane group, step) program of ALL THREE gradients: K/V blocks
    along the steps; q, the context, the cotangent and the statistics
    resident as ONE chunk, the whole lane-padded sequence. Per head and K/V
    block the scores are multiplied once, TRANSPOSED as in
    :func:`_bwd_dkv_kernel` (``sᵀ = k·qᵀ``, (bkv, whole)), ``p = exp(sᵀ −
    lse)`` and ``ds = p·(dp − delta)`` are formed once, and five GEMMs take
    everything from them where dq + dkv run seven: sᵀ, dpᵀ = v·doᵀ,
    dv_j = pᵀ·do and dk_j = scale · dsᵀ·q as dkv takes them, and
    dq += scale · ds·k_j — the one product that contracts over the tile's
    FIRST axis — taken the other way round, ``dqᵀ += k_jᵀ·dsᵀ``: the small
    operand is transposed, a (lanes, bkv) tile a step, the product is a plain
    GEMM into a transposed (lanes, whole) f32 accumulator that lives in VMEM
    across the steps, and dq's rows are transposed back block by block as
    they are written, once a program (the (bkv, whole) tile itself is never
    transposed: PERF.md section 6, PR 34, has what each way costs). Head
    ``h``'s dq is head ``h``'s ROWS of that accumulator, so its product takes
    those rows of ``k_jᵀ`` alone — (head_dim, bkv)·(bkv, whole), no lanes
    wasted on the other heads and nothing to select.

    delta_i = Σ_d o_id·do_id is formed at the first step from the resident
    context and cotangent, a head a sublane like lse, into VMEM scratch: it
    never reaches HBM. Ragged edges as in :func:`_bwd_dkv_kernel`: q and do
    rows past the sequence (``ragged_q``) zeroed, their lse selected to
    ``_LSE_PAST_END`` and their delta to 0, so p and ds there are an exact 0;
    K/V rows past it (``ragged_kv``) zeroed, since they feed dq (0 × garbage
    is NaN), and their own dk and dv rows are dropped.

    Steps: K/V block j is folded at step j and its dk and dv written; then
    dq's rows go out block by block, ``n_kv`` steps more. ``packed``: the
    three gradients are column blocks of ONE array and a program holds one
    block of a result, so a K/V block takes two steps — dk_j's column block
    at 2j, dv_j's (kept in scratch meanwhile) at 2j + 1, where nothing is
    computed — and dq's follow."""
    if packed:
        dk_ref, dq_acc, dv_acc, delta_ref = rest
        dq_ref = dv_ref = dk_ref
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, delta_ref = rest
    t = pl.program_id(2)
    whole = q_ref.shape[1]
    fold = _scale_folds_into_q(scale)

    def past_end(shape, axis, start=0):
        return start + jax.lax.broadcasted_iota(jnp.int32, shape, axis) >= n_valid

    @pl.when(t == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        o_do = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
        pick = _head_picker(o_do.shape, heads)
        at = jax.lax.broadcasted_iota(jnp.int32, (whole, _LANE), 1)
        tile = jnp.zeros(at.shape, jnp.float32)
        for h in range(heads):
            tile = jnp.where(at == h, jnp.sum(pick(h, o_do), axis=-1,
                                              keepdims=True), tile)
        delta = tile.T[:_STAT_ROWS]  # columns → rows: head h on sublane h
        if ragged_q:
            delta = jnp.where(past_end(delta.shape, 1), 0.0, delta)
        delta_ref[...] = delta

    def _fold_tile(kv_i):
        # input-dtype GEMMs, f32 accumulation — see _fwd_kernel
        q = q_ref[0]    # (whole, lanes)
        do = do_ref[0]
        k = k_ref[0]    # (bkv, lanes)
        v = v_ref[0]
        if fold:
            k = k * scale  # the same products as q · scale, bit for bit
        lse = lse_ref[0, 0]  # (8, whole): head h's on sublane h
        if ragged_q:
            q = jnp.where(past_end(q.shape, 0), jnp.zeros_like(q), q)
            do = jnp.where(past_end(do.shape, 0), jnp.zeros_like(do), do)
            lse = jnp.where(past_end(lse.shape, 1), _LSE_PAST_END, lse)
        if ragged_kv:
            stale = past_end(k.shape, 0, kv_i * block_kv)
            k = jnp.where(stale, jnp.zeros_like(k), k)
            v = jnp.where(stale, jnp.zeros_like(v), v)
        delta = delta_ref[...]
        pick = _head_picker(k.shape, heads)  # on (bkv, lanes) tiles
        k_t = k.T  # (lanes, bkv): head h's head_dim rows are its own
        head_dim = k_t.shape[0] // heads
        dk = dv = None
        for h in range(heads):  # unrolled: the compiler overlaps the heads
            logits = jax.lax.dot_general(
                pick(h, k), q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bkv, whole) f32
            if not fold:
                logits = logits * scale
            p = jnp.exp(logits - lse[h:h + 1])
            dv = pick(h, jnp.dot(p.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32), dv)
            dp = jax.lax.dot_general(
                pick(h, v), do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bkv, whole) f32
            ds = (p * (dp - delta[h:h + 1])).astype(q.dtype)
            dk = pick(h, jnp.dot(ds, q, preferred_element_type=jnp.float32),
                      dk)
            own = slice(h * head_dim, (h + 1) * head_dim)
            dq_acc[own, :whole] += jnp.dot(
                k_t[own], ds, preferred_element_type=jnp.float32)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        if packed:
            dv_acc[...] = dv
        else:
            dv_ref[0] = dv.astype(dv_ref.dtype)

    per = 2 if packed else 1  # steps a K/V block
    pl.when((t < per * n_kv) & (t % per == 0))(lambda: _fold_tile(t // per))
    if packed:
        @pl.when((t < 2 * n_kv) & (t % 2 == 1))
        def _emit_dv():
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(t >= per * n_kv)
    def _emit_dq():
        start = pl.multiple_of((t - per * n_kv) * block_kv, block_kv)
        rows = dq_acc[:, pl.ds(start, block_kv)].T
        dq_ref[0] = (rows if fold else rows * scale).astype(dq_ref.dtype)


#: the scoped VMEM ONE kernel's blocks, scratch and live temporaries must fit
#: on the chip this repo targets — the compiler refuses the kernel otherwise
#: ("exceeded scoped vmem limit"). Applied whatever the backend, so the CPU
#: interpreter tiles exactly as the chip will.
_SCOPED_VMEM_BYTES = flops.vmem_bytes("TPU v5 lite")


def _bwd_vmem_bytes(kernel: str, bq: int, bkv: int, dp: int, itemsize: int,
                    heads: int = 1) -> int:
    """Scoped VMEM the backward ``kernel`` (``"dq"``, ``"dkv"`` or
    ``"dqkv"``) needs at blocks (bq, bkv) with ``heads`` heads on the block's
    lanes. ``"dq"`` and ``"dkv"``: the (bq, bkv)
    tiles the compiler keeps alive (scores, p, dp, ds and the cast to the
    GEMM feed come to 2 × itemsize bytes an element: one f32 tile in bf16,
    two in f32, and no more for the other heads of a lane group), what
    scales with the q rows (dq: the double-buffered q, o, do and dq blocks,
    the f32 accumulator, o·do and the delta tile, the transposed lse and
    every head's masked q and do; dkv: q and do, twice, and their zeroed
    copies) and what scales with the K/V rows (dq: K and V, twice, and K
    zeroed; dkv: K, V, dk and dv, twice, two f32 accumulators and a head's
    masked K and V). An upper bound fitted on the sizes the v5e compiler
    reports where it refuses — bf16 and f32, 256 to 2,560 rows a side, one,
    two and four heads a lane group, 2,501 tokens: never under a reported
    size, within 4.5 MiB of it in bf16 at two and four heads near the limit
    and further over elsewhere (one head; float32, which the compiler packs
    tighter than linearly) — tests/test_chip_compile.py compiles what this
    admits, at its edge too.

    ``"dqkv"`` (bq = the whole padded sequence, resident): the (bkv, bq) tiles
    at 2 × itemsize + 1 bytes an element (dkv's, and ds's cast alive for its
    two products; a K/V block narrower than the heads' lanes together does
    not make them smaller), what scales with the sequence (q, o and do
    double-buffered, the f32 dqᵀ accumulator, lse and delta rows) and what
    scales with the K/V block (K and V double-buffered, the result blocks —
    three of them where q, k, v are apart — a head's masked K, the transposed
    K, dv's scratch) plus 1 MiB. Fitted the same way on the v5e compiler's
    refusals — both dtypes, one, two and four heads a lane group, K/V blocks
    of 512, 256 and 128, packed and apart, the sequence raised by 128 rows
    until it refuses, 1,536 to 6,400 rows: never under a reported size, 1.8
    to 3.8 MiB over it in bf16 and 0.2 to 9.3 in f32 (most at four heads and
    small blocks), so it admits 2,944 rows at block 512 in bf16 (14.1 MiB at
    the trunk's 2,560) where the compiler takes 3,584 (3,328 at four
    heads)."""
    if kernel == "dqkv":
        tiles = (2 * itemsize + 1) * bq * max(bkv, heads * _LANE)
        return (tiles + bq * (dp * (6 * itemsize + 4) + 96)
                + bkv * dp * (11 * itemsize + 4) + (1 << 20))
    tiles = 2 * itemsize * bq * bkv
    if kernel == "dq":
        rows = (bq * (dp * ((3 + 2 * heads) * itemsize + 36) + 64)
                + bkv * dp * 5 * itemsize)
    else:
        rows = (bq * (dp * (6 * itemsize + 2) + 64)
                + bkv * dp * (10 * itemsize + 8))
    return tiles + rows + (1 << 19)


def _fwd_vmem_bytes(bq: int, bkv: int, dp: int, itemsize: int,
                    heads: int = 1) -> int:
    """Scoped VMEM the forward needs at blocks (bq, bkv = the whole padded
    sequence) with ``heads`` heads on the block's lanes: the double-buffered
    q, o, K and V blocks, the lane-replicated f32 rows (the lse result
    double-buffered, each head's running max and denominator) and one live
    (bq, bkv) f32 score tile A HEAD (for one head the compiler keeps the mask,
    exp and the cast to the GEMM feed in place, and the accumulator costs
    nothing measurable beside them; the heads of a lane group it interleaves,
    the next one's score GEMM under this one's softmax, and then holds 2.0 of
    2 and 3.6 of 4 tiles). An upper bound, within 0.2 to 2.2 MiB (4.4 at four
    heads), of the sizes the v5e compiler reports where it refuses (bf16 and
    f32, bq 128 to 1024, 2,560 to 14,336 rows, one, two and four heads, with
    and without lse) — tests/test_chip_compile.py compiles what this admits,
    at its edge too."""
    blocks = 4 * (bq + bkv) * dp * itemsize + (2 + 2 * heads) * bq * _LANE * 4
    return blocks + heads * 4 * bq * bkv + (1 << 17)


def _fwd_blocks(block_q, block_kv, n_pad: int, dp: int, dtype,
                heads: int = 1) -> tuple:
    """The forward's (block_q, block_kv), Mosaic-legal for this dtype and
    padded sequence (ops/tiling.py; min() alone produced illegal tiles at odd
    requests or sub-16 sublanes on bf16, N=2501 is the worst case), for
    ``heads`` heads on the block's lanes. Explicit blocks win. With
    ``block_kv`` left ``None`` the whole sequence, padded to the lane width, is
    one chunk — K and V resident, a lane-dense score tile — at the largest
    ``block_q`` of 512, 256, 128 (or the one given) that the VMEM model
    admits; where none fits, the streamed (256, 512). An explicit ``block_kv``
    that covers the sequence asks for the same schedule, and ``block_q`` is
    then halved until the model admits it (float32 with two heads on the lanes
    at 2,501 tokens: 512 → 256). Lane width and not the sublane minimum: a
    lane-dense score tile, and the length the backward's resident chunk takes
    too (2,560 rows at 2,501 tokens). The backward no longer pads K and V at
    all, so the old cost of padding them twice (PERF.md section 6, PR 25)
    is gone on both layouts: the head-major backward pads tokens to the
    sublane tile only and lets the last block end past the array."""
    isz = jnp.dtype(dtype).itemsize

    def fits(bq, bkv):
        return _fwd_vmem_bytes(bq, bkv, dp, isz, heads) <= _SCOPED_VMEM_BYTES

    if block_kv is None:
        whole = tiling.round_up(n_pad, _LANE)
        for want in ((512, 256, 128) if block_q is None else (block_q,)):
            bq = tiling.legal_block(want, n_pad, dtype)
            if fits(bq, whole):
                return bq, whole
    block_q, block_kv = _default_blocks(block_q, block_kv)
    bq = tiling.legal_block(block_q, n_pad, dtype)
    bkv = tiling.legal_block(block_kv, n_pad, dtype)
    while bkv >= n_pad and not fits(bq, bkv) and bq > tiling.sublane_unit(dtype):
        bq = tiling.legal_block(bq // 2, n_pad, dtype)
    return bq, bkv


def _default_blocks(block_q, block_kv) -> tuple:
    """The streamed schedule's blocks where the caller left them to the
    kernel, forward and backward."""
    return (256 if block_q is None else block_q,
            512 if block_kv is None else block_kv)


def _bwd_blocks(block_q, block_kv, n_pad: int, dp: int, dtype,
                heads: int = 1) -> dict:
    """The backward's launches and the (block_q, block_kv) of each,
    ``{"dqkv": ...}`` or ``{"dq": ..., "dkv": ...}``, Mosaic-legal for this
    dtype and padded sequence, from what the forward's choice looks at too:
    padded length, lanes, dtype, heads a lane group. A q block is also the
    lane dim of the statistics' block, so it is a multiple of 128 (or the
    whole padded sequence).

    With both blocks left to the kernels, ONE launch (``dqkv``: q, o, do and
    the whole f32 dq resident, K/V blocks along the steps) wherever the
    ``"dqkv"`` row of :func:`_bwd_vmem_bytes` admits the whole padded
    sequence, at the largest K/V block of 512, 256, 128 it admits: (2560, 512)
    at the 200px trunk's 2,501 tokens in bf16, where the launch alone is
    8.26 ms on the v5e (40 images, two lane groups of two heads) against
    11.69 ms for dq + dkv, 8.69 ms at (2560, 256) and 9.49 at (2560, 1024)
    with the scoped limit raised for it (PERF.md section 6, PR 34). Float32
    at two heads a lane group there, and sequences past 2,944 tokens (bf16),
    are not admitted and take the two launches, as explicit blocks do.

    Of the two launches, each takes the side it streams as ONE chunk wherever
    :func:`_bwd_vmem_bytes` admits that — dq keeps a lane group's K and V
    resident across its q blocks, dkv keeps q and do resident across its K/V
    blocks — at the largest other block of 512, 256, 128 (or the one given):
    (512, 2560) and (2560, 512) at the 200px trunk's 2,501 tokens in bf16,
    where a launch is 4.31 and 6.26 ms on the v5e against 5.94 and 8.04 ms
    at (256, 512) (PERF.md section 6, PR 29). Where nothing fits, and where
    both blocks are given, the streamed (256, 512) or the given pair, with
    the larger side halved until the kernel fits: its budget differs from the
    forward's, so forward-legal blocks (f32 at ``NS_FLASH_BLOCKS``) can
    overflow it."""
    isz = jnp.dtype(dtype).itemsize
    whole = tiling.round_up(n_pad, _LANE)

    def q_block(want):
        return tiling.legal_block(want, n_pad, dtype, min_unit=_LANE)

    def kv_block(want):
        return tiling.legal_block(want, n_pad, dtype)

    def fits(kernel, bq, bkv):
        return _bwd_vmem_bytes(kernel, bq, bkv, dp, isz,
                               heads) <= _SCOPED_VMEM_BYTES

    def streamed(kernel):
        bq, bkv = _default_blocks(block_q, block_kv)
        bq, bkv = q_block(bq), kv_block(bkv)
        while not fits(kernel, bq, bkv):
            smaller = ((bq, kv_block(max(1, bkv // 2))) if bkv >= bq
                       else (q_block(max(1, bq // 2)), bkv))
            if smaller == (bq, bkv):
                raise ValueError(
                    f"flash attention backward: no legal blocks fit "
                    f"{_SCOPED_VMEM_BYTES >> 20} MiB of VMEM at {dp} lanes "
                    f"({jnp.dtype(dtype).name}) — smallest tried {smaller}")
            bq, bkv = smaller
        return bq, bkv

    def resident(kernel, given):
        if (block_kv if kernel == "dq" else block_q) is not None:
            return None  # the streamed side was asked for in chunks
        other = q_block if kernel == "dq" else kv_block
        for want in ((512, 256, 128) if given is None else (given,)):
            pair = ((other(want), whole) if kernel == "dq"
                    else (whole, other(want)))
            if fits(kernel, *pair):
                return pair
        return None

    if block_q is None and block_kv is None:
        for bkv in map(kv_block, (512, 256, 128)):
            if fits("dqkv", whole, bkv):
                return {"dqkv": (whole, bkv)}
    return {"dq": resident("dq", block_q) or streamed("dq"),
            "dkv": resident("dkv", block_kv) or streamed("dkv")}


def _lse_rows(lse, n_valid: int, tokens: int):
    """The log-sum-exp as the backward kernels read it, ``(rows, groups, 8,
    tokens)`` f32: head ``h`` of the lane group on sublane ``h``, tokens on
    the lanes — 32 bytes a token and lane group where the lane-replicated
    columns the kernels once took were 512 a head, and the one-lane residual
    ``(rows, groups, heads, ≥ n_valid)`` as it is but for the padding. What
    lies past the sequence is padding and means nothing (the residual holds
    there whatever the forward computed for its stale q rows): dq's rows
    there are dropped, and dkv and dqkv select what they read there."""
    heads = lse.shape[2]
    return jnp.pad(lse[..., :n_valid], (
        (0, 0), (0, 0), (0, _STAT_ROWS - heads), (0, tokens - n_valid)))


def _bwd_dqkv_call(lse, q, k, v, o, do, *, offsets, groups, heads, lanes,
                   packed, scale, n_valid, whole, bkv, interpret):
    """The one launch of all three gradients (:func:`_bwd_dqkv_kernel`),
    operands and results as :func:`_bwd_call` says: grid (rows, lane groups,
    steps), q, o, do and lse as ``whole``-row blocks that stay put across a
    lane group's steps, K and V in blocks of ``bkv``; each result block is
    visited once — dk's and dv's K/V block by K/V block (``packed``: one after
    the other, two steps a K/V block), then dq's row blocks."""
    rows, n_tok = do.shape[:2]
    q_off, k_off, v_off = offsets
    width = groups * lanes
    n_kv = pl.cdiv(n_tok, bkv)
    per = 2 if packed else 1  # steps a K/V block

    def kv_block(t):  # stays at the last one while dq's rows go out
        return jnp.minimum(t // per, n_kv - 1)

    res_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, whole, lanes), lambda b, g, t: (b, 0, off + g))
    kv_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, bkv, lanes), lambda b, g, t: (b, kv_block(t), off + g))
    # dqᵀ as far as dq's last row block reads; (packed) dv_j until its step;
    # delta as rows
    scratch = [pltpu.VMEM((lanes, max(whole, n_kv * bkv)), jnp.float32),
               *([pltpu.VMEM((bkv, lanes), jnp.float32)] if packed else []),
               pltpu.VMEM((_STAT_ROWS, whole), jnp.float32)]
    if packed:
        def out_at(b, g, t):
            tile = t < 2 * n_kv
            return (b, jnp.where(tile, t // 2, t - 2 * n_kv),
                    jnp.where(tile, jnp.where(t % 2 == 0, k_off, v_off),
                              q_off) + g)

        out_specs = pl.BlockSpec((1, bkv, lanes), out_at)
        out_shape = _sds((rows, n_tok, 3 * width), q.dtype, q)
    else:
        out_specs = [pl.BlockSpec((1, bkv, lanes), lambda b, g, t: (
            b, jnp.maximum(t - n_kv, 0), g)), kv_spec(0), kv_spec(0)]
        out_shape = [_sds((rows, n_tok, width), q.dtype, q)] * 3
    with profiling.scope("flash_attention/dqkv"):
        out = pl.pallas_call(
            functools.partial(
                _bwd_dqkv_kernel, scale=scale, n_valid=n_valid, block_kv=bkv,
                n_kv=n_kv, heads=heads, ragged_q=n_valid != whole,
                ragged_kv=n_valid % bkv != 0, packed=packed),
            grid=(rows, groups, (per + 1) * n_kv),
            in_specs=[pl.BlockSpec((1, 1, _STAT_ROWS, whole),
                                   lambda b, g, t: (b, g, 0, 0)),
                      res_spec(q_off), kv_spec(k_off), kv_spec(v_off),
                      res_spec(0), res_spec(0)],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="dqkv",
        )(lse, q, k, v, o, do)
    return (out,) if packed else tuple(out)


def _bwd_call(lse, q, k, v, o, do, *, offsets, groups, heads, lanes, packed,
              scale, n_valid, blocks, interpret):
    """The backward's launches as ``blocks`` names them
    (:func:`_bwd_blocks`): ``dqkv``, or ``dq`` then ``dkv``. q, k, v, the
    context ``o`` and the cotangent ``do`` are addressed as :func:`_fwd_call`
    addresses q, k, v — ``(rows, tokens, columns)`` arrays read in
    ``(1, block, lanes)`` blocks at column block ``offsets[i] + g`` (``o`` and
    ``do`` at ``g``), the token axis free to end inside the last block — and
    the gradients go back through the same blocks: three ``(rows, tokens,
    groups·lanes)`` arrays, or (``packed``) ONE ``(rows, tokens,
    3·groups·lanes)`` array, which ``dqkv`` writes whole and which otherwise
    the dq launch creates and the dkv launch, taking it aliased to its own
    result, completes. ``lse``: :func:`_lse_rows`; the dq launch writes delta
    in the same form for the dkv launch, ``dqkv`` keeps its own in VMEM."""
    if "dqkv" in blocks:
        whole, bkv = blocks["dqkv"]
        return _bwd_dqkv_call(
            lse, q, k, v, o, do, offsets=offsets, groups=groups, heads=heads,
            lanes=lanes, packed=packed, scale=scale, n_valid=n_valid,
            whole=whole, bkv=bkv, interpret=interpret)
    rows, n_tok = do.shape[:2]
    q_off, k_off, v_off = offsets
    width = groups * lanes
    semantics = pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))

    bq, bkv = blocks["dq"]
    n_q, n_kv = pl.cdiv(n_tok, bq), pl.cdiv(n_tok, bkv)
    stat_spec = pl.BlockSpec((1, 1, _STAT_ROWS, bq),
                             lambda b, g, i, j: (b, g, 0, i))
    q_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, bq, lanes), lambda b, g, i, j: (b, i, off + g))
    kv_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, bkv, lanes), lambda b, g, i, j: (b, j, off + g))
    with profiling.scope("flash_attention/dq"):
        dq, delta = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, n_valid=n_valid,
                              block_kv=bkv, n_kv=n_kv, heads=heads,
                              ragged_kv=n_valid % bkv != 0),
            grid=(rows, groups, n_q, n_kv),
            in_specs=[stat_spec, q_spec(q_off), kv_spec(k_off),
                      kv_spec(v_off), q_spec(0), q_spec(0)],
            out_specs=[q_spec(q_off), stat_spec],
            out_shape=[_sds((rows, n_tok, (3 if packed else 1) * width),
                            q.dtype, q),
                       _sds(lse.shape, jnp.float32, q)],
            scratch_shapes=[pltpu.VMEM((bq, lanes), jnp.float32)],
            compiler_params=semantics,
            interpret=interpret,
            name="dq",
        )(lse, q, k, v, o, do)

    # transposed grid: K/V blocks outer, q chunks innermost; packed: one
    # step more, at which the result's block moves from dk's column block to
    # dv's (see _bwd_dkv_kernel) and the q chunk stays
    bq, bkv = blocks["dkv"]
    n_q, n_kv = pl.cdiv(n_tok, bq), pl.cdiv(n_tok, bkv)
    last = n_q - 1

    def chunk(i):
        return jnp.minimum(i, last) if packed else i

    stat_spec = pl.BlockSpec((1, 1, _STAT_ROWS, bq),
                             lambda b, g, j, i: (b, g, 0, chunk(i)))
    q_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, bq, lanes), lambda b, g, j, i: (b, chunk(i), off + g))
    kv_spec = lambda off: pl.BlockSpec(  # noqa: E731
        (1, bkv, lanes), lambda b, g, j, i: (b, j, off + g))
    operands = (lse, delta, q, k, v, do)
    in_specs = [stat_spec, stat_spec, q_spec(q_off), kv_spec(k_off),
                kv_spec(v_off), q_spec(0)]
    if packed:
        operands += (dq,)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_specs = pl.BlockSpec(
            (1, bkv, lanes), lambda b, g, j, i: (
                b, j, jnp.where(i < n_q, k_off, v_off) + g))
        out_shape = _sds(dq.shape, dq.dtype, q)
    else:
        out_specs = [kv_spec(0), kv_spec(0)]
        out_shape = [_sds((rows, n_tok, width), k.dtype, q)] * 2
    with profiling.scope("flash_attention/dkv"):
        out = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, n_valid=n_valid,
                              block_q=bq, n_q=n_q, heads=heads,
                              ragged_q=n_valid % bq != 0, packed=packed),
            grid=(rows, groups, n_kv, n_q + packed),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            input_output_aliases={len(operands) - 1: 0} if packed else {},
            scratch_shapes=[pltpu.VMEM((bkv, lanes), jnp.float32),
                            pltpu.VMEM((bkv, lanes), jnp.float32)],
            compiler_params=semantics,
            interpret=interpret,
            name="dkv",
        )(*operands)
    return (out,) if packed else (dq, *out)


def _flash_backward(operands, o, lse, g, num_heads, scale, block_q, block_kv):
    """The gradients of ``operands`` as :func:`_attention` takes them, in
    their form: three ``(B, N, H·D)`` arrays or the one packed ``(B, N,
    3·H·D)``. ``o``, ``g``: the context and its cotangent ``(B, N, H·D)``;
    ``lse``: the VJP forward's ``(B·H, padded tokens)``. Layout as the
    forward's (:func:`_heads_per_lane_group`), counted by
    ``kernels.flash_bwd_layout``; ``kernels.flash_bwd_schedule`` says which
    launches :func:`_bwd_blocks` chose: the one ``dqkv`` (``fused``), or dq
    and dkv with dq holding K and V as one chunk (``resident``) or streaming
    them (``streamed``; the dkv launch chooses for q and do by the same
    rule)."""
    packed = len(operands) == 1
    B, N, C = o.shape
    H, D = num_heads, C // num_heads
    in_place = _heads_per_lane_group(H, D)
    _kernels.inc("kernels.flash_bwd_layout",
                 key="in_place" if in_place else "head_major")
    if in_place:
        # where the model holds them: nothing is moved
        arrays = (*(operands * 3 if packed else operands), o, g)
        offsets = tuple(i * C // _LANE for i in range(3)) if packed else (0,) * 3
        rows, groups, heads, lanes = B, C // _LANE, in_place, _LANE
        n_pad = tiling.round_up(N, 8)
    else:
        arrays = tuple(_to_heads(x, B, N, H, D) for x in (
            *_unpack(operands, H), *_unpack((o, g), H)))
        offsets = (0,) * 3
        rows, groups, heads = B * H, 1, 1
        n_pad, lanes = arrays[0].shape[1:]
    blocks = _bwd_blocks(block_q, block_kv, n_pad, lanes, arrays[0].dtype,
                         heads)
    _kernels.inc("kernels.flash_bwd_schedule", key=(
        "fused" if "dqkv" in blocks
        else "resident" if N <= blocks["dq"][1] else "streamed"))
    # a lane axis takes no partial block: whole q blocks of every kernel
    lse = _lse_rows(lse.reshape(rows, groups, heads, -1), N, tiling.round_up(
        N, math.lcm(*(bq for bq, _ in blocks.values()))))
    in_place_packed = bool(in_place) and packed
    spec = rows_spec(rows)
    grads = per_device(
        functools.partial(
            _bwd_call, offsets=offsets, groups=groups, heads=heads,
            lanes=lanes, packed=in_place_packed, scale=scale, n_valid=N,
            blocks=blocks, interpret=kernel_interpret()),
        (spec,) * 6, (spec,) * (1 if in_place_packed else 3))(lse, *arrays)
    if in_place:
        return grads
    grads = [x[:, :N, :D].reshape(B, H, N, D).transpose(0, 2, 1, 3)
             for x in grads]
    if packed:  # (B, N, 3, H, D) is the projection's column order
        return (jnp.stack(grads, axis=2).reshape(operands[0].shape),)
    return tuple(x.reshape(B, N, C) for x in grads)


def online_softmax_update(o, l, m, logits, v_blk):
    """One blockwise-softmax accumulation step — THE shared update used by
    the pure-XLA blockwise path below and the ring-attention rotation steps
    (parallel/ring_attention.py): fold a new logits block into the running
    (output-numerator, denominator, max) triple, all f32.

    Shapes: o ``(..., nq, D)``, l/m ``(..., nq)``, logits ``(..., nq, bkv)``,
    v_blk ``(..., bkv, D)`` — leading dims broadcast (B, H, ...).
    """
    m_new = jnp.maximum(m, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum("...qk,...kd->...qd", p, v_blk)
    return o, l, m_new


def blockwise_attention_xla(q, k, v, scale, block_kv: int = 512, *,
                            causal: bool = False,
                            window: int | None = None) -> jax.Array:
    """Pure-XLA blockwise softmax attention — the Mosaic-free middle path.

    Same online-softmax math as the Pallas kernel (and the ring steps,
    parallel/ring_attention.py:62-71), expressed as a ``lax.scan`` over K/V
    chunks: the N² logit matrix never exists as one array — only one
    (B, H, N, block_kv) block per step, which XLA keeps fused with its
    exp/max/accumulate tail. Compiles anywhere ``lax`` does, so it serves as
    the safety net for accelerators where the Pallas kernel fails to lower
    (Mosaic rejected the kernel once on real hardware at N=2501 — this path
    has no kernel to reject). Expected between dense and Pallas in speed;
    strictly better than dense in HBM traffic at long N.

    q/k/v ``(B, N, H, D)`` → ``(B, N, H, D)`` in q's dtype, f32 softmax.
    ``causal`` (token t sees j ≤ t), ``window`` (and only t − window < j) and
    fewer heads in k and v than in q (query head h reads K/V head
    ``h // (H/KV)``) as :func:`flash_attention_masked` has them — whose
    differentiable stand-in off the TPU and second oracle this then is; every
    chunk is computed and masked, none skipped.
    """
    B, N, H, D = q.shape
    kind = mask_kind(causal, window)
    if k.shape[2] != H:  # each K/V head, repeated for the query heads on it
        k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, H, N, D)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    block_kv = min(block_kv, max(1, N))
    pad = (-N) % block_kv
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nb = kf.shape[2] // block_kv
    kb = kf.reshape(B, H, nb, block_kv, D).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(B, H, nb, block_kv, D).transpose(2, 0, 1, 3, 4)
    col = jnp.arange(nb * block_kv)
    valid = (col < N).reshape(nb, block_kv)
    if kind != "none":  # (nb, N, block_kv): what row t sees of each chunk
        row = jnp.arange(N)[:, None]
        sees = (col < N) & (col <= row)
        if window is not None:
            sees &= col > row - window
        valid = sees.reshape(N, nb, block_kv).transpose(1, 0, 2)

    o = jnp.zeros((B, H, N, D), jnp.float32)
    l = jnp.zeros((B, H, N), jnp.float32)
    m = jnp.full((B, H, N), _NEG_INF, jnp.float32)

    def body(carry, blk):
        o, l, m = carry
        k_b, v_b, val = blk
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, k_b) * scale
        val = val[None, None] if val.ndim == 2 else val[None, None, None, :]
        logits = jnp.where(val, logits, _NEG_INF)
        return online_softmax_update(o, l, m, logits, v_b), None

    (o, l, _), _ = jax.lax.scan(body, (o, l, m), (kb, vb, valid))
    return (o / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)


def _dense_attention_f32(q, k, v, scale):
    """XLA-einsum oracle the tests compare the kernels against, f32
    accumulation (ViT.py:110-114)."""
    logits = jnp.einsum(
        "bnhd,bmhd->bhnm", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    p = jax.nn.softmax(logits, axis=-1)
    return p, jnp.einsum("bhnm,bmhd->bnhd", p, v.astype(jnp.float32))


def _attention_fwd(operands, num_heads, scale, block_q, block_kv):
    out, lse = _flash_forward(operands, num_heads, scale, block_q, block_kv,
                              with_lse=True)
    return out, (operands, out, lse)


def _attention_bwd(num_heads, scale, block_q, block_kv, residuals, g):
    """The residuals are the operands as the forward read them (the packed
    projection stays packed), the context and one lane of lse: the backward
    reads all of them where they lie (:func:`_flash_backward`)."""
    operands, o, lse = residuals
    return (_flash_backward(operands, o, lse, g, num_heads, scale, block_q,
                            block_kv),)


_attention.defvjp(_attention_fwd, _attention_bwd)


# ---------------------------------------------------------------------------
# masked forward on shared K/V heads
# ---------------------------------------------------------------------------

def mask_kind(causal: bool, window: int | None) -> str:
    """The key of ``kernels.flash_fwd_mask``: ``none``, ``causal``, ``window``
    (a causal window: token t sees tokens t − window < j ≤ t)."""
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal window of at least one "
                             f"token: causal={causal}, window={window}")
        return "window"
    return "causal" if causal else "none"


def _visible_chunks(i, *, bq: int, bkv: int, n_valid: int, causal: bool,
                    window: int | None, lib=jnp):
    """(first, last) K/V chunk that any token of query block ``i`` may see;
    ``i`` a traced scalar (``lib=jnp``) or a Python int (``lib=_Ints``: the
    builtins under ``jnp``'s names, for the static grid size)."""
    last_row = lib.minimum(i * bq + bq - 1, n_valid - 1)
    hi = (last_row if causal else n_valid - 1) // bkv
    lo = (lib.maximum(i * bq - (window - 1), 0) // bkv if window is not None
          else 0)
    return lo, hi


class _Ints:
    """``minimum``/``maximum`` on Python ints, for the static grid size."""
    minimum, maximum = staticmethod(min), staticmethod(max)


def _whole_chunk(i, c, *, bq: int, bkv: int, n_valid: int, causal: bool,
                 window: int | None):
    """Whether every token of query block ``i`` sees the whole of chunk ``c``
    (it then takes the unmasked fold); traced scalars or Python ints."""
    whole = (c + 1) * bkv <= n_valid
    if causal:
        whole &= (c + 1) * bkv - 1 <= i * bq
    if window is not None:
        whole &= c * bkv > i * bq + bq - 1 - window
    return whole


#: the rows of the last q block's short folds come in whole groups of this
#: many: the sublanes one packed int8 tile of a selection holds (16 would do
#: for bfloat16 operands alone)
_TAIL_ROWS = 32


def _tail_rows(n_valid: int, bq: int) -> int | None:
    """Rows the LAST q block's folds run on, or None where they run on all
    ``bq``: the rows that block holds — ``n_valid`` less the blocks before it
    — in whole groups of :data:`_TAIL_ROWS`, where that is at most half the
    block. Every sequence the samplers make is ``k² + 1`` tokens, so the last
    block of a launch at their shapes holds ONE row (32 of 1,024 rows); a
    sequence that ends on a block boundary, or whose last block is more than
    half full, has no short folds and lowers to the program without."""
    held = n_valid - (pl.cdiv(n_valid, bq) - 1) * bq
    rows = tiling.round_up(held, _TAIL_ROWS)
    return rows if 2 * rows <= bq else None


def _tail_key(tail: int | None, bq: int) -> str:
    """The key of ``kernels.flash_fwd_tail``."""
    return "whole" if tail is None else f"{tail}/{bq}"


def _fwd_masked_kernel(*refs, scale: float, n_valid: int, bq: int, bkv: int,
                       n_kv: int, causal: bool, window: int | None,
                       selected: bool = False, parts: int = 1,
                       turn: tuple | None = None, heads: int = 1,
                       tail: int | None = None):
    """One (image, group of ``heads`` query heads, q block, visited chunk)
    program of the masked forward. ``heads`` 1 — every launch but
    ``fwd_masked`` on shared K/V heads — is one head on the block's lanes.
    More (:func:`_masked_fold`): the q and result blocks are ``(1, bq, heads ·
    lanes)``, adjacent query heads of ONE K/V head side by side where
    ``q_proj`` wrote them, K and V the ``(1, bkv, lanes)`` chunk they share,
    and each head has its own columns of every scratch. What does not depend
    on the head — K, V with a ragged chunk's stale rows zeroed, the element
    mask — is made once, by the first head; then each head's score GEMM,
    float32 softmax and value GEMM follow as the one-head program's own
    operations in their own order, so a head's context is bit for bit what a
    program of its own gives. The heads' chains stand in ONE basic block with
    no dependence between them: the scheduler lays one head's ``exp`` under
    another's MXU passes, which a program a head cannot (its one chain waits
    for the vector unit and back).

    ``tail`` (:func:`_tail_rows`; any ``heads``, ``parts``, ``turn``,
    ``selected``): the LAST q block holds at most that many rows of the
    sequence — one, at every shape the samplers launch — and its folds run on
    the first ``tail`` rows alone: a second pair of folds (unmasked, masked:
    the same chains, whatever the heads) that reads q, the turned q, the
    selection's tile and the softmax's state at that static row slice, and
    that the last q block is sent to where every other block takes the folds
    on ``bq`` rows. Rows are independent in both GEMMs and in the softmax, so
    a valid row's context is bit for bit what the whole block's fold gives
    it; the chunks, their fetches and the grid are what they are without, and
    what runs once a block (the state's reset, the turn, the result's
    division) stays on the whole block: rows past ``n_valid`` are outside the
    result and are not written either way. A short fold is traced only where
    the last block can reach it (no unmasked one where it sees no chunk
    whole). None: the program letter for letter without the option.

    ``parts`` (one head a program): the score is the sum of
    that many products, ``parts`` q blocks then ``parts`` k blocks before v,
    laid side by side on the lanes into ONE contraction (the latent forward's
    ``q_nope·k_nope + q_r·k_r``: :func:`_fwd_latent_kernel`); the value head,
    and with it the accumulator and the result, has its own width.
    ``selected`` (one head a program): one more operand after v,
    the ``(bq, bkv)`` int8 tile of a per-query key selection
    (``ops/sparse_select.py``), comes before the result; a pair is then kept
    where the tile is not 0 AND the mask lets it through, so every visited
    chunk takes the masked fold. Grid step ``j`` folds chunk
    ``first + j`` of the chunks the mask lets this q block see
    (:func:`_visible_chunks`; the K/V index maps address the same chunk) and
    does nothing once past the last of them. The element mask is built only
    in a chunk the mask's edge (the diagonal, the window's far edge, the end
    of the sequence) crosses; a chunk every token of the block sees whole
    takes the unmasked fold. ``turn`` (:func:`_turn_geometry`; one part): q
    comes as its projection wrote it and two more operands before the result,
    the ``(bq, 128)`` float32 cos and sin tables of the lane group that holds
    a head's rotated dims, turn it HERE, once a q block and head, into one
    more scratch after the softmax's (:func:`_turn_q_block`), which every
    fold then reads in place of the q block.

    A row whose first visited chunk is wholly masked for it (a window's far
    chunk, for the block's last rows) holds m = −1e30 and garbage l, acc until
    its diagonal chunk — always visited, and later — scales them by
    exp(−1e30 − m) = 0."""
    q_refs, k_refs, v_ref = refs[:parts], refs[parts:2 * parts], refs[2 * parts]
    rest = refs[2 * parts + 1:]
    keep_ref, rest = (rest[0], rest[1:]) if selected else (None, rest)
    turned_ref = None
    if turn is not None:
        cos_ref, sin_ref, *rest, turned_ref = rest
    o_ref, acc_ref, m_ref, l_ref = rest
    # the grid of a launch that turns q has the q blocks outside the heads
    i, j = pl.program_id(2 if turn is None else 1), pl.program_id(3)
    geometry = dict(bq=bq, bkv=bkv, n_valid=n_valid, causal=causal,
                    window=window)
    lo, hi = _visible_chunks(i, **geometry)
    c = lo + j

    fold_scale = _scale_folds_into_q(scale)

    def of_head(f: int, ref):
        """Head ``f``'s columns of a block or scratch ``heads`` heads wide;
        the ref itself in the one-head program."""
        if heads == 1 or ref is None:
            return ref
        width = ref.shape[-1] // heads
        return ref.at[..., pl.ds(f * width, width)]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if turn is not None:
            for f in range(heads):
                _turn_q_block(of_head(f, q_refs[0]), cos_ref, sin_ref,
                              of_head(f, turned_ref),
                              scale if fold_scale else None, *turn)

    def fold(masked: bool, rows: int | None = None):
        # the block's rows in an operand's block and in a scratch: all, or
        # the last block's first ``rows``
        block, held = (0, ...) if rows is None else (
            (0, slice(rows)), slice(rows))
        for f in range(heads):
            acc_f, m_f, l_f = (of_head(f, r) for r in (acc_ref, m_ref, l_ref))
            qs = [of_head(f, r)[block] for r in q_refs]
            if f == 0:  # the group's one chunk
                ks, v = [r[0] for r in k_refs], v_ref[0]
            if turn is not None:  # turned, and scaled where the scale folds
                qs = [of_head(f, turned_ref)[held]]
            elif fold_scale:
                qs = [q * scale for q in qs]
            q = qs[0] if parts == 1 else jnp.concatenate(qs, axis=1)
            if f == 0:
                k = ks[0] if parts == 1 else jnp.concatenate(ks, axis=1)
                if masked and n_valid % bkv:
                    # rows of a ragged last chunk hold whatever the buffer
                    # held; their p is an exact 0, and 0 × garbage is NaN
                    vrow = c * bkv + jax.lax.broadcasted_iota(
                        jnp.int32, v.shape, 0)
                    v = jnp.where(vrow < n_valid, v, jnp.zeros_like(v))
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq, bkv)
            if not fold_scale:
                logits = logits * scale
            if masked:
                if f == 0:  # one element mask for all the heads
                    row = i * bq + jax.lax.broadcasted_iota(
                        jnp.int32, logits.shape, 0)
                    col = c * bkv + jax.lax.broadcasted_iota(
                        jnp.int32, logits.shape, 1)
                    keep = col < n_valid
                    if causal:
                        keep &= col <= row
                    if window is not None:
                        keep &= col > row - window
                    if selected:
                        keep &= keep_ref[block].astype(jnp.int32) != 0
                logits = jnp.where(keep, logits, _NEG_INF)
            m_prev = jnp.max(m_f[held], axis=-1, keepdims=True)  # (bq, 1)
            l_prev = jnp.max(l_f[held], axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(logits - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_f[held] = acc_f[held] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            stats = (logits.shape[0], m_f.shape[1])
            m_f[held] = jnp.broadcast_to(m_new, stats)
            l_f[held] = jnp.broadcast_to(l_new, stats)

    whole = _whole_chunk(i, c, **geometry)
    if selected:
        whole = False
    if tail is None:  # as written before the option: the pinned programs
        pl.when((c <= hi) & whole)(lambda: fold(False))
        pl.when((c <= hi) & jnp.logical_not(whole))(lambda: fold(True))
    else:
        last = pl.cdiv(n_valid, bq) - 1
        first, end = _visible_chunks(last, lib=_Ints, **geometry)
        a_whole_one = any(_whole_chunk(last, chunk, **geometry)
                          for chunk in range(first, end + 1))
        visited = c <= hi
        for rows, mine, unmasked in ((None, i != last, True),
                                     (tail, i == last, a_whole_one)):
            if unmasked and not selected:
                pl.when(visited & mine & whole)(
                    functools.partial(fold, False, rows))
            pl.when(visited & mine & jnp.logical_not(whole))(
                functools.partial(fold, True, rows))

    @pl.when(j == n_kv - 1)
    def _emit():
        for f in range(heads):
            o_f, acc_f, l_f = (of_head(f, r) for r in (o_ref, acc_ref, l_ref))
            l = jnp.max(l_f[...], axis=-1, keepdims=True)
            o_f[0] = (acc_f[...] / l).astype(o_f.dtype)


def _turn_geometry(rotary: Rotary) -> tuple | None:
    """``(lane group of the head, first rotated lane in it, pairs, by
    halves)`` where the launch can turn q itself — the head's rotated dims lie
    inside ONE group of 128 lanes, so every partner is a lane roll away
    within the group — else None: :meth:`Rotary.apply` turns q in XLA."""
    group, lane = divmod(rotary.first, _LANE)
    half = len(rotary.inv_freq)
    if lane + 2 * half > _LANE:
        return None
    return group, lane, half, rotary.pairing == "rotate_half"


def _turn_q_block(q_ref, cos_ref, sin_ref, out_ref, scale: float | None,
                  group: int, lane0: int, half: int, halves: bool):
    """``out_ref (bq, lanes)`` = the q block with its rotated dims turned and
    the whole of it times ``scale`` (None: the scores are scaled), as
    ``ops.rotary.apply_rotary`` then the fold of the scale would leave it:
    the lane group that holds the rotated dims through float32 — ``x · cos +
    partner · sin`` by the tables (1 and 0 on the lanes that pass through),
    the partner a lane roll away, the first of a pair taking it from the
    right — and ONE rounding to q's dtype; the other groups copied."""
    lo, hi = group * _LANE, (group + 1) * _LANE
    x = q_ref[0, :, lo:hi].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    reach = half if halves else 1
    first_of_pair = (lane < lane0 + half if halves
                     else (lane & 1) == (lane0 & 1))
    partner = jnp.where(first_of_pair, pltpu.roll(x, _LANE - reach, 1),
                        pltpu.roll(x, reach, 1))
    turned = (x * cos_ref[...] + partner * sin_ref[...]).astype(out_ref.dtype)
    scaled = (lambda q: q) if scale is None else (lambda q: q * scale)
    if lo:
        out_ref[:, :lo] = scaled(q_ref[0, :, :lo])
    out_ref[:, lo:hi] = scaled(turned)
    if hi < out_ref.shape[1]:
        out_ref[:, hi:] = scaled(q_ref[0, :, hi:])


def _masked_blocks(n_tokens: int, dtype) -> tuple:
    """(block_q, block_kv) of the masked forward: K/V streamed in chunks of
    512 (a window of 512 then lies in two chunks of a 512-row q block), both
    clamped to a short sequence; VMEM is a few MB whatever the length."""
    block = tiling.legal_block(512, tiling.round_up(n_tokens, 8), dtype)
    return block, block


#: q rows ONE program of ``fwd_masked`` may hold over all the heads it folds,
#: at 128 lanes a head (a head of 256 lanes counts twice): 9 × 256. Mosaic
#: unrolls the body over every (8, 128) tile of every head's (bq, bkv) scores,
#: so what a process's FIRST set-up pays — the body's compile, the executable
#: it stores once a call site — grows with heads × rows (1.0–1.9 MB and
#: 1.1–1.9 s a launch at this budget, 0.45–0.9 MB and 0.4–1.1 s a head a
#: program; twice the rows, 1.6–2.1 MB and 2.5–3.5 s). The same row keeps the
#: blocks and scratch of the widest group inside the default scoped VMEM, in
#: float32 too (tests/test_chip_compile.py compiles the edge). A WARM set-up
#: pays for neither: it pays for tracing and lowering the body, ``heads``
#: chains of Python whatever the rows — which is why the launch keeps its
#: trace (:func:`_fwd_masked_call`).
_FOLD_ROWS = 2304


def _masked_fold(rep: int, n_tokens: int, lanes: int, dtype) -> tuple:
    """``(heads, block_q)`` of ``fwd_masked``: how many of the ``rep`` query
    heads that share a K/V head ONE program folds a fetched chunk into, and
    its q block. From the call's shapes alone: q blocks of 256 rows wherever
    a program folds more than one head — half :func:`_masked_blocks`'s, for
    half the compiled body a head, at 6 % of the kernel's time in
    SmallThinker and none in Laguna or Nemotron — and the largest divisor of
    ``rep`` whose heads × rows stay within :data:`_FOLD_ROWS`: 7 of
    SmallThinker's 7, 6 and 9 of Laguna's, 8 of Nemotron's 16. ``rep`` 1, or no divisor but 1 (a
    prime above 9), is the one-head program at :func:`_masked_blocks`'s q
    block."""
    bq = tiling.legal_block(256, tiling.round_up(n_tokens, 8), dtype)
    rows = _FOLD_ROWS * _LANE // lanes
    heads = max(f for f in range(1, rep + 1) if rep % f == 0 and
                (f == 1 or f * bq <= rows))
    if heads == 1:
        return 1, _masked_blocks(n_tokens, dtype)[0]
    return heads, bq


def _chunk_walk(tokens: int, geometry: dict) -> tuple:
    """(q blocks, steps of the last grid axis, ``chunk(i, j)``) of a launch
    that walks only the K/V chunks a q block's mask lets it see: the axis is
    as long as the most chunks any q block sees, and ``chunk(i, j)`` — for the
    index maps — is the ``j``-th chunk q block ``i`` sees, or its last one
    again once past them (not fetched again; the kernel skips the fold)."""
    n_q = pl.cdiv(tokens, geometry["bq"])
    spans = [_visible_chunks(i, lib=_Ints, **geometry) for i in range(n_q)]
    n_kv = max(hi - lo + 1 for lo, hi in spans)

    def chunk(i, j):
        lo, hi = _visible_chunks(i, **geometry)
        return jnp.minimum(lo + j, hi)

    return n_q, n_kv, chunk


def _walk_scratch(bq: int, lanes: int, heads: int = 1) -> list:
    """The online softmax's state across a q block's visited chunks, of every
    one of the ``heads`` query heads a program folds: head ``f`` holds columns
    ``f · lanes`` on of the accumulator, ``f · 128`` on of the statistics."""
    return [
        pltpu.VMEM((bq, heads * lanes), jnp.float32),  # output accumulator
        pltpu.VMEM((bq, heads * _LANE), jnp.float32),  # running max
        pltpu.VMEM((bq, heads * _LANE), jnp.float32),  # running denominator
    ]


#: grid (rows, heads or groups of them, q blocks, visited chunks): the state
#: is carried along the last axis alone
_WALK_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("selected", "rep", "lanes", "scale", "n_valid", "bq",
                     "bkv", "causal", "window", "interpret", "turn", "heads",
                     "tail"))
def _fwd_masked_call(q, k, v, *more, selected, rep, lanes, scale, n_valid,
                     bq, bkv, causal, window, interpret, turn=None, heads=1,
                     tail=None):
    """The masked launch (``fwd_masked``), or ``selected``, with ``keep``
    first in ``more`` — an int8 ``(rows, tokens⁺, tokens⁺)`` selection in
    whole ``(bq, bkv)`` tiles, one for all the heads — the selected one
    (``fwd_selected``: the same body, a name of its own in a device trace).
    ``q``: ``(rows, tokens, H·lanes)``; ``k``, ``v``:
    ``(rows, tokens, H/rep·lanes)``, query head ``h`` reading K/V column
    block ``h // rep``; all three where the projections wrote them, the token
    axis ending inside the last block. ``heads`` (a divisor of ``rep``:
    :func:`_masked_fold`; 1 under a selection) consecutive query heads, all of
    ONE K/V head, are one program's: group ``g``'s q and result blocks are
    ``(1, bq, heads·lanes)`` at column block ``g`` and its K and V blocks
    ``(1, bkv, lanes)`` at column block ``g // (rep // heads)``, a chunk
    fetched once a (group, q block, chunk) and folded into every head of the
    group (:func:`_fwd_masked_kernel`), scratch a head. Grid ``(rows, H /
    heads, q blocks, visited chunks)``: the last axis is as long as the most
    chunks any q block sees (two for a window of 512 at blocks of 512; all of
    them under a causal mask), and a block that sees fewer re-addresses its
    last chunk, which is not fetched again, and skips the fold. ``turn`` with
    two tables last in ``more``, the float32 ``(tokens⁺, 128)`` cos and sin of
    the rotated lane group in whole q blocks: q is unturned and the launch
    turns it (:func:`_fwd_masked_kernel`), a ``(bq, 128)`` block of each table
    a q block and one more scratch, the turned block of every head; the grid
    is then ``(rows, q blocks, H / heads, visited chunks)``, the groups INSIDE
    the q blocks, so that a q block's tables are fetched once for all its
    heads and not once a group (0.6 GB a ``fwd_selected`` launch at 9,217
    tokens × 64 heads, under steps that have no time to hide it); every other
    block is fetched as often either way. ``tail``: the rows the last q
    block's folds run on (:func:`_tail_rows` of ``n_valid`` and ``bq``, the
    caller's to compute; None: all of them, the program without the short
    folds) — that block still takes a grid step for every chunk it sees, each
    multiplying ``tail`` rows where it multiplied ``bq``.

    An inline ``jit``: the launch lands in the caller's program as it would
    without (no call, the caller's named scopes on it), but JAX keeps its
    trace, so the layers of a stack that launch the same shapes trace the
    kernel's body ONCE — a body that holds ``heads`` chains is ``heads`` times
    the Python to trace, and a warm set-up pays for tracing in full (two
    traces for SmallThinker's eight launches, two for Laguna's five)."""
    keep, tables = (more[0], more[1:]) if selected else (None, more)
    rows, tokens, width = q.shape
    geometry = dict(bq=bq, bkv=bkv, n_valid=n_valid, causal=causal,
                    window=window)
    n_q, n_kv, chunk = _chunk_walk(tokens, geometry)
    wide = heads * lanes
    grid = [rows, width // wide, n_q, n_kv]
    at = lambda index_map: index_map  # written over (b, g, i, j)
    if turn is not None:
        grid[1:3] = grid[2], grid[1]
        at = lambda index_map: lambda b, i, g, j: index_map(b, g, i, j)

    def kv_map(b, g, i, j):
        return (b, chunk(i, j), g // (rep // heads))

    q_spec = pl.BlockSpec((1, bq, wide), at(lambda b, g, i, j: (b, i, g)))
    kv_spec = pl.BlockSpec((1, bkv, lanes), at(kv_map))
    name, operands, in_specs = "fwd_masked", (q, k, v), [q_spec, kv_spec, kv_spec]
    if keep is not None:
        name, operands = "fwd_selected", (q, k, v, keep)
        in_specs.append(pl.BlockSpec(
            (1, bq, bkv), at(lambda b, g, i, j: (b, i, kv_map(b, g, i, j)[1]))))
    scratch = _walk_scratch(bq, lanes, heads)
    if turn is not None:
        operands += tables
        in_specs += [pl.BlockSpec((bq, _LANE),
                                  at(lambda b, g, i, j: (i, 0)))] * 2
        scratch.append(pltpu.VMEM((bq, wide), q.dtype))  # the turned q blocks
    with profiling.scope(f"flash_attention/{name}"):
        return pl.pallas_call(
            functools.partial(_fwd_masked_kernel, scale=scale, n_kv=n_kv,
                              selected=keep is not None, turn=turn,
                              heads=heads, tail=tail, **geometry),
            grid=tuple(grid),
            in_specs=in_specs,
            out_specs=q_spec,
            out_shape=_sds(q.shape, q.dtype, q),
            scratch_shapes=scratch,
            compiler_params=_WALK_PARAMS,
            interpret=interpret,
            name=name,
        )(*operands)


def flash_attention_masked(q, k, v, scale: float, *, causal: bool = True,
                           window: int | None = None,
                           rotary: Rotary | None = None) -> jax.Array:
    """The forward under a mask and on shared K/V heads, as its own launch
    (``pallas_call(name="fwd_masked")``, ``%fwd_masked`` in a device trace, so
    that what reads ``%fwd`` keeps reading the unmasked kernel).

    q ``(B, N, H, D)``; k, v ``(B, N, KV, D)`` with ``H % KV == 0``: query head
    ``h`` reads K/V head ``h // (H/KV)``. ``causal``: token t sees j ≤ t;
    ``window``: and only t − window < j. Returns ``(B, N, H, D)`` in q's
    dtype, softmax in float32. At a head size that fills whole lanes (128,
    256) the three arrays are read in place, token-major, one head a lane
    group, and the context written where the output projection reads it; any
    other head size is zero-padded to the lanes first (a copy in HBM on each
    side). K/V chunks that lie wholly outside the mask of a q block are
    neither fetched nor computed (:func:`_fwd_masked_call`), and a chunk that
    is fetched is fetched once for all the query heads ONE program folds it
    into: the largest divisor of ``H / KV`` whose heads × 256 q rows stay
    within 2,304 (:func:`_masked_fold`: 7 of 7 at SmallThinker's shape, 6 of 6
    and 9 of 9 at Laguna's, 8 of 16 at Nemotron's), their chains independent
    in one program so that one head's softmax runs under another's GEMMs;
    each head's context is bit for bit what a program of its own gives, and
    ``H == KV`` is that program itself at q blocks of 512.
    ``kernels.flash_fwd_fold`` counts the heads a program folds, +1 a trace.
    The last q block's folds run on the rows it holds, in whole groups of 32,
    where those are at most half a block (:func:`_tail_rows`; one row of 256
    or 512 at the samplers' ``k² + 1`` tokens), bit for bit the folds on the
    whole block; ``kernels.flash_fwd_tail`` counts ``<rows>/<q block>`` or
    ``whole``, +1 a trace. Blocks, heads a program and those rows come from
    the shapes alone. ``rotary``: q comes UNTURNED and the launch turns the q
    block it holds, as :func:`flash_attention_selected` says (a head of 128 whose
    every dim turns, or whose first half does: its one lane group). No
    backward yet: the VJP raises by name (ROADMAP Reach)."""
    _check_shared_heads(q, k, v)
    _kernels.inc("kernels.flash_fwd_mask", key=mask_kind(causal, window))
    q, rotary = _turned_where_it_must_be(q, rotary)
    return _masked_forward(q, k, v, None, scale, causal, window, rotary)


def _check_shared_heads(q, k, v) -> None:
    B, N, H, D = q.shape
    if (H % k.shape[2] or k.shape != v.shape or k.shape[:2] != (B, N)
            or k.shape[3] != D):
        raise ValueError(f"q {q.shape} cannot share k {k.shape}, v {v.shape}: "
                         "query heads must divide into the K/V heads")


def _masked_forward(q, k, v, keep, scale, causal, window, rotary=None):
    """The launch of :func:`flash_attention_masked` (``keep`` None) or
    :func:`flash_attention_selected` on ``(B, N, heads, D)`` operands: heads
    zero-padded to whole lanes where ``D`` does not fill them, blocks and the
    heads a program folds from the shape (:func:`_masked_fold`; one head under
    a selection), the rows of the last q block's folds from the length
    (:func:`_tail_rows`; ``kernels.flash_fwd_tail``), one launch a device
    under a mesh. ``rotary``: q is unturned and the launch turns it, by tables
    made here for every device."""
    B, N, H, D = q.shape
    KV = k.shape[2]
    lanes = tiling.round_up(D, _LANE)
    if lanes != D:
        q, k, v = (_pad_to(x, 3, _LANE) for x in (q, k, v))
    bq, bkv = _masked_blocks(N, q.dtype)
    heads = 1  # a selection's tile is (bq, bkv) of these blocks
    if keep is None:
        heads, bq = _masked_fold(H // KV, N, lanes, q.dtype)
    _kernels.inc("kernels.flash_fwd_fold", key=str(heads))
    tail = _tail_rows(N, bq)
    _kernels.inc("kernels.flash_fwd_tail", key=_tail_key(tail, bq))
    spec = rows_spec(B)
    operands = (q.reshape(B, N, H * lanes), k.reshape(B, N, KV * lanes),
                v.reshape(B, N, KV * lanes))
    if keep is not None:
        operands += (keep,)
    specs, turn = (spec,) * len(operands), None
    if rotary is not None:
        turn = _turn_geometry(rotary)
        operands += rotary_tables(
            tiling.round_up(N, bq), _LANE, rotary.inv_freq, rotary.scale,
            pairing=rotary.pairing, first=turn[1])
        specs += (P(), P())
    out = per_device(
        functools.partial(
            _fwd_masked_call, selected=keep is not None, rep=H // KV,
            lanes=lanes, scale=scale, n_valid=N, bq=bq, bkv=bkv, causal=causal,
            window=window, interpret=kernel_interpret(), turn=turn,
            heads=heads, tail=tail),
        specs, spec,
    )(*operands)
    return out.reshape(B, N, H, lanes)[..., :D]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _masked_no_vjp(q, k, v, scale, causal, window, rotary=None):
    return flash_attention_masked(q, k, v, scale, causal=causal, window=window,
                                  rotary=rotary)


def _masked_no_vjp_fwd(*args):
    raise NotImplementedError(
        "the fwd_masked kernel has no backward yet (ROADMAP Reach: the masks "
        "and shared K/V heads in dq/dkv): differentiate "
        "ops.flash_attention.blockwise_attention_xla, which is what "
        "masked_attention runs off the TPU")


_masked_no_vjp.defvjp(_masked_no_vjp_fwd, lambda *a: None)


def masked_attention(q, k, v, scale: float, *, causal: bool = True,
                     window: int | None = None,
                     rotary: Rotary | None = None) -> jax.Array:
    """Attention under a causal mask or a causal window on shared K/V heads,
    shapes as :func:`flash_attention_masked`, q unturned where ``rotary`` says
    what still turns it; the backend decides what runs: that kernel on the
    TPU, which turns q itself where it can; anywhere else ``apply_rotary``
    (``kernels.flash_fwd_rotary`` counts ``xla``) and
    :func:`blockwise_attention_xla` (plain JAX, differentiable)."""
    if jax.default_backend() == "tpu":
        return _masked_no_vjp(q, k, v, scale, causal, window, rotary)
    return blockwise_attention_xla(_turned_by_xla(q, rotary), k, v, scale,
                                   causal=causal, window=window)


def flash_attention_selected(q, k, v, scale: float, keep,
                             rotary: Rotary | None = None) -> jax.Array:
    """The causal forward over a per-query SET of keys, as its own launch
    (``pallas_call(name="fwd_selected")``, ``%fwd_selected`` in a device
    trace; ``%fwd_masked`` keeps reading the launch without a selection).

    q, k, v as :func:`flash_attention_masked`; ``keep`` int8 ``(B, N⁺, N⁺)``,
    N⁺ the token count in whole blocks (``ops.sparse_select.mask_length``),
    not 0 where query t attends to key s — one selection for all the heads.
    Token t attends to ``{s ≤ t : keep[t, s]}``, which must not be empty (the
    ``top`` best of the visible keys never is). Every chunk at or below the
    diagonal is multiplied and masked by its tile: with a scattered set there
    is no chunk to skip, and a gather a row is 2,048 descriptors a query.
    The last q block's folds run on the rows it holds and read the tile at
    those rows, as :func:`flash_attention_masked` says.

    ``rotary``: q comes UNTURNED, as its projection wrote it, and this is the
    rotation of every query head it still needs (k comes turned). Where a
    head's rotated dims lie inside one group of 128 lanes (a head of ``[nope
    192 | rot 64]``: its second group) the launch turns the q block it
    already holds, once a (head, q block), in VMEM: two more operands, the
    float32 ``(N⁺, 128)`` cos and sin of that lane group (1 and 0 on the
    lanes that pass through), made here by ``apply_rotary``'s own expressions
    — float32 products, one rounding to q's dtype before the MXU, as there,
    and no pass over q in HBM. Any other head is turned by ``apply_rotary``
    and launched as without. ``kernels.flash_fwd_rotary`` counts which,
    ``kernel`` or ``xla``, once a trace. No backward yet: the VJP raises by
    name."""
    _check_shared_heads(q, k, v)
    B, N = q.shape[:2]
    bq, bkv = _masked_blocks(N, q.dtype)
    want = (B, tiling.round_up(N, bq), tiling.round_up(N, bkv))
    if keep.shape != want or keep.dtype != jnp.int8:
        raise ValueError(f"keep {keep.dtype}{keep.shape}: the selection of "
                         f"{N} tokens is int8{want}")
    _kernels.inc("kernels.flash_fwd_mask", key="selected")
    q, rotary = _turned_where_it_must_be(q, rotary)
    return _masked_forward(q, k, v, keep, scale, True, None, rotary)


def _turned_by_xla(q, rotary: Rotary | None):
    """``apply_rotary`` on ``(B, N, H, D)``, counted; q as it is where no
    rotation came with it."""
    if rotary is None:
        return q
    B, N, H, D = q.shape
    _kernels.inc("kernels.flash_fwd_rotary", key="xla")
    return rotary.apply(q.reshape(B, N, H * D), H).reshape(q.shape)


def _turned_where_it_must_be(q, rotary: Rotary | None) -> tuple:
    """``(q, rotary)`` as :func:`_masked_forward` takes them: a rotation the
    launch can run itself (:func:`_turn_geometry`) handed on beside the
    unturned q and counted ``kernel``; any other applied here, by XLA."""
    if rotary is None or _turn_geometry(rotary) is None:
        return _turned_by_xla(q, rotary), None
    _kernels.inc("kernels.flash_fwd_rotary", key="kernel")
    return q, rotary


def selected_attention_xla(q, k, v, scale: float, keep) -> jax.Array:
    """:func:`flash_attention_selected` in plain ``jax.numpy``: the whole
    score matrix under the selection, softmax in float32. Differentiable in
    q, k, v; the oracle of the kernel and what runs off the TPU, at sizes
    whose ``(B, H, N, N)`` scores fit."""
    B, N, H, D = q.shape
    if k.shape[2] != H:
        k, v = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    sees = (jnp.arange(N)[None, :] <= jnp.arange(N)[:, None])
    sees = sees & (keep[:, :N, :N] != 0)
    logits = jnp.einsum("bnhd,bmhd->bhnm", q, k,
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(sees[:, None], logits, _NEG_INF), axis=-1)
    return jnp.einsum("bhnm,bmhd->bnhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected_no_vjp(q, k, v, keep, scale, rotary=None):
    return flash_attention_selected(q, k, v, scale, keep, rotary)


def _selected_no_vjp_fwd(*args):
    raise NotImplementedError(
        "the fwd_selected kernel has no backward yet (ROADMAP Reach: the "
        "selection in dq/dkv): differentiate "
        "ops.flash_attention.selected_attention_xla, which is what "
        "selected_attention runs off the TPU")


_selected_no_vjp.defvjp(_selected_no_vjp_fwd, lambda *a: None)


def selected_attention(q, k, v, scale: float, keep,
                       rotary: Rotary | None = None) -> jax.Array:
    """Causal attention over the per-query key sets ``keep``
    (``ops.sparse_select.select``), shapes as
    :func:`flash_attention_selected`, q unturned where ``rotary`` says what
    still turns it; the backend decides what runs: that kernel on the TPU,
    which turns q itself where it can; anywhere else ``apply_rotary``
    (``kernels.flash_fwd_rotary`` counts ``xla``) and
    :func:`selected_attention_xla`."""
    if jax.default_backend() == "tpu":
        return _selected_no_vjp(q, k, v, keep, scale, rotary)
    return selected_attention_xla(_turned_by_xla(q, rotary), k, v, scale, keep)


# ---------------------------------------------------------------------------
# latent forward: a score in two parts, the second over a key part all the
# heads share, the value head at its own width
# ---------------------------------------------------------------------------

def latent_sizes(q_nope, q_r, k_nope, k_r, v) -> tuple:
    """``(B, N, H, nope, rot, vd)`` of the latent forward's operands, or a
    ``ValueError`` that names what the launch cannot address: the operands
    are read where the projections wrote them, token-major, so ``nope`` and
    ``vd`` are whole lane groups each (any number, each its own) and ``rot``
    is half a group or one (64: two heads' rotated parts share a group's
    lanes; 128)."""
    B, N, H, nope = q_nope.shape
    rot, vd = k_r.shape[-1], v.shape[-1]
    if (q_r.shape != (B, N, H, rot) or k_nope.shape != q_nope.shape
            or k_r.shape != (B, N, rot) or v.shape != (B, N, H, vd)):
        raise ValueError(
            f"q_nope {q_nope.shape}, q_r {q_r.shape}, k_nope {k_nope.shape}, "
            f"k_r {k_r.shape}, v {v.shape}: the latent forward takes q_nope "
            "and k_nope (B, N, H, nope), q_r (B, N, H, rot), ONE k_r (B, N, "
            "rot) for all the heads and v (B, N, H, vd)")
    if nope % _LANE or vd % _LANE or rot not in (_LANE // 2, _LANE) or (
            H * rot) % _LANE:
        raise ValueError(
            f"head sizes (nope {nope}, rot {rot}, vd {vd}) on {H} heads: the "
            f"fwd_latent launch reads nope and vd in whole groups of {_LANE} "
            f"lanes and rot of {_LANE // 2} (an even number of heads) or "
            f"{_LANE}")
    return B, N, H, nope, rot, vd


def _fwd_latent_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, acc_ref,
                       m_ref, l_ref, *mine, **geometry):
    """One (image, head, q block, visited chunk) program of the latent
    forward: :func:`_fwd_masked_kernel`'s body — its chunk skipping, masks and
    online softmax — over a score in two parts. At ``rot`` 64 the q_r block
    holds TWO heads' rotated parts on its 128 lanes and the shared k_r comes
    twice over on as many, so a head's half is told apart by a lane mask (no
    lane shift), once a q block, into the scratch ``mine``."""
    if mine:
        (mine_ref,) = mine
        upper = pl.program_id(1) % 2 == 1

        @pl.when(pl.program_id(3) == 0)
        def _keep_this_heads_half():
            lane = jax.lax.broadcasted_iota(jnp.int32, mine_ref.shape, 2)
            q_r = qr_ref[...]
            mine_ref[...] = jnp.where((lane >= _LANE // 2) == upper, q_r,
                                      jnp.zeros_like(q_r))

        qr_ref = mine_ref
    _fwd_masked_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, acc_ref,
                       m_ref, l_ref, parts=2, **geometry)


def _latent_vmem_bytes(bq: int, bkv: int, nope: int, vd: int,
                       itemsize: int) -> int:
    """Scoped VMEM the latent forward needs at blocks (bq, bkv): the
    double-buffered q_nope, q_r, result and k_nope, k_r, v blocks (the rotated
    parts a lane group each), both sides of the one contraction laid side by
    side, the float32 accumulator and the two lane-replicated statistics, a
    head's half of q_r, and three live (bq, bkv) float32 tiles (scores, p and
    the mask's select). An upper bound by the masked forward's count of
    tiles; tests/test_chip_compile.py compiles what it admits."""
    q_side = bq * (nope + _LANE + vd) * itemsize
    k_side = bkv * (nope + _LANE + vd) * itemsize
    joined = (bq + bkv) * (nope + _LANE) * itemsize
    scratch = bq * (4 * vd + 8 * _LANE + _LANE * itemsize)
    return 2 * (q_side + k_side) + joined + scratch + 12 * bq * bkv + (1 << 19)


def _latent_blocks(n_tokens: int, nope: int, vd: int, dtype) -> tuple:
    """(block_q, block_kv) of the latent forward: K/V streamed in the masked
    forward's chunks of 512, the q block the largest of 1,024 and 512 rows
    that the VMEM row admits (a head's K/V chunks below the diagonal are
    fetched once a q block, so twice the rows is half that traffic), both
    clamped to a short sequence."""
    _, bkv = _masked_blocks(n_tokens, dtype)
    isz = jnp.dtype(dtype).itemsize
    for want in (1024, 512):
        bq = tiling.legal_block(want, tiling.round_up(n_tokens, 8), dtype)
        if _latent_vmem_bytes(bq, bkv, nope, vd, isz) <= _SCOPED_VMEM_BYTES:
            break
    return bq, bkv


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("heads", "scale", "n_valid", "bq", "bkv", "causal",
                     "interpret", "tail"))
def _fwd_latent_call(q_nope, q_r, k_nope, k_r, v, *, heads, scale, n_valid,
                     bq, bkv, causal, interpret, tail=None):
    """The latent launch (``fwd_latent``). ``q_nope``, ``k_nope``: ``(rows,
    tokens, H·nope)``; ``v``: ``(rows, tokens, H·vd)``; ``q_r``: ``(rows,
    tokens, H·rot)``; ``k_r``: ``(rows, tokens, 128)``, the one rotated key
    part of all the heads (twice over at ``rot`` 64); every one where its
    projection wrote it, the token axis ending inside the last block. Grid
    and chunk walk as :func:`_fwd_masked_call`'s: head ``h`` reads column
    block ``h`` of q_nope, k_nope and v, the lane group of q_r its rotated
    part lies in, and column block 0 of k_r. ``tail`` as there: the rows the
    last q block's folds run on, the head's half of q_r read at the same
    slice. An inline ``jit`` as that launch is, so that the attention layers
    of a stack, which all launch one shape, trace the body once (five sites a
    Pangu forward)."""
    rows, tokens, _ = q_nope.shape
    nope, vd = q_nope.shape[2] // heads, v.shape[2] // heads
    share = heads * _LANE // q_r.shape[2]  # heads a lane group of q_r
    geometry = dict(bq=bq, bkv=bkv, n_valid=n_valid, causal=causal,
                    window=None)
    n_q, n_kv, chunk = _chunk_walk(tokens, geometry)
    q_spec = lambda lanes, per=1: pl.BlockSpec(
        (1, bq, lanes), lambda b, h, i, j: (b, i, h // per))
    k_spec = lambda lanes, one=False: pl.BlockSpec(
        (1, bkv, lanes),
        lambda b, h, i, j: (b, chunk(i, j), 0 if one else h))
    scratch = _walk_scratch(bq, vd)
    if share > 1:  # this head's half of the q_r block
        scratch.append(pltpu.VMEM((1, bq, _LANE), q_r.dtype))
    with profiling.scope("flash_attention/fwd_latent"):
        return pl.pallas_call(
            functools.partial(_fwd_latent_kernel, scale=scale, n_kv=n_kv,
                              tail=tail, **geometry),
            grid=(rows, heads, n_q, n_kv),
            in_specs=[q_spec(nope), q_spec(_LANE, share), k_spec(nope),
                      k_spec(_LANE, one=True), k_spec(vd)],
            out_specs=q_spec(vd),
            out_shape=_sds(v.shape, v.dtype, v),
            scratch_shapes=scratch,
            compiler_params=_WALK_PARAMS,
            interpret=interpret,
            name="fwd_latent",
        )(q_nope, q_r, k_nope, k_r, v)


def flash_attention_latent(q_nope, q_r, k_nope, k_r, v, scale: float, *,
                           causal: bool = True) -> jax.Array:
    """Attention whose score is the SUM of two products, the second over a
    key part that all the heads share, with the value head at its own width,
    as its own launch (``pallas_call(name="fwd_latent")``, ``%fwd_latent`` in
    a device trace): ``s_ts = (q_nope_h,t · k_nope_h,s + q_r_h,t · k_r,s) ·
    scale``, softmax in float32 over s ≤ t (``causal``; else every s), ``o_h =
    Σ_s p_ts v_h,s``.

    q_nope, k_nope ``(B, N, H, nope)``; q_r ``(B, N, H, rot)``; k_r ``(B, N,
    rot)``; v ``(B, N, H, vd)``; returns ``(B, N, H, vd)`` in v's dtype. Every
    operand is read token-major where its projection wrote it and the context
    written where the output projection reads it: no per-head ``[k_nope,
    k_r]`` array, no head padded to another's size, nothing sliced or
    transposed in HBM. The one thing made on the way is k_r twice over on 128
    lanes at ``rot`` 64 (2.4 MB at 9,217 tokens against a head-wise key of
    453 MB; it folds into whatever wrote k_r), so that the two heads of a
    q_r lane group meet it by a lane mask. Head sizes the launch cannot
    address are refused by name (:func:`latent_sizes`). K/V chunks above
    the diagonal are neither fetched nor multiplied
    (:func:`_fwd_masked_call`'s walk); blocks from the shape and a VMEM row
    (:func:`_latent_blocks`). Where the LAST q block holds few rows of the
    sequence — one of 1,024 at the ``k² + 1`` tokens the samplers make — its
    folds run on those rows in whole groups of 32 and not on the block, by
    the one rule of the three launches of this body (:func:`_tail_rows`: up
    to half a block; past it, and on a block boundary, the program is the one
    without); a row's context is bit for bit the same either way, and
    ``kernels.flash_fwd_tail`` counts which, ``<rows>/<q block>`` or
    ``whole``, +1 a trace. No backward yet: the VJP raises by name."""
    B, N, H, nope, rot, vd = latent_sizes(q_nope, q_r, k_nope, k_r, v)
    _kernels.inc("kernels.flash_fwd_mask", key=mask_kind(causal, None))
    if rot < _LANE:
        k_r = jnp.concatenate([k_r, k_r], axis=-1)
    bq, bkv = _latent_blocks(N, nope, vd, v.dtype)
    tail = _tail_rows(N, bq)
    _kernels.inc("kernels.flash_fwd_tail", key=_tail_key(tail, bq))
    spec = rows_spec(B)
    out = per_device(
        functools.partial(
            _fwd_latent_call, heads=H, scale=scale, n_valid=N, bq=bq, bkv=bkv,
            causal=causal, interpret=kernel_interpret(), tail=tail),
        (spec,) * 5, spec,
    )(q_nope.reshape(B, N, H * nope), q_r.reshape(B, N, H * rot),
      k_nope.reshape(B, N, H * nope), k_r, v.reshape(B, N, H * vd))
    return out.reshape(B, N, H, vd)


def latent_attention_xla(q_nope, q_r, k_nope, k_r, v, scale: float, *,
                         causal: bool = True) -> jax.Array:
    """:func:`flash_attention_latent` in plain ``jax.numpy``: the whole score
    matrix from its two products, softmax in float32. Differentiable; the
    oracle of the kernel and what runs off the TPU, at sizes whose ``(B, H,
    N, N)`` scores fit. Any head sizes."""
    N = q_nope.shape[1]
    logits = (jnp.einsum("bnhd,bmhd->bhnm", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bnhd,bmd->bhnm", q_r, k_r,
                           preferred_element_type=jnp.float32)) * scale
    if causal:
        sees = jnp.arange(N)[None, :] <= jnp.arange(N)[:, None]
        logits = jnp.where(sees, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhnm,bmhd->bnhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _latent_no_vjp(q_nope, q_r, k_nope, k_r, v, scale, causal):
    return flash_attention_latent(q_nope, q_r, k_nope, k_r, v, scale,
                                  causal=causal)


def _latent_no_vjp_fwd(*args):
    raise NotImplementedError(
        "the fwd_latent kernel has no backward yet (ROADMAP Reach: a score in "
        "two parts in dq/dkv): differentiate "
        "ops.flash_attention.latent_attention_xla, which is what "
        "latent_attention runs off the TPU")


_latent_no_vjp.defvjp(_latent_no_vjp_fwd, lambda *a: None)


def latent_attention(q_nope, q_r, k_nope, k_r, v, scale: float, *,
                     causal: bool = True) -> jax.Array:
    """Attention over a two-part score with a key part shared by all the
    heads, shapes as :func:`flash_attention_latent`; the backend decides what
    runs (``kernels.flash_latent_schedule``, ``kernel`` | ``xla``, +1 a
    trace): that kernel on the TPU, :func:`latent_attention_xla` (plain JAX,
    differentiable, any head sizes) anywhere else."""
    on_chip = jax.default_backend() == "tpu"
    _kernels.inc("kernels.flash_latent_schedule",
                 key="kernel" if on_chip else "xla")
    if on_chip:
        return _latent_no_vjp(q_nope, q_r, k_nope, k_r, v, scale, causal)
    return latent_attention_xla(q_nope, q_r, k_nope, k_r, v, scale,
                                causal=causal)


# ---------------------------------------------------------------------------
# fused quant-aware trunk attention (qkv producer → flash → proj consumer)
# ---------------------------------------------------------------------------

def _fused_trunk_kernel(*refs, heads: int, head_dim: int, scale: float,
                        n_valid: int, block_kv: int, n_kv: int,
                        qkv_bias: bool, proj_bias: bool, w8a8: bool):
    """One (batch, q-block, kv-block) program of the fused sampler-trunk
    attention: the w8a16 qkv dequant-matmul runs INSIDE the kernel as the
    producer (int8 weights + per-column scales staged in VMEM, dequantized at
    the MXU feed), the online softmax folds the kv chunk exactly like
    :func:`_fwd_kernel`, and on the last chunk the proj dequant-matmul
    consumes the attention output block in place — the (B, N, 3C) qkv and
    (B, N, C) context activations never round-trip through HBM.

    Numerics mirror the unfused ``QuantDense → flash_attention → QuantDense``
    composition term for term (same dot shapes over the same K reductions,
    same f32 scale/bias epilogues, same compute-dtype casts, same online-
    softmax update order), so the fused path is bitwise at f32 and within
    round-off at bf16 — tests/test_fusion.py pins both.

    ``w8a8=True`` switches the two trunk GEMMs to int8×int8 with int32 MXU
    accumulation: the x activations arrive pre-quantized (per-tensor dynamic
    scale folded into the qkv scales by the wrapper) and the attention output
    is requantized per q-block before the proj GEMM. Attention itself
    (softmax, p·v) stays in the compute dtype — only the trunk GEMM feeds are
    int8. Gated behind the paired-FID ``quantized_sampler_guard``.
    """
    bqkv_ref = bp_ref = None
    if qkv_bias and proj_bias:
        (xq_ref, xkv_ref, wqkv_ref, sqkv_ref, bqkv_ref, wp_ref, sp_ref,
         bp_ref, o_ref, q_s, acc_s, m_s, l_s) = refs
    elif qkv_bias:
        (xq_ref, xkv_ref, wqkv_ref, sqkv_ref, bqkv_ref, wp_ref, sp_ref,
         o_ref, q_s, acc_s, m_s, l_s) = refs
    elif proj_bias:
        (xq_ref, xkv_ref, wqkv_ref, sqkv_ref, wp_ref, sp_ref, bp_ref,
         o_ref, q_s, acc_s, m_s, l_s) = refs
    else:
        (xq_ref, xkv_ref, wqkv_ref, sqkv_ref, wp_ref, sp_ref,
         o_ref, q_s, acc_s, m_s, l_s) = refs
    kv_i = pl.program_id(2)
    C = heads * head_dim
    cdt = q_s.dtype
    w_all = wqkv_ref[...]   # (C, 3C) int8
    s_all = sqkv_ref[0]     # (3C,) f32 (w8a8: pre-folded with the act scale)
    b_all = bqkv_ref[0] if qkv_bias else None

    def project(x, w_cols, s_cols, b_cols):
        # one column range of the qkv dequant-matmul — per output element the
        # SAME K=C reduction the unfused kernel computes, so slicing the
        # weight columns (vs slicing the full qkv output) is value-identical
        if w8a8:
            y = jax.lax.dot_general(
                x, w_cols, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32) * s_cols
        else:
            y = jax.lax.dot_general(
                x, w_cols.astype(cdt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * s_cols
        if b_cols is not None:
            y = y + b_cols
        return y.astype(cdt)  # the QuantDense epilogue cast

    @pl.when(kv_i == 0)
    def _init():
        # q projection once per (batch, q-block); carried across kv chunks
        q_s[...] = project(xq_ref[0], w_all[:, :C], s_all[:C],
                           b_all[:C] if qkv_bias else None)
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # k/v projection for THIS kv chunk — recomputed per chunk, the price of
    # never writing the (B, N, 2C) k/v activation to HBM (2·bkv·C·C MACs per
    # chunk vs a (B, N, 2C) HBM round-trip per layer)
    kv = project(xkv_ref[0], w_all[:, C:], s_all[C:],
                 b_all[C:] if qkv_bias else None)  # (bkv, 2C) cdt

    for h in range(heads):
        lo, hi = h * head_dim, (h + 1) * head_dim
        q_h = q_s[:, lo:hi]          # (bq, hd) cdt
        k_h = kv[:, lo:hi]           # (bkv, hd)
        v_h = kv[:, C + lo:C + hi]
        # identical update math to _fwd_kernel — the zero-padded head-dim
        # lanes of the unfused path contribute exact +0.0 partial products,
        # so the hd-width reduction here is bitwise the Dp-width one
        logits = jax.lax.dot_general(
            q_h, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv) f32
        col = kv_i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(col < n_valid, logits, _NEG_INF)
        m_prev = jnp.max(m_s[h], axis=-1, keepdims=True)  # (bq, 1) replicated
        l_prev = jnp.max(l_s[h], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v_h.dtype), v_h,
                     preferred_element_type=jnp.float32)
        acc_s[:, lo:hi] = acc_s[:, lo:hi] * alpha + pv
        m_s[h] = jnp.broadcast_to(m_new, m_s.shape[1:])
        l_s[h] = jnp.broadcast_to(l_new, l_s.shape[1:])

    @pl.when(kv_i == n_kv - 1)
    def _emit():
        outs = []
        for h in range(heads):
            lo, hi = h * head_dim, (h + 1) * head_dim
            l = jnp.max(l_s[h], axis=-1, keepdims=True)
            outs.append((acc_s[:, lo:hi] / l).astype(cdt))
        attn = jnp.concatenate(outs, axis=-1)  # (bq, C) cdt, head-major cols
        if w8a8:
            amax = jnp.max(jnp.abs(attn.astype(jnp.float32)))
            qs = jnp.where(amax > 0, amax / 127.0, 1.0)
            ai = jnp.clip(jnp.round(attn.astype(jnp.float32) / qs),
                          -127.0, 127.0).astype(jnp.int8)
            y = jax.lax.dot_general(
                ai, wp_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
            y = y * (qs * sp_ref[0])
        else:
            y = jax.lax.dot_general(
                attn, wp_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sp_ref[0]
        if bp_ref is not None:
            # proj bias fused at the scale multiply — the same contraction
            # point as the unfused QuantDense epilogue (quant._mm_kernel)
            y = y + bp_ref[0]
        o_ref[0] = y  # f32; the wrapper casts to the compute dtype


def fused_trunk_attention(x, w_qkv, s_qkv, b_qkv, w_proj, s_proj, b_proj, *,
                          num_heads: int, scale: float, block_q: int = 512,
                          block_kv: int = 1024, mode: str = "pallas"):
    """Quant-aware fused trunk attention: ``x → qkv dequant-GEMM → flash
    attention → proj dequant-GEMM`` as ONE Pallas kernel (inference only —
    the sampler hot path; training keeps the unfused composition and its
    custom VJP).

    ``x``: (B, N, C) activations in the compute dtype; ``w_qkv``/``w_proj``:
    int8 (C, 3C)/(C, C) weights with f32 per-output-column scales (the
    ops/quant.py codec); biases f32 or None. Returns (B, N, C) in ``x``'s
    dtype — the full QuantDense epilogue (scale, bias, cast) included.
    ``mode="w8a8"`` additionally quantizes the activations (per-tensor
    dynamic scale, int8×int8 trunk GEMMs). Backend policy as
    :func:`kernel_interpret`.
    """
    from ddim_cold_tpu.ops import quant as _quant

    B, N, C = x.shape
    head_dim = C // num_heads
    if C % num_heads:
        raise ValueError(f"embed dim {C} must divide by heads {num_heads}")
    if mode not in ("pallas", "w8a8"):
        raise ValueError(f"fused attention mode must be 'pallas' or 'w8a8', "
                         f"got {mode!r}")
    w8a8 = mode == "w8a8"
    interpret = kernel_interpret()

    if w8a8:
        xi, xs = _quant.quantize_act(x)
        x_in = xi
        s_eff = s_qkv.astype(jnp.float32) * xs  # per-tensor act scale folded
    else:
        x_in, s_eff = x, s_qkv.astype(jnp.float32)
    bq = tiling.legal_block(block_q, N, x_in.dtype)
    bkv = tiling.legal_block(block_kv, N, x_in.dtype)
    xq = _pad_to(x_in, 1, bq)
    xkv = _pad_to(x_in, 1, bkv)
    n_q, n_kv = xq.shape[1] // bq, xkv.shape[1] // bkv

    C3 = 3 * C
    inputs = [xq, xkv, w_qkv, s_eff[None, :]]
    in_specs = [
        pl.BlockSpec((1, bq, C), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bkv, C), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((C, C3), lambda b, i, j: (0, 0)),
        pl.BlockSpec((1, C3), lambda b, i, j: (0, 0)),
    ]
    if b_qkv is not None:
        inputs.append(b_qkv.astype(jnp.float32)[None, :])
        in_specs.append(pl.BlockSpec((1, C3), lambda b, i, j: (0, 0)))
    inputs += [w_proj, s_proj.astype(jnp.float32)[None, :]]
    in_specs += [
        pl.BlockSpec((C, C), lambda b, i, j: (0, 0)),
        pl.BlockSpec((1, C), lambda b, i, j: (0, 0)),
    ]
    if b_proj is not None:
        inputs.append(b_proj.astype(jnp.float32)[None, :])
        in_specs.append(pl.BlockSpec((1, C), lambda b, i, j: (0, 0)))
    kernel = functools.partial(
        _fused_trunk_kernel, heads=num_heads, head_dim=head_dim, scale=scale,
        n_valid=N, block_kv=bkv, n_kv=n_kv, qkv_bias=b_qkv is not None,
        proj_bias=b_proj is not None, w8a8=w8a8)
    with profiling.scope("flash_attention/fused_qkv"):
        out = pl.pallas_call(
            kernel,
            grid=(B, n_q, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, C), lambda b, i, j: (b, i, 0)),
            out_shape=_sds((B, n_q * bq, C), jnp.float32, x),
            scratch_shapes=[
                pltpu.VMEM((bq, C), x.dtype),        # projected q block
                pltpu.VMEM((bq, C), jnp.float32),     # per-head output acc
                pltpu.VMEM((num_heads, bq, _LANE), jnp.float32),  # running max
                pltpu.VMEM((num_heads, bq, _LANE), jnp.float32),  # running den
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(*inputs)
    with profiling.scope("flash_attention/fused_proj"):
        # scale + bias already applied in-kernel; only slice off the q-block
        # padding and cast back to the compute dtype
        return out[:, :N].astype(x.dtype)
