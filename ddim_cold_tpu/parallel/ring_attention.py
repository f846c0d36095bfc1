"""Ring attention — sequence/context parallelism over a mesh axis.

The reference's attention is dense O(N²) on one device (ViT.py:110-114; max
in-repo sequence 257 tokens, worst plausible 2501 for the 200px/p4 config) —
sequence parallelism is NOT a reference capability, but it is first-class
here: this is the TPU-native long-context primitive (blockwise softmax with
running max/denominator, K/V blocks rotating around the ring via ``ppermute``
over ICI), the shard_map analogue of Ring Attention (arXiv:2310.01889).

Memory per device drops from O(N²) to O(N·N/P) logits; compute overlaps with
the neighbor exchange. Padding tokens (sequences rarely divide the ring) are
handled with a key-validity mask carried alongside K/V.

Usage: either call ``ring_attention`` inside your own ``shard_map`` with the
sequence dim sharded over ``axis_name``, or use ``ring_self_attention`` which
wraps padding + shard_map over an existing mesh axis.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ddim_cold_tpu.utils import profiling

_NEG_INF = -1e30


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_valid: Optional[jax.Array],
    *,
    axis_name: str,
    scale: float,
    varying_axes: Optional[tuple[str, ...]] = None,
) -> jax.Array:
    """Blockwise-softmax attention with K/V ring rotation.

    Shapes (per-device shards): q/k/v ``(B, n_local, H, D)``, kv_valid
    ``(B, n_local)`` bool (True = real token) or None. Returns ``(B, n_local,
    H, D)``. Non-causal (ViT) — every query attends to every valid key.
    """
    axis_size = jax.lax.psum(1, axis_name)
    B, n_loc, H, D = q.shape
    if kv_valid is None:
        kv_valid = jnp.ones((B, n_loc), dtype=bool)

    # running (output·denominator, denominator, max) accumulators, f32 —
    # marked varying over every axis the inputs vary on (the ring axis, plus
    # the batch axis on a composed dp×sp mesh) for shard_map's vma loop typing
    vary = lambda x: jax.lax.pcast(x, varying_axes or (axis_name,), to="varying")
    o = vary(jnp.zeros((B, H, n_loc, D), jnp.float32))
    l = vary(jnp.zeros((B, H, n_loc), jnp.float32))
    m = vary(jnp.full((B, H, n_loc), _NEG_INF, jnp.float32))
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,H,nq,D)

    def accumulate(o, l, m, k_blk, v_blk, valid_blk):
        from ddim_cold_tpu.ops.flash_attention import online_softmax_update

        logits = jnp.einsum("bhqd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)) * scale
        logits = jnp.where(valid_blk[:, None, None, :], logits, _NEG_INF)
        # v arrives (B, k, H, D); the shared update wants (B, H, k, D)
        return online_softmax_update(
            o, l, m, logits, v_blk.astype(jnp.float32).transpose(0, 2, 1, 3))

    def body(_, carry):
        o, l, m, k_blk, v_blk, valid_blk = carry
        o, l, m = accumulate(o, l, m, k_blk, v_blk, valid_blk)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        with profiling.scope("sp/ring_exchange"):
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            valid_blk = jax.lax.ppermute(valid_blk, axis_name, perm)
        return o, l, m, k_blk, v_blk, valid_blk

    # axis_size − 1 rotations; the final block is consumed outside the loop so
    # no dead last exchange rides the ICI.
    o, l, m, k_blk, v_blk, valid_blk = jax.lax.fori_loop(
        0, axis_size - 1, body, (o, l, m, k, v, kv_valid))
    o, l, _ = accumulate(o, l, m, k_blk, v_blk, valid_blk)
    out = o / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "data",
    batch_axis: Optional[str] = None,
    head_axis: Optional[str] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Global-array front end: pads the sequence to the ring size, shards it
    over ``axis``, runs ``ring_attention`` under shard_map, unpads.

    q/k/v are ``(B, N, H, D)`` global arrays; the result matches dense softmax
    attention. On a composed mesh (e.g. ``{'data': 2, 'seq': 4}``) pass
    ``batch_axis`` so the batch dim stays sharded over data parallelism while
    the ring rotates over ``axis`` — each (data, seq) device row then holds a
    (B/dp, N/sp) tile and the ppermute rides only the seq axis. With tensor
    parallelism too (dp×tp×sp), pass ``head_axis`` so the Megatron-column-
    split qkv activations keep their heads sharded over tp — softmax is
    per-head, so each tp group rings only its own heads; without it the specs
    would force an all-gather and redundant full-head compute.
    """
    B, N, H, D = q.shape
    if scale is None:
        scale = D**-0.5
    parts = int(mesh.shape[axis])
    n_pad = (-N) % parts
    valid = jnp.arange(N + n_pad) < N
    valid = jnp.broadcast_to(valid[None], (B, N + n_pad))
    if n_pad:
        pad = [(0, 0), (0, n_pad), (0, 0), (0, 0)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))

    seq_spec = P(batch_axis, axis, head_axis, None)
    varying = (axis,) + tuple(a for a in (batch_axis, head_axis) if a)
    fn = shard_map(
        partial(ring_attention, axis_name=axis, scale=scale, varying_axes=varying),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(batch_axis, axis)),
        out_specs=seq_spec,
    )
    out = fn(q, k, v, valid)
    return out[:, :N]
