"""Switch-style mixture-of-experts MLP — the ``ep`` (expert-parallel) axis.

The reference is data-parallel only (SURVEY.md C17) and its ViT uses a dense
MLP (reference ViT.py:74-90); this module is TPU-native scale-out beyond
parity: ``num_experts`` in the YAML swaps each block's MLP for a top-1
routed expert bank (Switch Transformer, arXiv:2101.03961) whose stacked
expert parameters shard over an ``expert`` mesh axis
(parallel/sharding.py). The routing math is pure one-hot einsum
dispatch/combine — static shapes, no gather/scatter, no host control flow —
so XLA lays the token exchange onto ICI collectives by itself.

Design notes (TPU-first):

* routing is per batch row over its N tokens with per-expert capacity
  ``C = ceil(N / E · capacity_factor)`` — everything stays (B, …)-leading,
  so the ``data`` batch sharding composes untouched;
* overflow tokens are DROPPED by the expert (their MLP delta is zero) and
  ride the block's residual connection unchanged — the Switch paper's
  behavior, and what keeps shapes static;
* the router runs in float32 (softmax stability under bf16 compute);
* the Switch load-balance auxiliary loss is ``sow``n into the ``losses``
  collection; the train step adds ``moe_aux_weight ×`` its mean (it is a
  no-op for consumers that do not mark the collection mutable, so the
  sampler/eval paths need no changes).

Two dispatch implementations, selectable per config (``moe_dispatch``):

* ``"einsum"`` (default) — one-hot dispatch/combine tensors (B, N, E, C)
  with E·C ≈ N·capacity_factor, i.e. **O(B·N²·cf) activation memory per
  MoE block**: all-GEMM, no gather/scatter, the friendliest form for the
  XLA partitioner — and fine at the 64px scales (N ≤ 257);
* ``"index"`` — stable-sort tokens by expert id, gather each expert's
  capacity slice, scatter-free token-side combine via a per-token slot
  gather: **O(B·N·cf·D)** activations, no quadratic tensor anywhere. The
  stable sort preserves token order within an expert, so exactly the same
  tokens overflow as under the einsum path's cumsum priority — the two
  modes are numerically interchangeable (tested) — making MoE composable
  with long-sequence configs (the 200px/p4 N=2501 case that motivated it,
  ADVICE r3).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ddim_cold_tpu.models.init import trunc_normal
from ddim_cold_tpu.ops.quant import gelu_exact

Dtype = Any


class SwitchMlp(nn.Module):
    """Top-1 routed expert bank, drop-in for the block's dense ``Mlp``."""

    num_experts: int
    hidden_features: int
    out_features: int
    capacity_factor: float = 1.25
    drop: float = 0.0
    dtype: Dtype = jnp.float32
    dispatch: str = "einsum"  # "einsum" (one-hot GEMMs) | "index" (sort/gather)

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        import math

        B, N, D = x.shape
        E, H = self.num_experts, self.hidden_features
        # per-expert queue length: static at trace time (N, E, cf all static)
        C = max(1, math.ceil(N * self.capacity_factor / E))

        # ---- router (f32: softmax stability under bf16 compute) ----------
        wr = self.param("router", trunc_normal(std=0.02), (D, E), jnp.float32)
        logits = jnp.einsum("bnd,de->bne", x.astype(jnp.float32), wr)
        probs = jax.nn.softmax(logits, axis=-1)  # (B, N, E)
        expert = jnp.argmax(probs, axis=-1)  # (B, N)
        gate = jnp.max(probs, axis=-1)  # (B, N)

        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (B, N, E)
        if self.dispatch == "index":
            # sort/gather routing, O(B·N·cf·D): stable sort by expert id
            # groups tokens per expert WITHOUT changing their order inside a
            # group, so slot priority (and therefore the overflow set) is
            # identical to the einsum path's cumsum priority.
            perm = jnp.argsort(expert, axis=1, stable=True)          # (B, N)
            exp_sorted = jnp.take_along_axis(expert, perm, axis=1)   # (B, N)
            x_sorted = jnp.take_along_axis(
                x.astype(self.dtype), perm[..., None], axis=1)       # (B, N, D)
            counts = jnp.sum(onehot, axis=1).astype(jnp.int32)       # (B, E)
            starts = jnp.cumsum(counts, axis=1) - counts             # (B, E)
            # expert e's queue slot c holds sorted token starts[e] + c
            c_ar = jnp.arange(C, dtype=jnp.int32)
            idx = starts[:, :, None] + c_ar[None, None, :]           # (B, E, C)
            q_valid = c_ar[None, None, :] < counts[:, :, None]       # (B, E, C)
            idx = jnp.clip(idx, 0, N - 1).reshape(B, E * C)
            xe = jnp.take_along_axis(x_sorted, idx[..., None], axis=1)
            xe = (xe.reshape(B, E, C, D)
                  * q_valid[..., None].astype(self.dtype))
        elif self.dispatch == "einsum":
            # position of each token in its expert's queue (per batch row)
            pos = jnp.cumsum(onehot, axis=1) - onehot  # (B, N, E)
            within = pos < C
            keep = onehot * within  # (B, N, E) — dropped tokens zero out here
            slot = jax.nn.one_hot(
                (pos * onehot).sum(-1).astype(jnp.int32), C, dtype=jnp.float32)
            # dispatch/combine one-hots (B, N, E, C): static-shape einsum routing
            dispatch = keep[..., None] * slot[:, :, None, :]
            combine = dispatch * gate[..., None, None]
            xe = jnp.einsum("bnd,bnec->becd", x.astype(self.dtype),
                            dispatch.astype(self.dtype))
        else:
            raise ValueError(
                f"dispatch must be 'einsum' or 'index', got {self.dispatch!r}")

        # ---- experts: stacked params, leading E shards over 'expert' -----
        O = self.out_features
        w1 = self.param("w1", trunc_normal(std=0.02), (E, D, H), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros_init(), (E, H), jnp.float32)
        w2 = self.param("w2", trunc_normal(std=0.02), (E, H, O), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros_init(), (E, O), jnp.float32)

        h = jnp.einsum("becd,edh->bech", xe, w1.astype(self.dtype))
        h = h + b1.astype(self.dtype)[None, :, None, :]
        h = gelu_exact(h)
        h = nn.Dropout(self.drop, deterministic=deterministic)(h)
        ye = jnp.einsum("bech,ehd->becd", h, w2.astype(self.dtype))
        ye = ye + b2.astype(self.dtype)[None, :, None, :]
        if self.dispatch == "index":
            # token-side combine: each token reads its own queue slot (a
            # gather, no (B, N, E, C) combine tensor). pos = this token's
            # rank within its expert group, recovered by inverting the sort.
            rank = (jnp.arange(N, dtype=jnp.int32)[None, :]
                    - jnp.take_along_axis(starts, exp_sorted, axis=1))
            # invert the sort by scattering rank back to token order — O(N),
            # where a second argsort would be another full TPU sort
            tok_pos = jnp.put_along_axis(jnp.zeros_like(rank), perm, rank,
                                         axis=1, inplace=False)      # (B, N)
            keep_tok = tok_pos < C
            slot_tok = jnp.clip(expert.astype(jnp.int32) * C + tok_pos,
                                0, E * C - 1)
            y = jnp.take_along_axis(ye.reshape(B, E * C, O),
                                    slot_tok[..., None], axis=1)
            w_tok = (gate * keep_tok).astype(self.dtype)
            y = y * w_tok[..., None]
        else:
            y = jnp.einsum("becd,bnec->bnd", ye, combine.astype(self.dtype))
        y = nn.Dropout(self.drop, deterministic=deterministic)(y)

        # ---- Switch load-balance loss: E · Σ_e f_e · P_e -----------------
        # f_e = fraction of tokens routed to e, P_e = mean router prob of e
        frac = onehot.mean(axis=(0, 1))  # (E,)
        mean_prob = probs.mean(axis=(0, 1))  # (E,)
        self.sow("losses", "moe_aux", E * jnp.sum(frac * mean_prob))
        return y
