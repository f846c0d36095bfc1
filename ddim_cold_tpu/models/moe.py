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
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.grouped_matmul import grouped_mlp
from ddim_cold_tpu.ops.quant import gelu_exact

Dtype = Any

#: what the held experts' router read (``kernels.moe_route_source``)
_kernels = metrics.scope("kernels")


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


class HeldExpertsMlp(nn.Module):
    """Top-k routed experts (gated SiLU or gated ReLU, or ungated squared
    ReLU), with or without a shared one beside them, at the residual width or
    in a latent of their own, routed from the rows they read or from another
    tensor, computed by a chip
    that is TOLD WHICH EXPERTS IT HOLDS: of ``num_routed`` experts, the
    ``num_held`` from ``first_held`` on. The expert-parallel share of a layer,
    without its exchange.

    With y ``(rows, hidden)``: ``r = softmax_f32(y W_r)`` over all
    ``num_routed`` router outputs (``score="sigmoid"``: ``sigmoid_f32``, each
    output by itself); ``S`` = the ``top_k`` largest (ties to the lower
    index) of r, or with ``selection_bias`` of ``r + b``, b a per-expert
    ``e_score_correction_bias`` that chooses and never weighs; ``w_e =
    scaling · r_e / Σ_{e'∈S} r_e'`` (``norm_topk``; else
    ``scaling · r_e``); out ``= Shared(y) + Σ_{e ∈ S ∩ held} w_e E_e(y)``,
    ``Shared`` and every ``E_e`` the MLP ``W_down(SiLU(W_gate y) ⊙ W_up y)``
    at width ``hidden_features``. What the experts held elsewhere would add is
    left out: the shares of all the chips, the shared expert counted once,
    sum to the whole layer (tested).

    ``__call__(y, route_from=None)``: with ``route_from`` (y's leading shape,
    any width) the router reads IT, ``r = softmax_f32(route_from W_r)``, and
    only the router: the experts and the shared expert still read y. A stack
    whose router sits a sub-layer before its experts hands the earlier tensor
    here (``models/smallthinker.py``: the layer's input, before attention);
    everything after the logits is the same code.

    ``shared_features=0``: no shared expert — no parameters, no product, the
    sum starts from zeros; the shares then sum to the whole layer with nothing
    counted once.

    ``hidden_act="relu"``: gated like ``"silu"``, ``relu`` in the place of
    ``SiLU``: ``W_down(relu(W_gate y) ⊙ W_up y)``. No zero of the ReLU is
    skipped: the products are dense. Refused beside a shared expert, which no
    configuration has ReLU-gated: ``hybrid.GatedMlp`` is SiLU's.
    ``hidden_act="relu2"``: ``Shared`` and every ``E_e`` the UNGATED MLP
    ``W_down relu(W_up ·)²``. ``latent_features``: the routed experts live in
    a latent of that width, narrower than the residual stream: ``ℓ = y W_ℓin``
    once a layer, ``E_e`` on ℓ at ``latent_features → hidden_features →
    latent_features``, and out ``= Shared(y) + (Σ_{e ∈ S ∩ held} w_e E_e(ℓ))
    W_ℓout``; the router and the shared expert stay on the full width.
    ``W_ℓout`` has no bias, so the shares still sum to the whole layer.

    ``zero_experts`` (default 0: nothing below exists, and the traced program
    is the one without the field): the router is WIDER than the experts that
    have weights. It has ``num_routed + zero_experts`` outputs; ``r``, the
    bias ``b`` and the top k run over all of them; outputs ``num_routed`` and
    up are zero-compute experts of type identity, ``E_e(y) = y``. With
    ``Z = {e ∈ S : e ≥ num_routed}``: out ``= Shared(y) + Σ_{e ∈ S ∩ held} w_e
    E_e(y) + (Σ_{e ∈ Z} w_e) · y``. A pick in Z goes to the null group (no row
    of it is multiplied) and its weight times y is added in the float32
    combine. A row computes between 0 and ``top_k`` expert MLPs, the weights
    of its picks with weights sum to no fixed number (``norm_topk`` normalises
    over all of S, identities included), and the identity term belongs to no
    chip: every chip of a layer computes it alike for its own rows, so the
    shares of all the chips sum to the whole layer with the identity term —
    as the shared expert — counted ONCE (tested). Refused in a latent
    (``latent_features``): ``y`` and the experts' results would differ in
    width, and no configuration has both.

    No capacity, no dropped assignment: every (row, expert, weight) triple of
    the ``rows · top_k`` routed is kept in a buffer of exactly that many rows
    (the worst case, every row routed to held experts only — which is every
    run's case where ``num_held == num_routed``), sorted by expert
    with the assignments to experts held elsewhere last, in a null group that
    has no weights and costs no product: ``ops.grouped_matmul`` skips the row
    tiles past the last held group and writes them as zeros. The sorted rows
    are gathered once and go through ``ops.grouped_matmul.grouped_mlp``: two
    launches on the TPU, one for gate, up and ``act(g) ⊙ u`` (both products
    and the activation in float32, one rounding; ungated: up and
    ``relu(u)²``), one
    for down. Each row then adds
    up its own ``top_k`` results by position (a gather by the inverse
    permutation, weighted and summed in float32: no scatter).

    Counters, +1 a trace: ``kernels.moe_route_source`` (``layer_input`` with
    ``route_from``, ``expert_input`` without); ``kernels.moe_zero_experts``
    (``identity`` with ``zero_experts``, ``none`` without).

    Parameters: ``router (width of what it reads, num_routed +
    zero_experts)``;
    ``gate_proj``,
    ``up_proj`` ``(num_held, hidden, width)``, ``down_proj`` ``(num_held,
    width, hidden)``; ``shared_expert`` a ``hybrid.GatedMlp`` (none with
    ``shared_features=0``);
    ``e_score_correction_bias (num_routed + zero_experts,)`` with
    ``selection_bias``.
    Ungated: no ``gate_proj``, ``shared_expert`` a ``hybrid.SquaredReluMlp``.
    In a latent: ``fc1_latent_proj``, ``fc2_latent_proj`` (a Dense each), and
    the banks' ``hidden`` is ``latent_features``."""

    num_routed: int
    top_k: int
    first_held: int
    num_held: int
    hidden_features: int
    shared_features: int
    scaling: float = 1.0
    norm_topk: bool = True
    score: str = "softmax"
    selection_bias: bool = False
    hidden_act: str = "silu"
    latent_features: int | None = None
    zero_experts: int = 0
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array,
                 route_from: jax.Array | None = None) -> jax.Array:
        from ddim_cold_tpu.models.hybrid import GatedMlp, SquaredReluMlp

        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {self.score!r}: 'softmax' and 'sigmoid' "
                             "are written")
        if self.hidden_act not in ("silu", "relu", "relu2"):
            raise ValueError(f"hidden_act {self.hidden_act!r}: 'silu', 'relu' "
                             "(gated) and 'relu2' (ungated) are written")
        if self.hidden_act == "relu" and self.shared_features:
            raise ValueError("hidden_act 'relu' with a shared expert "
                             f"(shared_features {self.shared_features}): the "
                             "shared expert is written SiLU-gated or ungated")
        if self.zero_experts and self.latent_features is not None:
            raise ValueError(
                f"zero_experts {self.zero_experts} with latent_features "
                f"{self.latent_features}: an identity expert returns the "
                "rows at the residual width, not in the experts' latent")
        gated = self.hidden_act != "relu2"
        *lead, D = x.shape
        y = x.reshape(-1, D)
        if route_from is not None and route_from.shape[:-1] != x.shape[:-1]:
            raise ValueError(f"route_from {route_from.shape} names other rows "
                             f"than the experts read {x.shape}")
        _kernels.inc("kernels.moe_route_source", key=(
            "expert_input" if route_from is None else "layer_input"))
        _kernels.inc("kernels.moe_zero_experts",
                     key="identity" if self.zero_experts else "none")
        routed_on = y if route_from is None else route_from.reshape(
            -1, route_from.shape[-1])
        T, k, G, F = y.shape[0], self.top_k, self.num_held, self.hidden_features
        # the router's outputs: the experts with weights, then the identities
        R = self.num_routed + self.zero_experts
        if not 0 <= self.first_held <= self.num_routed - G or k > R:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + G - 1} held, "
                f"{k} a token, of {self.num_routed} routed"
                + (f" and {self.zero_experts} zero-compute"
                   if self.zero_experts else ""))
        shared = None
        if self.shared_features:
            shared = (GatedMlp if gated else SquaredReluMlp)(
                {"hidden_size": D, "intermediate_size": self.shared_features},
                self.dtype, self.param_dtype, name="shared_expert")(y)

        param = lambda name, shape: self.param(
            name, trunc_normal(std=0.02), shape, self.param_dtype
        ).astype(self.dtype)
        # the routing — logits, the top k, the sort and the group bounds —
        # depends on what the router reads alone and is traced under a scope
        # of its own (``obs.scopes``' ``route`` layer): handed another tensor
        # than the experts', XLA may run it as early as that tensor exists
        with jax.named_scope("trunk/route"):
            logits = jnp.dot(
                routed_on,
                param("router", (routed_on.shape[-1], R)),
                preferred_element_type=jnp.float32)
            r = (jax.nn.softmax(logits, axis=-1) if self.score == "softmax"
                 else jax.nn.sigmoid(logits))
            if self.selection_bias:
                bias = self.param("e_score_correction_bias",
                                  nn.initializers.zeros_init(),
                                  (R,), self.param_dtype)
                _, top_e = jax.lax.top_k(r + bias.astype(jnp.float32), k)
                top_r = jnp.take_along_axis(r, top_e, axis=-1)
            else:
                # (T, k); ties to the lower index
                top_r, top_e = jax.lax.top_k(r, k)
            if self.norm_topk:
                top_r = top_r / jnp.sum(top_r, axis=-1, keepdims=True)
            weight = self.scaling * top_r

            # assignment a = (row a // k, its (a % k)-th expert); key: the held
            # expert's index here, or G for an expert held elsewhere or a
            # zero-compute one (router outputs num_routed and up)
            local = top_e - self.first_held
            held = (local >= 0) & (local < G)
            key = jnp.where(held, local, G).reshape(T * k).astype(jnp.int32)
            order = jnp.argsort(key, stable=True)
            bounds = jnp.searchsorted(key[order],
                                      jnp.arange(G + 1, dtype=jnp.int32))
            group_sizes = jnp.diff(bounds)
            # whole row tiles, so that the product pads nothing; rows past the
            # assignments read row 0 and lie past every group
            M = tiling.round_up(T * k, 128)
            source = jnp.pad(order // k, (0, M - T * k))
        latent = self.latent_features is not None
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        # what the experts read, K wide: the rows, or their latent
        z = dense(self.latent_features, "fc1_latent_proj")(y) if latent else y
        K = z.shape[-1]
        rows = z[source]

        out = grouped_mlp(rows, param("gate_proj", (G, K, F)) if gated else None,
                          param("up_proj", (G, K, F)),
                          param("down_proj", (G, F, K)), group_sizes,
                          **({"act": self.hidden_act} if gated else {}))

        where = jnp.argsort(order).reshape(T, k)  # a's place among the sorted
        weight = jnp.where(held, weight, 0.0)
        total = (jnp.zeros((T, K), jnp.float32) if latent or shared is None
                 else shared.astype(jnp.float32))
        for j in range(k):
            total += weight[:, j, None] * out[where[:, j]].astype(jnp.float32)
        if self.zero_experts:
            # (Σ_{e ∈ Z} w_e) · y: what each row's identity picks weigh
            passed = jnp.where(top_e >= self.num_routed,
                               self.scaling * top_r, 0.0)
            total += (jnp.sum(passed, axis=-1, keepdims=True)
                      * y.astype(jnp.float32))
        if latent:
            beside = None if shared is None else shared.astype(jnp.float32)
            total = dense(D, "fc2_latent_proj")(
                total.astype(self.dtype)).astype(jnp.float32)
            if beside is not None:
                total = beside + total
        return total.astype(self.dtype).reshape(*lead, D)
