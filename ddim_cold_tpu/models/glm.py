"""The ``glm_moe_dsa`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): multi-head *latent* attention
(low-rank query and key/value paths) under a *learned sparse selection* (a
per-layer indexer picks, for every query, the ``index_topk`` keys it attends
to; layers without an indexer borrow the selection of the nearest one before
them), and MLPs that are dense in the leading layers and sigmoid-scored top-k
routed experts plus a shared one after them. The wrapper, the input and output
stage, ``RMSNorm`` and ``GatedMlp`` are ``hybrid``'s; the rotary tables and
their application ``laguna``'s; the expert layer ``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json`` (``model_type: glm_moe_dsa``), letter for letter. The stack
may be a SLICE of the published one: layer i here is published layer
``layers_from + i`` and reads that entry of the per-layer lists
``indexer_types`` and ``mlp_layer_types``. With x ∈ R^{L×hidden_size}, ε =
``rms_norm_eps``, y = RMSNorm(x), no bias but the indexer's LayerNorm,
positions 0 (class token), 1, … in raster order:

* layer i: ``x += Attn_i(RMSNorm(x))``; ``x += FFN_i(RMSNorm(x))``.
* ``Attn_i`` (H = ``num_attention_heads``): ``c_q = RMSNorm(y W_qa)`` ∈
  R^``q_lora_rank``; ``q_h = c_q W_qb`` → H heads of ``[q_nope
  (qk_nope_head_dim), q_rope (qk_rope_head_dim)]``. ``[c_kv, k_r] = y W_kva``
  ∈ R^(``kv_lora_rank`` + ``qk_rope_head_dim``); ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope_h, v_h (v_head_dim)] = c_kv W_kvb`` for each head. Rotary
  (``rope_parameters``; ``rope_interleave``: dims 2j, 2j + 1 pair) on every
  ``q_rope_h`` and on the one ``k_r``, which all the heads share: ``k_h =
  [k_nope_h, k_r]``. Scores ``q_h · k_h · qk_head_dim^−½`` over **s ∈ S_t
  only**, softmax in float32, ``o_h = Σ_s p_s v_h,s``, out ``= concat_h(o_h)
  W_o``. Computed PER HEAD (``ops.flash_attention.selected_attention``, head
  size 256 on both sides of the product): the sampler keeps no cache, so the
  latent is a factorisation here, and multiplying the absorbed latent
  (576/512 dims a pair against 256/256) would cost 2.1x the operations.
  q reaches the launch UNTURNED and is turned there, on the q block it holds
  (``ops.rotary.Rotary``; by ``apply_rotary`` off the TPU): of a head's 256
  columns 64 turn, and no float32 pass crosses the other 192 in HBM.
  k and v are each written ONCE, by ``kv_b_proj``'s own GEMMs, as the launch
  reads them — ``(n, L, H·256)``, a head on two whole lane groups — and
  nothing touches them after (:class:`_KeysAndValuesInPlace`): v is ``c_kv``
  times the kernel's v columns; k is ``[c_kv, k_r]`` times the kernel's
  k_nope columns over *placement rows*, an identity under every head's last
  64 columns, which put the shared ``k_r`` there. The kernel keeps the
  published column order. Not the score in two parts of the ``pangu`` stack
  (``fwd_latent``): 192 is a lane group and a half, and widened to 256 beside
  a 128-lane rotated group the score would take three passes of the 128-deep
  MXU a tile where the 256 of ``[k_nope, k_r]`` take two.
* the indexer, where ``indexer_types[layer] == "full"`` (J =
  ``index_n_heads`` heads of D = ``index_head_dim``): ``q^I = c_q W^I_q``;
  ``k^I = LayerNorm(y W^I_k)`` (one head); rotary on the first
  ``qk_rope_head_dim`` dims of both (``indexer_rope_interleave``); ``w = y
  W^I_w · J^−½ · D^−½``; ``I_ts = Σ_j w_tj · ReLU(q^I_tj · k^I_s)``; S_t = the
  ``index_topk`` best visible keys of query t, exact ties at the threshold all
  kept (``ops.sparse_select.select``). ``"shared"``: no indexer parameters,
  the S of the nearest ``full`` layer before it — handed from layer to layer
  as the second item of what a layer returns.
* ``FFN_i``: ``mlp_layer_types[layer] == "dense"``: the gated SiLU MLP at
  ``intermediate_size``; ``"sparse"``: ``moe.HeldExpertsMlp`` with
  ``score="sigmoid"`` and the selection bias (``topk_method: noaux_tc``;
  ``n_group`` = ``topk_group`` = 1, so no group limit), ``num_experts_per_tok``
  a token, weights renormalised (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``, experts at ``moe_intermediate_size``, the shared
  one at ``n_shared_experts`` times that.

**The share**, as the ``laguna`` stack has it: ``n_routed_experts`` is how
many experts THIS chip holds, ``experts_held_from`` (default 0) the first of
them, ``n_experts_routed`` (default: all held) the router's published width.

On the TPU the kernels (``dsa_index``, ``dsa_select``, ``fwd_selected``,
``moe_gmm``) have no backward yet and say so by name; off the TPU every path
is plain JAX and differentiates (the selection itself is piecewise constant).
"""

from __future__ import annotations

import math
from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.models.hybrid import GatedMlp, RMSNorm
from ddim_cold_tpu.models.init import trunc_normal
from ddim_cold_tpu.models.laguna import rotary_frequencies
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops.flash_attention import selected_attention
from ddim_cold_tpu.ops.rotary import Rotary, apply_rotary
from ddim_cold_tpu.ops.sparse_select import select

Dtype = Any

#: the indexer's LayerNorm, as the public inference code has it
INDEX_NORM_EPS = 1e-6
#: which indexer kind each traced layer had (``kernels.dsa_indexer_layers``)
_kernels = metrics.scope("kernels")

_PER_LAYER = ("indexer_types", "mlp_layer_types")


def published_index(c: Mapping[str, Any], i: int) -> int:
    return c.get("layers_from", 0) + i


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    first, depth = published_index(c, 0), c["num_hidden_layers"]
    for key in _PER_LAYER:
        if len(c[key]) < first + depth:
            raise ValueError(f"{key} has {len(c[key])} entries for layers "
                             f"{first}..{first + depth - 1}")
    unknown = (set(c["indexer_types"][first:first + depth]) - {"full", "shared"}
               | set(c["mlp_layer_types"][first:first + depth])
               - {"dense", "sparse"})
    if unknown:
        raise ValueError(f"layer kinds {sorted(unknown)}: this stack has full "
                         "| shared indexers and dense | sparse MLPs")
    if c["indexer_types"][first] != "full":
        raise ValueError(
            f"layer {first} shares the key selection of a layer before it: a "
            "slice of the stack starts at a layer with a 'full' indexer")
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} {c[key]!r}: this stack is written for "
                             f"{want!r}")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim must be qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if c["v_head_dim"] != c["qk_head_dim"]:
        raise ValueError(
            f"v_head_dim {c['v_head_dim']} against qk_head_dim "
            f"{c['qk_head_dim']}: the attention kernels multiply heads of one "
            "size on both sides")
    if c["index_head_dim"] < c["qk_rope_head_dim"]:
        raise ValueError("the indexer rotates its first qk_rope_head_dim dims")
    if c["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("rope_type: this stack is written for 'default'")
    rotary_frequencies(c["rope_parameters"], c["qk_rope_head_dim"])
    routed = c.get("n_experts_routed", c["n_routed_experts"])
    held_from = c.get("experts_held_from", 0)
    if not 0 <= held_from <= routed - c["n_routed_experts"]:
        raise ValueError(
            f"experts {held_from}..{held_from + c['n_routed_experts'] - 1} "
            f"held of {routed} routed")


def _pairing(interleave: bool) -> str:
    return "interleave" if interleave else "rotate_half"


def _dense(feats: int, name: str, dtype, param_dtype) -> nn.Dense:
    return nn.Dense(feats, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                    kernel_init=trunc_normal(std=0.02), name=name)


class Indexer(nn.Module):
    """S of a ``full`` layer, as the mask ``selected_attention`` reads, from
    the layer's normed input ``y`` and the query latent ``c_q``."""

    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y, c_q):
        c = self.trunk
        n, L, _ = y.shape
        J, D = c["index_n_heads"], c["index_head_dim"]
        dense = lambda feats, name: _dense(feats, name, self.dtype,
                                           self.param_dtype)
        rope = rotary_frequencies(c["rope_parameters"], c["qk_rope_head_dim"])
        pairing = _pairing(c.get("indexer_rope_interleave", False))
        q = apply_rotary(dense(J * D, "wq_b")(c_q), J, *rope, pairing=pairing)
        k = nn.LayerNorm(epsilon=INDEX_NORM_EPS, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="k_norm")(
            dense(D, "wk")(y))
        k = apply_rotary(k, 1, *rope, pairing=pairing)
        w = (dense(J, "weights_proj")(y).astype(jnp.float32)
             * (J ** -0.5 * D ** -0.5))
        return select(q.reshape(n, L, J, D), k, w, c["index_topk"])


class _DenseByColumnSets(nn.Module):
    """A bias-free dense map whose ONE kernel ``(in, Σ widths)`` is multiplied
    a column set at a time: one result array a set, each written where its
    reader takes it, and no slice of an activation after."""

    widths: tuple
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", trunc_normal(std=0.02),
                            (x.shape[-1], sum(self.widths)), self.param_dtype)
        x, kernel = x.astype(self.dtype), kernel.astype(self.dtype)
        return [jnp.dot(x, columns) for columns in jnp.split(
            kernel, np.cumsum(self.widths)[:-1], axis=1)]


def latent_multipliers(c: Mapping[str, Any]) -> tuple:
    """``(a_q, a_kv)``: what the query latent and the key/value latent are
    multiplied by after their norms, ``sqrt(hidden_size / rank)`` where the
    trunk says ``mla_scale_q_lora`` / ``mla_scale_kv_lora``, else 1."""
    return tuple(
        math.sqrt(c["hidden_size"] / c[rank]) if c.get(flag) else 1.0
        for flag, rank in (("mla_scale_q_lora", "q_lora_rank"),
                           ("mla_scale_kv_lora", "kv_lora_rank")))


def latent_paths(c: Mapping[str, Any], y, rope, pairing: str, dtype,
                 param_dtype, *, apart: bool = False):
    """The low-rank paths of latent attention up to the key/value latent, for
    every stack that has them, called inside the attention module's
    ``__call__`` (the parameters are that module's: ``q_a_proj``,
    ``q_a_layernorm``, ``q_b_proj``, ``kv_a_proj_with_mqa``,
    ``kv_a_layernorm``): ``(c_q, q, k_r, c_kv)`` of the layer's normed input
    ``y (n, L, hidden)``, the ONE shared ``k_r (n, L, rot)`` rotated by
    ``rope`` (:func:`laguna.rotary_frequencies`) under ``pairing``, ``c_kv
    (n, L, kv_lora_rank)`` normed.

    **The rescaled latents** (``mla_scale_q_lora``, ``mla_scale_kv_lora``
    true in the trunk; absent or false: 1, and no operation is emitted): the
    published layer multiplies q by ``a_q = sqrt(hidden_size / q_lora_rank)``
    behind ``q_b_proj`` and the normed key/value latent by ``a_kv =
    sqrt(hidden_size / kv_lora_rank)`` before ``kv_b_proj``; ``k_r`` is not
    rescaled. Both are applied here behind the latent's own RMSNorm, on its
    rounded result (:func:`latent_multipliers`): ``c_q = RMSNorm(y W_qa) ·
    a_q``, ``c_kv = RMSNorm(c_kv) · a_kv``, the published order for ``c_kv``.
    ``q_b_proj`` is linear, so ``(c_q · a_q) W_qb = (c_q W_qb) · a_q`` — bit
    for bit where a_q is a power of two (2 at 6,144 / 1,536). ``c_q`` is
    returned rescaled.

    q comes in the column order its reader wants. Published (``apart``
    false): a head's parts side by side, ``q (n, L, H·(nope + rot))``, as
    ``q_b_proj`` wrote it, UNTURNED: a quarter of its columns turn, and its
    reader hands the rotation on to the one launch that reads q
    (``Rotary(*rope, pairing, nope)`` to ``selected_attention``), which
    turns the block it holds. ``apart``: the columns of ``q_b_proj`` as all
    the heads' nope parts then all their rotated parts, ``q = (q_nope (n, L,
    H·nope), q_r (n, L, H·rot))``, each on whole lanes where a kernel reads
    it, q_r TURNED here: the rotation touches nothing else."""
    H, nope, rot = (c["num_attention_heads"], c["qk_nope_head_dim"],
                    c["qk_rope_head_dim"])
    rank = c["kv_lora_rank"]
    kw = dict(dtype=dtype, param_dtype=param_dtype)
    dense = lambda feats, name: _dense(feats, name, **kw)
    norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)

    a_q, a_kv = latent_multipliers(c)
    c_q = norm("q_a_layernorm")(dense(c["q_lora_rank"], "q_a_proj")(y))
    if a_q != 1.0:
        c_q = c_q * a_q
    if apart:
        q_nope, q_r = _DenseByColumnSets((H * nope, H * rot), name="q_b_proj",
                                         **kw)(c_q)
        q = q_nope, apply_rotary(q_r, H, *rope, pairing=pairing)
    else:
        q = dense(H * (nope + rot), "q_b_proj")(c_q)
    kv_a = dense(rank + rot, "kv_a_proj_with_mqa")(y)
    k_r = apply_rotary(kv_a[..., rank:], 1, *rope, pairing=pairing)
    c_kv = norm("kv_a_layernorm")(kv_a[..., :rank])
    if a_kv != 1.0:
        c_kv = c_kv * a_kv
    return c_q, q, k_r, c_kv


def latent_projections(c: Mapping[str, Any], y, rope, pairing: str, dtype,
                       param_dtype):
    """:func:`latent_paths` with the columns ``apart`` and the key/value
    up-projection ``kv_b_proj`` in the same column order: ``(c_q, (q_nope,
    q_r), k_r, (k_nope (n, L, H·nope), v (n, L, H·vd)))``, the kernel's
    columns as all the k_nope then all the v, an array each. (The published
    order's reader is :class:`_KeysAndValuesInPlace`.)"""
    H, nope, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                   c["v_head_dim"])
    kw = dict(dtype=dtype, param_dtype=param_dtype)
    c_q, q, k_r, c_kv = latent_paths(c, y, rope, pairing, apart=True, **kw)
    return c_q, q, k_r, tuple(_DenseByColumnSets(
        (H * nope, H * vd), name="kv_b_proj", **kw)(c_kv))


class _KeysAndValuesInPlace(nn.Module):
    """``kv_b_proj`` of a stack whose key head is ``[k_nope, k_r]`` on whole
    lane groups (192 + 64): ``(k (n, L, H·(nope + rot)), v (n, L, H·vd))`` of
    ``c_kv (n, L, rank)`` and the shared, rotated ``k_r (n, L, rot)``, each
    written ONCE, by its own GEMM, where the attention launch reads it. The
    ONE kernel ``(rank, H·(nope + vd))`` stays in the published column order
    (a head's ``[k_nope, v]`` side by side); its column sets are taken on the
    weight, every call.

    v is ``c_kv`` times the heads' v columns. k is ``[c_kv, k_r]`` times a
    weight of ``rank + rot`` rows: the first ``rank`` hold each head's k_nope
    columns and ``rot`` columns of zeros; the last ``rot`` are *placement
    rows*, for every head zeros under its nope columns and the identity under
    its last ``rot``. Column block h of the product is then ``[c_kv W_nope,h,
    k_r]`` = k_h, bit for bit what a concatenate of the two would hold (a
    product with 1 and sums with exact zeros in the accumulator), for ``rot``
    more rows of contraction and ``rot`` more columns a head on the MXU."""

    heads: int
    nope: int
    vd: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, c_kv, k_r):
        H, nope, vd = self.heads, self.nope, self.vd
        rank, rot = c_kv.shape[-1], k_r.shape[-1]
        kernel = self.param("kernel", trunc_normal(std=0.02),
                            (rank, H * (nope + vd)), self.param_dtype)
        kernel = kernel.astype(self.dtype).reshape(rank, H, nope + vd)
        place = jnp.eye(rot, nope + rot, k=nope, dtype=self.dtype)
        w_k = jnp.concatenate(
            [jnp.pad(kernel[..., :nope], ((0, 0), (0, 0), (0, rot))),
             jnp.broadcast_to(place[:, None], (rot, H, nope + rot))])
        c_kv, k_r = c_kv.astype(self.dtype), k_r.astype(self.dtype)
        k = jnp.dot(jnp.concatenate([c_kv, k_r], axis=-1),
                    w_k.reshape(rank + rot, H * (nope + rot)))
        return k, jnp.dot(c_kv, kernel[..., nope:].reshape(rank, H * vd))


class LatentAttention(nn.Module):
    trunk: Mapping[str, Any]
    indexer: bool
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y, keep):
        """(the attention's result, the selection it attended over)."""
        c = self.trunk
        n, L, width = y.shape
        H, nope, rot = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"])
        hd, vd = c["qk_head_dim"], c["v_head_dim"]
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        dense = lambda feats, name: _dense(feats, name, **kw)
        rope = rotary_frequencies(c["rope_parameters"], rot)
        pairing = _pairing(c.get("rope_interleave", False))
        c_q, q, k_r, c_kv = latent_paths(c, y, rope, pairing, **kw)
        k, v = _KeysAndValuesInPlace(H, nope, vd, name="kv_b_proj", **kw)(
            c_kv, k_r)
        if self.indexer:
            with jax.named_scope("trunk/dsa_index"):
                keep = Indexer(c, name="indexer", **kw)(y, c_q)
        out = selected_attention(q.reshape(n, L, H, hd), k.reshape(n, L, H, hd),
                                 v.reshape(n, L, H, vd), hd ** -0.5, keep,
                                 Rotary(*rope, pairing, nope))
        return dense(width, "o_proj")(out.reshape(n, L, H * vd)), keep


class GlmLayer(nn.Module):
    """``(x, keep) → (x, keep)``: the second item is the key selection the
    layer attended over, its own (``full``) or the one it was handed
    (``shared``), for the next layer."""

    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, keep=None):
        c, layer = self.trunk, published_index(self.trunk, self.index)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        kind = c["indexer_types"][layer]
        if kind == "shared" and keep is None:
            raise ValueError(f"layer {layer} shares a key selection and was "
                             "handed none")
        _kernels.inc("kernels.dsa_indexer_layers", key=kind)
        with jax.named_scope("trunk/mla"):
            out, keep = LatentAttention(c, kind == "full", name="self_attn",
                                        **kw)(norm("input_layernorm")(x), keep)
            x = x + out
        dense = c["mlp_layer_types"][layer] == "dense"
        with jax.named_scope("trunk/mlp" if dense else "trunk/moe"):
            y = norm("post_attention_layernorm")(x)
            if dense:
                return x + GatedMlp(c, name="mlp", **kw)(y), keep
            return x + HeldExpertsMlp(
                num_routed=c.get("n_experts_routed", c["n_routed_experts"]),
                top_k=c["num_experts_per_tok"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["n_routed_experts"],
                hidden_features=c["moe_intermediate_size"],
                shared_features=(c["n_shared_experts"]
                                 * c["moe_intermediate_size"]),
                scaling=c.get("routed_scaling_factor", 1.0),
                norm_topk=c.get("norm_topk_prob", True),
                score="sigmoid", selection_bias=True,
                name="mlp", **kw)(y), keep


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return GlmLayer(trunk, i, dtype, param_dtype, name=name)
