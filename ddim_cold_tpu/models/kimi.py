"""The ``kimi_linear`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): pre-normed layers whose mixer is,
by two published lists of layer numbers, either *delta attention* (a matrix
state a head that every token decays by a vector, one factor a key channel,
and then corrects by the delta rule: ``ops/kda.py``) or multi-head *latent*
attention WITHOUT positions (no rotation of either score part, no query
latent), and whose MLP is dense in the leading layers and sigmoid-scored top-k
routed experts plus a shared one after them. The wrapper, the input and output
stage, ``RMSNorm``, ``GatedMlp`` and the depthwise causal convolution
(``causal_conv_silu``: one piece of code with the ``jamba`` and ``nemotron_h``
stacks' mixers, its arithmetic ``ops/short_conv.py``'s: on the TPU the one
launch ``causal_conv``, which also norms q and k) are ``hybrid``'s; the
attention launch ``ops.flash_attention.latent_attention`` with the
``pangu_ultra_moe`` stack; the projections written a column set at a time
``glm._DenseByColumnSets``; the expert layer ``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json`` (``model_type: kimi_linear``), letter for letter, its nested
``linear_attn_config`` and all. The stack may be a SLICE of the published one:
layer i here is published layer ``layers_from + i``, and the two lists count
from 1. With x ∈ R^{L×hidden_size}, every norm an RMSNorm with a gain, ε =
``rms_norm_eps``, no bias anywhere, positions 0 (class token), 1, … in raster
order, NO position term inside any layer:

* layer i: ``x += Mixer_i(N_a(x))``; ``x += FFN_i(N_b(x))``
  (``input_layernorm``, ``post_attention_layernorm``). ``Mixer_i`` is delta
  attention where ``layers_from + i + 1 ∈ linear_attn_config.kda_layers``,
  latent attention where it is in ``full_attn_layers``; a layer in neither
  list, or in both, is refused by name.
* delta attention (H = ``linear_attn_config.num_heads``, d = its
  ``head_dim``): ``q = L2(SiLU(conv(y W_q)))``, ``k = L2(SiLU(conv(y
  W_k)))``, ``v = SiLU(conv(y W_v))``, three projections to H·d and three
  depthwise causal convolutions of ``short_conv_kernel_size`` taps without
  bias, ``L2`` a head's d channels over ``sqrt(Σx² + 1e-6)`` in float32; the
  decay ``g = −exp(A_log_h) · softplus((y W_fa) W_fb + dt_bias)`` ∈ R^{H·d},
  float32, through a rank-d path, ``A_log`` one a head; the step ``β =
  sigmoid_f32(y W_β)`` a head; then ``ops.kda.kda_scan`` with ``scale =
  d^−½``; out ``= (RMSNorm_head(o) ⊙ sigmoid((y W_ga) W_gb)) W_o``: the norm
  over each head's d channels with ONE gain of d for all heads, the gate
  low-rank and AFTER the norm.
* latent attention (H = ``num_attention_heads``): ``[q_nope_h, q_r_h] = y
  W_q`` for each head, DIRECT (``q_lora_rank`` null); ``[c_kv, k_r] = y
  W_kva``; ``c_kv = RMSNorm(c_kv)``; ``[k_nope_h, v_h] = c_kv W_kvb``; no
  rotation (``mla_use_nope``): ``s_ts = (q_nope_h,t · k_nope_h,s + q_r_h,t ·
  k_r,s) · (nope + rot)^−½`` for s ≤ t, the second part's key side shared by
  all the heads, softmax in float32; out ``= concat_h(o_h) W_o``.
* ``FFN_i``: published layer ``< first_k_dense_replace``: the gated SiLU MLP
  at ``intermediate_size``; else ``moe.HeldExpertsMlp`` with
  ``score="sigmoid"`` and the selection bias (``num_expert_group`` =
  ``topk_group`` = 1: no group limit), ``num_experts_per_token`` a token,
  weights renormalised (``moe_renormalize``) and scaled by
  ``routed_scaling_factor``, experts at ``moe_intermediate_size``, the shared
  one at ``num_shared_experts`` times that.

**The column order of two weights is not the published one**, as in the
``pangu_ultra_moe`` stack and for its reason (every operand of the attention
an array of its own on whole lanes): ``q_proj``'s columns are all the heads'
nope parts, then all their second parts (published: a head's ``[nope, rope]``
side by side), ``kv_b_proj``'s all the ``k_nope``, then all the ``v``;
``pangu.published_columns`` is the permutation.

**The share**, as the other expert stacks have it: ``num_experts`` is how
many experts THIS chip holds, ``experts_held_from`` (default 0) the first of
them, ``num_experts_routed`` (default: all held) the router's published width.

On the TPU the kernels (``kda_chunk``, ``fwd_latent``, ``moe_gmm``) have no
backward yet and say so by name; off the TPU every path is plain JAX and
differentiates.
"""

from __future__ import annotations

from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddim_cold_tpu.models.glm import (
    _dense, _DenseByColumnSets, published_index)
from ddim_cold_tpu.models.hybrid import (
    GatedMlp, RMSNorm, _dt_bias_init, causal_conv_silu)
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.models.nemotron import _a_log_init
from ddim_cold_tpu.ops import flash_attention
from ddim_cold_tpu.ops.kda import kda_scan

Dtype = Any

def layer_kind(c: Mapping[str, Any], i: int) -> str:
    """``"kda"`` or ``"mla"`` of layer i of the slice, by its published number
    ``layers_from + i + 1`` in the two lists; a refusal that names the layer
    for one in neither or in both."""
    number = published_index(c, i) + 1
    lists = c["linear_attn_config"]
    delta = number in lists["kda_layers"]
    full = number in lists["full_attn_layers"]
    if delta == full:
        raise ValueError(
            f"layer {number} (layers_from {published_index(c, 0)} + {i} + 1) "
            f"is in {'both' if delta else 'neither'} of "
            "linear_attn_config.kda_layers and full_attn_layers: a layer's "
            "mixer is delta attention or latent attention")
    return "kda" if delta else "mla"


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    for i in range(c["num_hidden_layers"]):
        layer_kind(c, i)
    for key, want in (("hidden_act", "silu"), ("mla_use_nope", True),
                      ("q_lora_rank", None), ("moe_layer_freq", 1),
                      ("moe_router_activation_func", "sigmoid"),
                      ("num_expert_group", 1), ("topk_group", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} {c[key]!r}: this stack is written for "
                             f"{want!r}")
    H, nope, rot, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"])
    # the attention launch's own rule, asked at the sizes of one token
    flash_attention.latent_sizes(
        *(jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
            (1, 1, H, nope), (1, 1, H, rot), (1, 1, H, nope), (1, 1, rot),
            (1, 1, H, vd))))
    routed = c.get("num_experts_routed", c["num_experts"])
    held_from = c.get("experts_held_from", 0)
    if not 0 <= held_from <= routed - c["num_experts"]:
        raise ValueError(
            f"experts {held_from}..{held_from + c['num_experts'] - 1} held "
            f"of {routed} routed")


class _ShortConv(nn.Module):
    """One depthwise causal convolution and its SiLU, without bias, and with
    ``l2_head_dim`` (q's and k's) each head's channels over ``sqrt(Σx² +
    1e-6)`` behind it, in the same launch: a module of its own so that the
    three of a mixer each hold a ``conv1d_kernel``."""

    taps: int
    l2_head_dim: int | None = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        return causal_conv_silu(self, u, self.taps, False, self.l2_head_dim)


class HeadwiseGatedRMSNorm(nn.Module):
    """``RMSNorm_head(o) ⊙ sigmoid(gate)``: the variance over each head's
    ``head_dim`` channels, ONE gain of ``head_dim`` for all the heads, the
    gate after the norm; float32 inside."""

    head_dim: int
    eps: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, o, gate):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.head_dim,), self.param_dtype)
        heads = o.astype(jnp.float32).reshape(*o.shape[:-1], -1, self.head_dim)
        heads = heads * jax.lax.rsqrt(
            jnp.mean(heads * heads, -1, keepdims=True) + self.eps)
        heads = heads * scale.astype(jnp.float32)
        return (heads.reshape(o.shape)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)


class DeltaAttention(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y):
        c, lin = self.trunk, self.trunk["linear_attn_config"]
        H, d, taps = (lin["num_heads"], lin["head_dim"],
                      lin["short_conv_kernel_size"])
        width = y.shape[-1]
        f32 = jnp.float32
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        dense = lambda feats, name: _dense(feats, name, **kw)
        # q and k leave their convolution L2-normed a head, v as it is
        q, k, v = (_ShortConv(taps, d if part in "qk" else None,
                              name=f"{part}_conv1d", **kw)(
            dense(H * d, f"{part}_proj")(y)) for part in "qkv")
        dt_bias = self.param("dt_bias", _dt_bias_init(), (H * d,),
                             self.param_dtype)
        rate = jnp.exp(self.param("A_log", _a_log_init, (H,),
                                  self.param_dtype).astype(f32))
        g = -jnp.repeat(rate, d) * jax.nn.softplus(
            dense(H * d, "f_b_proj")(dense(d, "f_a_proj")(y)).astype(f32)
            + dt_bias.astype(f32))
        beta = jax.nn.sigmoid(dense(H, "b_proj")(y).astype(f32))
        out = kda_scan(q, k, v, g, beta, d ** -0.5)
        gate = dense(H * d, "g_b_proj")(dense(d, "g_a_proj")(y))
        out = HeadwiseGatedRMSNorm(d, c["rms_norm_eps"], name="o_norm", **kw)(
            out, gate)
        return dense(width, "o_proj")(out)


class NopeLatentAttention(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y):
        c = self.trunk
        n, L, width = y.shape
        H, nope, rot, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                            c["qk_rope_head_dim"], c["v_head_dim"])
        rank = c["kv_lora_rank"]
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        q_nope, q_r = _DenseByColumnSets((H * nope, H * rot), name="q_proj",
                                         **kw)(y)
        kv_a = _dense(rank + rot, "kv_a_proj_with_mqa", **kw)(y)
        c_kv = RMSNorm(c["rms_norm_eps"], name="kv_a_layernorm", **kw)(
            kv_a[..., :rank])
        k_nope, v = _DenseByColumnSets((H * nope, H * vd), name="kv_b_proj",
                                       **kw)(c_kv)
        out = flash_attention.latent_attention(
            q_nope.reshape(n, L, H, nope), q_r.reshape(n, L, H, rot),
            k_nope.reshape(n, L, H, nope), kv_a[..., rank:],
            v.reshape(n, L, H, vd), (nope + rot) ** -0.5)
        return _dense(width, "o_proj", **kw)(out.reshape(n, L, H * vd))


class KimiLayer(nn.Module):
    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, kind = self.trunk, layer_kind(self.trunk, self.index)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        mixer = DeltaAttention if kind == "kda" else NopeLatentAttention
        with jax.named_scope(f"trunk/{kind}"):
            x = x + mixer(c, name="self_attn", **kw)(
                norm("input_layernorm")(x))
        dense = published_index(c, self.index) < c["first_k_dense_replace"]
        with jax.named_scope("trunk/mlp" if dense else "trunk/moe"):
            y = norm("post_attention_layernorm")(x)
            if dense:
                return x + GatedMlp(c, name="mlp", **kw)(y)
            return x + HeldExpertsMlp(
                num_routed=c.get("num_experts_routed", c["num_experts"]),
                top_k=c["num_experts_per_token"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["num_experts"],
                hidden_features=c["moe_intermediate_size"],
                shared_features=(c["num_shared_experts"]
                                 * c["moe_intermediate_size"]),
                scaling=c.get("routed_scaling_factor", 1.0),
                norm_topk=c.get("moe_renormalize", True),
                score="sigmoid", selection_bias=True, name="mlp", **kw)(y)


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return KimiLayer(trunk, i, dtype, param_dtype, name=name)
