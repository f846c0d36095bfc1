"""The ``nemotron_h`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): layers of ONE pre-normed sub-layer
each, whose kind a pattern string gives by index — a Mamba-2 mixer (a matrix
state a head, scanned over chunks as matrix products: ``ops/ssd.py``), causal
grouped-query attention without any position term, or top-k routed experts
that are ungated squared-ReLU MLPs in a latent narrower than the residual
stream, beside a shared expert on the full width. The wrapper, the input and
output stage, ``RMSNorm``, ``SquaredReluMlp`` and the mixers' depthwise causal
convolution (``causal_conv_silu``: one piece of code with the ``jamba``
stack's Mamba-1 mixer, its arithmetic ``ops/short_conv.py``'s: on the TPU
the one launch ``causal_conv``) are ``hybrid``'s; the attention launch
``ops.flash_attention.masked_attention``; the expert layer
``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json`` (``model_type: nemotron_h``), letter for letter. With x ∈
R^{L×hidden_size}, every norm an RMSNorm with a gain, ε =
``layer_norm_epsilon``, no bias except the convolution's, positions 0 (class
token), 1, … in raster order, published causality kept in all three kinds:

* layer i: ``x += Mixer_i(RMSNorm_i(x))``; ``Mixer_i`` by
  ``hybrid_override_pattern[i]``: ``M``, ``*`` or ``E``. (``-``, the family's
  dense MLP layer, is refused by name: no configuration runs it.) A slice of
  the published stack carries the slice of the published pattern.
* ``M`` (H = ``mamba_num_heads``, P = ``mamba_head_dim``, N =
  ``ssm_state_size``, G = ``n_groups``, d = H·P): ``[z, xBC, dt] = y W_in``
  (d + (d + 2GN) + H columns, in that order); ``xBC ← SiLU(conv1d(xBC))``,
  depthwise, ``conv_kernel`` taps, causal, with a bias (``use_conv_bias``);
  ``[x, B, C] = xBC`` (d, GN, GN); ``Δ = softplus(dt + dt_bias)`` a head,
  unclamped; ``a_h = −exp(A_log_h)``; then ``ops.ssd.ssd_scan`` over chunks of
  ``chunk_size`` (head h reads the B and C of group ``h // (H/G)``); ``y ←
  GroupRMSNorm(y ⊙ SiLU(z))`` — the gate FIRST, the variance over each of the
  G groups of d/G channels, one gain of d — and out ``= y W_out``.
* ``*``: ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads`` K/V heads (query head h reads K/V head ``h //
  (heads/kv)``), ``s_ts = q_t · k_s · head_dim^−½`` for s ≤ t, softmax in
  float32, NO rotary and no other position term inside the layer
  (``rope_theta`` and ``partial_rotary_factor`` are keys of ``config.json``
  that the ``nemotron_h`` modelling code reads nowhere).
* ``E``: ``moe.HeldExpertsMlp`` with ``score="sigmoid"`` and the selection
  bias (``n_group`` = ``topk_group`` = 1: no group limit),
  ``num_experts_per_tok`` a token, weights renormalised (``norm_topk_prob``)
  and scaled by ``routed_scaling_factor``; ``hidden_act="relu2"``: experts and
  the shared one ``W_down relu(W_up ·)²``; ``latent_features =
  moe_latent_size``: the routed experts at ``moe_latent_size →
  moe_intermediate_size → moe_latent_size`` between one ``fc1_latent_proj``
  and one ``fc2_latent_proj`` a layer, the router and the shared expert
  (``moe_shared_expert_intermediate_size``) on the full width.

**The share**, as the other expert stacks have it: ``n_routed_experts`` is how
many experts THIS chip holds, ``experts_held_from`` (default 0) the first of
them, ``n_experts_routed`` (default: all held) the router's published width.

On the TPU the kernels (``ssd_chunk``, ``fwd_masked``, ``moe_gmm``) have no
backward yet and say so by name; off the TPU every path is plain JAX and
differentiates.
"""

from __future__ import annotations

from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddim_cold_tpu.models.glm import _dense
from ddim_cold_tpu.models.hybrid import (
    RMSNorm, _dt_bias_init, causal_conv_silu)
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.ops.flash_attention import masked_attention
from ddim_cold_tpu.ops.ssd import ssd_scan

Dtype = Any

#: the pattern's letters this stack runs, and the scope each is traced under
KINDS = {"M": "trunk/mamba2", "*": "trunk/attn", "E": "trunk/moe"}


def layer_kind(c: Mapping[str, Any], i: int) -> str:
    """``hybrid_override_pattern[i]``, or a refusal that names the letter."""
    pattern = c["hybrid_override_pattern"]
    if i >= len(pattern):
        raise ValueError(f"hybrid_override_pattern {pattern!r} has no layer "
                         f"{i}: {c['num_hidden_layers']} layers asked for")
    kind = pattern[i]
    if kind == "-":
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}, layer {i}: '-', the "
            "family's dense MLP layer, is not written: no configuration runs "
            "it")
    if kind not in KINDS:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r}, layer {i}: {kind!r} is no "
            "layer kind: 'M' (Mamba-2), '*' (attention) and 'E' (experts) "
            "are written")
    return kind


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    for i in range(c["num_hidden_layers"]):
        layer_kind(c, i)
    for key, want in (("mamba_hidden_act", "silu"), ("mlp_hidden_act", "relu2"),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("use_bias", False),
                      ("sliding_window", None), ("n_group", 1),
                      ("topk_group", 1), ("time_step_limit", None)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} {c[key]!r}: this stack is written for "
                             f"{want!r}")
    if c["mamba_num_heads"] % c["n_groups"]:
        raise ValueError(f"mamba_num_heads {c['mamba_num_heads']} must divide "
                         f"into n_groups {c['n_groups']}")
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError("num_attention_heads must divide into "
                         "num_key_value_heads")
    routed = c.get("n_experts_routed", c["n_routed_experts"])
    held_from = c.get("experts_held_from", 0)
    if not 0 <= held_from <= routed - c["n_routed_experts"]:
        raise ValueError(
            f"experts {held_from}..{held_from + c['n_routed_experts'] - 1} "
            f"held of {routed} routed")


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's published initialisation: ``log`` of a decay rate drawn
    uniformly in [1, 16], one a head."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


class GatedGroupRMSNorm(nn.Module):
    """``RMSNorm(y ⊙ SiLU(z))`` with the variance taken over each of
    ``groups`` equal runs of channels and one gain over all of them."""

    groups: int
    eps: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y, z):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (y.shape[-1],), self.param_dtype)
        yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        runs = yf.reshape(*yf.shape[:-1], self.groups, -1)
        runs = runs * jax.lax.rsqrt(
            jnp.mean(runs * runs, -1, keepdims=True) + self.eps)
        return (runs.reshape(yf.shape) * scale.astype(jnp.float32)
                ).astype(self.dtype)


class Mamba2Mixer(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y):
        c = self.trunk
        H, P, N, G = (c["mamba_num_heads"], c["mamba_head_dim"],
                      c["ssm_state_size"], c["n_groups"])
        d = H * P
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        z, xBC, dt = jnp.split(
            _dense(2 * d + 2 * G * N + H, "in_proj", **kw)(y),
            (d, 2 * d + 2 * G * N), -1)
        xBC = causal_conv_silu(self, xBC, c["conv_kernel"], c["use_conv_bias"])
        x, B, C = jnp.split(xBC, (d, d + G * N), -1)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(c["time_step_min"], c["time_step_max"],
                                     c["time_step_floor"]), (H,),
            self.param_dtype)
        delta = jax.nn.softplus(dt.astype(jnp.float32)
                                + dt_bias.astype(jnp.float32))
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,),
                                self.param_dtype).astype(jnp.float32))
        D = self.param("D", nn.initializers.ones_init(), (H,), self.param_dtype)
        out = ssd_scan(x, delta, A, B, C, D, groups=G, chunk=c["chunk_size"])
        out = GatedGroupRMSNorm(G, c["layer_norm_epsilon"], name="norm",
                                **kw)(out, z)
        return _dense(c["hidden_size"], "out_proj", **kw)(out)


class CausalSharedKVAttention(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y):
        c = self.trunk
        n, L, width = y.shape
        heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                         c["head_dim"])
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        q = _dense(heads * hd, "q_proj", **kw)(y).reshape(n, L, heads, hd)
        k = _dense(kv * hd, "k_proj", **kw)(y).reshape(n, L, kv, hd)
        v = _dense(kv * hd, "v_proj", **kw)(y).reshape(n, L, kv, hd)
        out = masked_attention(q, k, v, hd ** -0.5, causal=True, window=None)
        return _dense(width, "o_proj", **kw)(out.reshape(n, L, heads * hd))


class NemotronLayer(nn.Module):
    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, kind = self.trunk, layer_kind(self.trunk, self.index)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        with jax.named_scope(KINDS[kind]):
            y = RMSNorm(c["layer_norm_epsilon"], name="norm", **kw)(x)
            if kind == "M":
                return x + Mamba2Mixer(c, name="mixer", **kw)(y)
            if kind == "*":
                return x + CausalSharedKVAttention(c, name="mixer", **kw)(y)
            return x + HeldExpertsMlp(
                num_routed=c.get("n_experts_routed", c["n_routed_experts"]),
                top_k=c["num_experts_per_tok"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["n_routed_experts"],
                hidden_features=c["moe_intermediate_size"],
                shared_features=c["moe_shared_expert_intermediate_size"],
                scaling=c.get("routed_scaling_factor", 1.0),
                norm_topk=c.get("norm_topk_prob", True),
                score="sigmoid", selection_bias=True, hidden_act="relu2",
                latent_features=c["moe_latent_size"], name="mixer", **kw)(y)


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return NemotronLayer(trunk, i, dtype, param_dtype, name=name)
