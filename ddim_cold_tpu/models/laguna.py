"""The ``laguna`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): a decoder whose layers mix causal
*window* and causal *full* attention on shared K/V heads, with rotary
positions and a per-head sigmoid gate, and whose MLPs are dense in the
leading layers and top-k routed experts plus a shared one after them. The
wrapper, the input and output stage, ``RMSNorm`` and ``GatedMlp`` are
``hybrid``'s.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json`` (``model_type: laguna``), letter for letter; the four
per-layer lists (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``gating_types``) may be longer than
``num_hidden_layers``: layer i reads entry i. With x ∈ R^{L×hidden_size},
ε = ``rms_norm_eps``, no bias:

* layer i: ``x += Attn_i(RMSNorm(x))``; ``x += FFN_i(RMSNorm(x))``.
* ``Attn_i``, H = ``num_attention_heads_per_layer[i]`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = y W_q``, ``k = y
  W_k``, ``v = y W_v``, ``g = sigmoid(y W_g)`` (one gate a head, from the
  layer's normed input). Rotary positions 0 (class token), 1, … in raster
  order on q and k, by ``rope_parameters[layer_types[i]]``
  (:func:`rotary_frequencies`: ``default``, or ``yarn`` over the first
  ``partial_rotary_factor`` of the dims, ``rotate_half`` pairing). Query head
  h reads K/V head ``h // (H / num_key_value_heads)``; scores ``q k^T ·
  head_dim^−½`` under the mask j ≤ t (``full_attention``) or t −
  ``sliding_window`` < j ≤ t (``sliding_attention``), softmax in float32;
  ``o_h = g_h · Σ_j p_hj v_j``; out ``= concat_h(o_h) W_o``.
  ``ops.flash_attention.masked_attention`` computes it, handed q UNTURNED
  with its rotation (``ops.rotary.Rotary``): on the TPU the ``fwd_masked``
  kernel, which turns each q block where it holds it; elsewhere
  ``apply_rotary`` and blockwise XLA. k is turned here, by ``apply_rotary``.
* ``FFN_i``: ``mlp_layer_types[i] == "dense"``: the gated SiLU MLP at
  ``intermediate_size``; ``"sparse"``: ``moe.HeldExpertsMlp``, softmax router
  over ``num_experts_routed`` outputs, ``num_experts_per_tok`` a token,
  weights renormalised (``norm_topk_prob``) and scaled by
  ``moe_routed_scaling_factor``, experts and the shared expert at
  ``moe_intermediate_size`` / ``shared_expert_intermediate_size``. SiLU is
  this stack's gate everywhere: ``HeldExpertsMlp`` is left at its default
  ``hidden_act`` (the experts' gate is a choice since the ``smallthinker``
  stack, whose experts are ReLU-gated).

**The share.** ``num_experts`` is how many experts THIS chip holds,
``experts_held_from`` (default 0) the first of them, ``num_experts_routed``
(default ``num_experts``: all held) the router's published width. What the
experts held elsewhere would add is left out, and that partial result goes on
to the next layer; nothing stands in for the other chips or their exchange.

On the TPU the two kernels (``fwd_masked``, ``moe_gmm``) have no backward yet
and say so by name; off the TPU every path is plain JAX and differentiates.
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
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.ops.flash_attention import masked_attention
from ddim_cold_tpu.ops.rotary import Rotary, apply_rotary

Dtype = Any

_LAYER_TYPES = ("full_attention", "sliding_attention")
_PER_LAYER = ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer", "gating_types")


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    depth = c["num_hidden_layers"]
    for key in _PER_LAYER:
        if len(c[key]) < depth:
            raise ValueError(f"{key} has {len(c[key])} entries for "
                             f"{depth} layers")
    unknown = (set(c["layer_types"][:depth]) - set(_LAYER_TYPES)
               | set(c["mlp_layer_types"][:depth]) - {"dense", "sparse"}
               | set(c["gating_types"][:depth]) - {"per_head"})
    if unknown:
        raise ValueError(f"layer kinds {sorted(unknown)}: this stack has "
                         f"{_LAYER_TYPES}, dense | sparse MLPs and a "
                         "per_head gate")
    for key, want in (("attention_bias", False),
                      ("moe_router_logit_softcapping", 0),
                      ("moe_apply_router_weight_on_input", False)):
        if c.get(key, want) != want:
            raise ValueError(f"{key} {c[key]!r}: this stack is written for "
                             f"{want!r}")
    if any(h % c["num_key_value_heads"]
           for h in c["num_attention_heads_per_layer"][:depth]):
        raise ValueError("every layer's query heads must divide into "
                         "num_key_value_heads")
    for kind in set(c["layer_types"][:depth]):
        rotary_frequencies(c["rope_parameters"][kind], c["head_dim"])
    routed = c.get("num_experts_routed", c["num_experts"])
    first = c.get("experts_held_from", 0)
    if not 0 <= first <= routed - c["num_experts"]:
        raise ValueError(
            f"experts {first}..{first + c['num_experts'] - 1} held of "
            f"{routed} routed")


def rotary_frequencies(rope: Mapping[str, Any], head_dim: int) -> tuple:
    """(inverse frequencies of the rotated pairs, float64 ``(rot / 2,)``;
    the factor on cos and sin) for one entry of ``rope_parameters``. ``rot =
    head_dim · partial_rotary_factor`` leading dims are rotated.

    ``default``: ``θ^(−2j/rot)``, factor 1. ``yarn`` (the arithmetic of
    ``transformers``' ``_compute_yarn_parameters``): with ``d(β) = rot ·
    ln(original_max_position_embeddings / (2πβ)) / (2 ln θ)``, ``low =
    max(⌊d(beta_fast)⌋, 0)``, ``high = min(⌈d(beta_slow)⌉, rot − 1)``,
    ``ramp_j = clip((j − low) / (high − low), 0, 1)``: ``inv_j / factor ·
    ramp_j + inv_j · (1 − ramp_j)``; cos and sin times ``attention_factor``
    (``0.1 ln factor + 1`` where the config gives none)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return inv, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: 'default' and 'yarn' are "
                         "written")
    factor = float(rope["factor"])
    reach = rope["original_max_position_embeddings"]

    def dim_of(turns):
        return rot * math.log(reach / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv / factor * ramp + inv * (1.0 - ramp), float(scale)


class GatedAttention(nn.Module):
    trunk: Mapping[str, Any]
    layer_type: str
    heads: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        n, L, width = x.shape
        heads, kv, hd = self.heads, c["num_key_value_heads"], c["head_dim"]
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        rope = rotary_frequencies(c["rope_parameters"][self.layer_type], hd)
        # q goes on as q_proj wrote it: the attention turns it where it holds
        # it; k, a sixth to a ninth of q's width, is turned here
        q = dense(heads * hd, "q_proj")(x)
        k = apply_rotary(dense(kv * hd, "k_proj")(x), kv, *rope)
        v = dense(kv * hd, "v_proj")(x)
        gate = jax.nn.sigmoid(dense(heads, "g_proj")(x).astype(jnp.float32))
        window = (c["sliding_window"]
                  if self.layer_type == "sliding_attention" else None)
        out = masked_attention(
            q.reshape(n, L, heads, hd), k.reshape(n, L, kv, hd),
            v.reshape(n, L, kv, hd), hd ** -0.5, causal=True, window=window,
            rotary=Rotary(*rope))
        # a head's gate on each of its lanes, token-major as the context is
        out = (out.reshape(n, L, heads * hd).astype(jnp.float32)
               * jnp.repeat(gate, hd, axis=-1)).astype(self.dtype)
        return dense(width, "o_proj")(out)


class LagunaLayer(nn.Module):
    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, i = self.trunk, self.index
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        layer_type = c["layer_types"][i]
        scope = ("trunk/attn_full" if layer_type == "full_attention"
                 else "trunk/attn_window")
        with jax.named_scope(scope):
            x = x + GatedAttention(
                c, layer_type, c["num_attention_heads_per_layer"][i],
                name="self_attn", **kw)(norm("input_layernorm")(x))
        dense = c["mlp_layer_types"][i] == "dense"
        with jax.named_scope("trunk/mlp" if dense else "trunk/moe"):
            y = norm("post_attention_layernorm")(x)
            if dense:
                return x + GatedMlp(c, name="mlp", **kw)(y)
            return x + HeldExpertsMlp(
                num_routed=c.get("num_experts_routed", c["num_experts"]),
                top_k=c["num_experts_per_tok"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["num_experts"],
                hidden_features=c["moe_intermediate_size"],
                shared_features=c["shared_expert_intermediate_size"],
                scaling=c.get("moe_routed_scaling_factor", 1.0),
                norm_topk=c.get("norm_topk_prob", True),
                name="mlp", **kw)(y)


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return LagunaLayer(trunk, i, dtype, param_dtype, name=name)
