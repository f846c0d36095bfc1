"""The ``smallthinker`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): a decoder whose ROUTER READS THE
LAYER'S INPUT — the residual stream as it arrives, before attention and
before any norm — while the experts it chooses read the normed stream AFTER
attention; whose experts are ReLU-gated, all routed, with no shared one; and
whose attention layers are, by two published lists, causal *window* layers
with rotary positions or causal *full* layers with no position term at all,
on shared K/V heads. The wrapper, the input and output stage and ``RMSNorm``
are ``hybrid``'s; the rotary frequencies ``laguna.rotary_frequencies``; the
attention launch ``ops.flash_attention.masked_attention`` with the ``laguna``
and ``nemotron_h`` stacks; the expert layer ``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json``, letter for letter; the two per-layer lists (``rope_layout``,
``sliding_window_layout``) may be longer than ``num_hidden_layers``: layer i
reads entry i. With x ∈ R^{L×hidden_size} the layer's input, ε =
``rms_norm_eps``, no bias anywhere, positions 0 (class token), 1, … in raster
order:

* ``ℓ = x W_r`` (``W_r``: hidden × ``moe_num_primary_experts_routed``): the
  router's logits, of x ITSELF.
* ``x' = x + Attn_i(RMSNorm_in(x))``, ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = y W_q``, ``k = y
  W_k``, ``v = y W_v``. ``rope_layout[i] == 1``: q and k turned by the default
  rotary (``rope_theta``, every dim, ``rotate_half`` pairing; ``rope_scaling``
  null); ``0``: q and k as projected. Query head h reads K/V head ``h //
  (heads / kv)``; scores ``q k^T · head_dim^−½`` under the mask j ≤ t
  (``sliding_window_layout[i] == 0``) or t − ``sliding_window_size`` < j ≤ t
  (``1``), softmax in float32; out ``= concat_h(o_h) W_o``.
  ``masked_attention`` computes it: with ``rope_layout`` 1 handed q UNTURNED
  with its rotation (``ops.rotary.Rotary``), which on the TPU the
  ``fwd_masked`` kernel applies to each q block where it holds it (elsewhere
  ``apply_rotary`` and blockwise XLA), k turned here by ``apply_rotary``;
  with ``rope_layout`` 0 ``rotary=None`` and nothing turns.
* ``z = RMSNorm_post(x')``; ``p = softmax_f32(ℓ)`` over all the router's
  outputs (``moe_primary_router_apply_softmax``); ``S`` = the
  ``moe_num_active_primary_experts`` largest (ties to the lower index);
  ``w_e = p_e / Σ_{e'∈S} p_e'`` (``norm_topk_prob``; no scaling factor);
  out ``= x' + Σ_{e ∈ S ∩ held} w_e W_down,e(relu(W_gate,e z) ⊙ W_up,e z)`` at
  width ``moe_ffn_hidden_size``: ``HeldExpertsMlp(z, route_from=x)`` with
  ``hidden_act="relu"`` and ``shared_features=0``. The softmax over all the
  outputs renormalised over S equals the softmax over S's logits alone, so
  the source's two orders of the two steps (softmax then top-k, top-k then
  softmax) give these weights; this one is written. No zero of the ReLU is
  skipped: the experts' products are dense.

The routing depends on x alone, so it is traced where the expert layer is
(scope ``trunk/route`` inside ``trunk/moe``) and XLA orders it: nothing here
stands in for an overlap with another chip or a prefetch of experts, which is
what the source puts the router first for.

**The share**, as the other expert stacks have it: ``moe_num_primary_experts``
is how many experts THIS chip holds, ``experts_held_from`` (default 0) the
first of them, ``moe_num_primary_experts_routed`` (default: all held) the
router's published width. What the experts held elsewhere would add is left
out, and that partial result goes on to the next layer.

On the TPU the two kernels (``fwd_masked``, ``moe_gmm``) have no backward yet
and say so by name; off the TPU every path is plain JAX and differentiates.
"""

from __future__ import annotations

from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddim_cold_tpu.models.hybrid import RMSNorm
from ddim_cold_tpu.models.init import trunc_normal
from ddim_cold_tpu.models.laguna import rotary_frequencies
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.ops.flash_attention import masked_attention
from ddim_cold_tpu.ops.rotary import Rotary, apply_rotary

Dtype = Any

_PER_LAYER = ("rope_layout", "sliding_window_layout")


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    depth = c["num_hidden_layers"]
    for key in _PER_LAYER:
        if len(c[key]) < depth:
            raise ValueError(f"{key} has {len(c[key])} entries for "
                             f"{depth} layers")
        unknown = set(c[key][:depth]) - {0, 1}
        if unknown:
            raise ValueError(f"{key} entries {sorted(unknown)}: a layer's is "
                             "0 or 1")
    if c.get("rope_scaling") is not None:
        raise ValueError(f"rope_scaling {c['rope_scaling']!r}: this stack is "
                         "written for the default rotary (null)")
    if not c.get("moe_primary_router_apply_softmax", True):
        raise ValueError("moe_primary_router_apply_softmax false (the "
                         "family's sigmoid router): this stack is written for "
                         "the softmax router")
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError(
            f"num_attention_heads {c['num_attention_heads']} must divide into "
            f"num_key_value_heads {c['num_key_value_heads']}")
    routed = c.get("moe_num_primary_experts_routed",
                   c["moe_num_primary_experts"])
    first = c.get("experts_held_from", 0)
    if not 0 <= first <= routed - c["moe_num_primary_experts"]:
        raise ValueError(
            f"experts {first}..{first + c['moe_num_primary_experts'] - 1} "
            f"held of {routed} routed")


class Attention(nn.Module):
    """``Attn_i`` of the module docstring on the layer's normed input."""

    trunk: Mapping[str, Any]
    rotated: bool
    windowed: bool
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, y):
        c = self.trunk
        n, L, width = y.shape
        heads, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                         c["head_dim"])
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        q, k, v = (dense(h * hd, f"{part}_proj")(y)
                   for part, h in (("q", heads), ("k", kv), ("v", kv)))
        rotary = None
        if self.rotated:
            # q goes on as q_proj wrote it: the attention turns it where it
            # holds it; k, a seventh of q's width, is turned here
            rope = rotary_frequencies({"rope_theta": c["rope_theta"]}, hd)
            k, rotary = apply_rotary(k, kv, *rope), Rotary(*rope)
        out = masked_attention(
            q.reshape(n, L, heads, hd), k.reshape(n, L, kv, hd),
            v.reshape(n, L, kv, hd), hd ** -0.5, causal=True,
            window=c["sliding_window_size"] if self.windowed else None,
            rotary=rotary)
        return dense(width, "o_proj")(out.reshape(n, L, heads * hd))


class SmallThinkerLayer(nn.Module):
    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, i = self.trunk, self.index
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        windowed = bool(c["sliding_window_layout"][i])
        arrived = x  # what the router reads: before the norm, before attention
        with jax.named_scope("trunk/attn_window" if windowed
                             else "trunk/attn_full"):
            x = x + Attention(c, bool(c["rope_layout"][i]), windowed,
                              name="self_attn", **kw)(
                norm("input_layernorm")(x))
        with jax.named_scope("trunk/moe"):
            return x + HeldExpertsMlp(
                num_routed=c.get("moe_num_primary_experts_routed",
                                 c["moe_num_primary_experts"]),
                top_k=c["moe_num_active_primary_experts"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["moe_num_primary_experts"],
                hidden_features=c["moe_ffn_hidden_size"],
                shared_features=0,
                norm_topk=c.get("norm_topk_prob", True),
                hidden_act="relu", name="mlp", **kw)(
                norm("post_attention_layernorm")(x), route_from=arrived)


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return SmallThinkerLayer(trunk, i, dtype, param_dtype, name=name)
