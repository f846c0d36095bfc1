"""The ``longcat_flash`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): a DOUBLE layer — two latent
attentions and two dense gated MLPs in sequence — whose one expert layer is a
*shortcut* round the second half: it reads the stream after the first
attention and its result is added only at the end of the layer, after the
second attention and the second MLP. The router is wider than the experts
that have weights: its outputs past them are zero-compute experts that return
their input. The latent attention is ``models/glm.py``'s projections (one
piece of code for three stacks) with the two latents rescaled after their
norms, attending to every causal pair as the ``pangu_ultra_moe`` stack does.
The wrapper, the input and output stage, ``RMSNorm`` and ``GatedMlp`` are
``hybrid``'s; the attention module and its rotary tables ``pangu``'s; the
expert layer ``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json``, letter for letter: ``num_layers`` (no
``num_hidden_layers``), ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``, ``zero_expert_type``, ``attention_method``,
``mla_scale_q_lora``, ``mla_scale_kv_lora``. The stack may be a SLICE of the
published one (``layers_from``; every layer is of one kind). With x ∈
R^{L×hidden_size}, ``N_*`` RMSNorms with their own gains, ε =
``rms_norm_eps``, no bias anywhere, positions 0 (class token), 1, … in raster
order, every attention causal:

* one layer (published module names ``input_layernorm[0|1]``,
  ``post_attention_layernorm[0|1]``, ``self_attn[0|1]``, ``mlps[0|1]``,
  ``mlp``; here ``input_layernorm_0`` …, flax having no module lists)::

      h1 = x  + Attn_0(N_in0(x))
      y  = N_post0(h1)
      s  = E(y)                      # the shortcut: computed from y, added last
      h2 = h1 + M_0(y)
      h3 = h2 + Attn_1(N_in1(h2))
      h4 = h3 + M_1(N_post1(h3))
      out = h4 + s

  ``M_i(y) = W_down(SiLU(W_gate y) ⊙ W_up y)`` at ``ffn_hidden_size``. ``s``
  is handed across three sub-layers; nothing depends on it until the last
  add, which is what lets a deployment overlap the experts' exchange with
  ``M_0 → Attn_1 → M_1``. Here one chip runs the layer without an exchange
  and XLA orders the two branches as it sees fit.
* ``Attn`` (H = ``num_attention_heads``): ``c_q = RMSNorm(y W_qa) · a_q`` ∈
  R^``q_lora_rank``, ``a_q = sqrt(hidden_size / q_lora_rank)``
  (``mla_scale_q_lora``; published as ``(c_q W_qb) · a_q``: the same number,
  ``glm.latent_paths``); ``[q_nope_h, q_r_h] = c_q W_qb``; ``[c_kv, k_r] = y
  W_kva``; ``c_kv = RMSNorm(c_kv) · a_kv``, ``a_kv = sqrt(hidden_size /
  kv_lora_rank)`` (``mla_scale_kv_lora``); ``[k_nope_h, v_h] = c_kv W_kvb``;
  ``k_r`` is not rescaled. Rotary (θ = ``rope_theta``, dims 2j and 2j + 1
  paired) on every ``q_r_h`` and on the ONE ``k_r``. ``s_ts = (q_nope_h,t ·
  k_nope_h,s + q_r_h,t · k_r,s) · (nope + rot)^−½`` for s ≤ t, softmax in
  float32, ``o_h = Σ_s p_ts v_h,s``, out ``= concat_h(o_h) W_o``. Computed PER
  HEAD through ``ops.flash_attention.latent_attention`` (``fwd_latent`` on the
  TPU) by ``pangu.DenseLatentAttention``, the module of the stack that
  attends the same way, handed this stack's pairing; the rescalings are
  ``glm.latent_paths``', read from the trunk. The sampler keeps no cache, so
  the latent is a factorisation here.
* ``E(y)``: ``r = softmax_f32(y W_r)`` over ALL ``n_experts_routed +
  zero_expert_num`` outputs (the experts with weights, then the zero-compute
  ones); S = the ``moe_topk`` largest of ``r + b``, b an
  ``e_score_correction_bias`` that chooses and never weighs; ``w_e =
  routed_scaling_factor · r_e``, NOT renormalised; ``E(y) = Σ_{e ∈ S, e <
  n_experts_routed} w_e E_e(y) + (Σ_{e ∈ S, e ≥ n_experts_routed} w_e) · y``
  (``zero_expert_type: identity``), ``E_e`` the gated SiLU MLP at
  ``expert_ffn_hidden_size``; no shared expert.

**The column order of two weights is not the published one**, as in the
``pangu_ultra_moe`` stack: ``q_b_proj`` holds all the heads' nope columns then
all their rotated columns, ``kv_b_proj`` all the ``k_nope`` then all the ``v``
(``pangu.published_columns`` is the permutation).

**The share**, as the other stacks have it: ``n_routed_experts`` is how many
experts with weights THIS chip holds, ``experts_held_from`` (default 0) the
first of them, ``n_experts_routed`` (default: all held) their published
count. The chip adds ``Σ_{e ∈ S ∩ held} w_e E_e(y)`` and the identity term;
the identity term is what every chip of a layer computes alike for its own
tokens and is counted once when the shares are added up (tested).

Scopes (``obs/scopes.LAYERS``): each attention with its pre-norm under
``trunk/mla``; each dense MLP with its pre-norm under ``trunk/mlp``
(``post_attention_layernorm_0``, which the experts read too, is the first
MLP's); the expert layer under ``trunk/moe`` with ``trunk/route`` inside; the
last add with the experts.

On the TPU the kernels (``fwd_latent``, ``moe_gmm``) have no backward yet and
say so by name; off the TPU every path is plain JAX and differentiates.
"""

from __future__ import annotations

from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddim_cold_tpu.models.hybrid import GatedMlp, RMSNorm
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.models.pangu import DenseLatentAttention, _rope
from ddim_cold_tpu.ops import flash_attention

Dtype = Any


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    for key, want in (("zero_expert_type", "identity"),
                      ("attention_method", "MLA"), ("attention_bias", False),
                      ("hidden_act", "silu"), ("rope_scaling", None)):
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
    _rope(c)
    routed = c.get("n_experts_routed", c["n_routed_experts"])
    held_from = c.get("experts_held_from", 0)
    if not 0 <= held_from <= routed - c["n_routed_experts"]:
        raise ValueError(
            f"experts {held_from}..{held_from + c['n_routed_experts'] - 1} "
            f"held of {routed} routed")
    if c.get("zero_expert_num", 0) < 0:
        raise ValueError(f"zero_expert_num {c['zero_expert_num']}")


class LongcatLayer(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        attend = lambda half, x: DenseLatentAttention(
            c, pairing="interleave", name=f"self_attn_{half}", **kw)(
            norm(f"input_layernorm_{half}")(x))
        mlp = lambda half, y: GatedMlp(
            {"hidden_size": c["hidden_size"],
             "intermediate_size": c["ffn_hidden_size"]},
            name=f"mlps_{half}", **kw)(y)
        with jax.named_scope("trunk/mla"):
            h1 = x + attend(0, x)
        with jax.named_scope("trunk/mlp"):
            y = norm("post_attention_layernorm_0")(h1)
        with jax.named_scope("trunk/moe"):
            # the shortcut: from what the first MLP reads; nothing below
            # depends on it until the layer's last add
            s = HeldExpertsMlp(
                num_routed=c.get("n_experts_routed", c["n_routed_experts"]),
                top_k=c["moe_topk"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["n_routed_experts"],
                hidden_features=c["expert_ffn_hidden_size"],
                shared_features=0,
                scaling=c.get("routed_scaling_factor", 1.0),
                norm_topk=False, selection_bias=True,
                zero_experts=c.get("zero_expert_num", 0),
                name="mlp", **kw)(y)
        with jax.named_scope("trunk/mlp"):
            h2 = h1 + mlp(0, y)
        with jax.named_scope("trunk/mla"):
            h3 = h2 + attend(1, h2)
        with jax.named_scope("trunk/mlp"):
            h4 = h3 + mlp(1, norm("post_attention_layernorm_1")(h3))
        with jax.named_scope("trunk/moe"):
            return h4 + s


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack: every layer is of the one kind."""
    return LongcatLayer(trunk, dtype, param_dtype, name=name)
