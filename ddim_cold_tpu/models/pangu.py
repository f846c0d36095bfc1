"""The ``pangu_ultra_moe`` layer stack of ``HybridDenoiser`` (``models/hybrid.py``
chooses it by the trunk's ``model_type``): multi-head *latent* attention (the
low-rank query and key/value paths of ``models/glm.py``, one piece of code for
both stacks) over EVERY causal pair, with query/key heads and value heads of
different sizes, inside *sandwich-normed* residuals (every sub-layer's result
passes a second RMSNorm before it is added), and MLPs that are dense in the
leading layers and sigmoid-scored top-k routed experts plus a shared one after
them. The wrapper, the input and output stage, ``RMSNorm`` and ``GatedMlp``
are ``hybrid``'s; the rotary tables and their application ``laguna``'s; the
expert layer ``moe.HeldExpertsMlp``.

Sizes come from ``trunk``, a mapping under the keys of the published
``config.json`` (``model_type: pangu_ultra_moe``), letter for letter:
``rope_theta`` (no ``rope_parameters``), ``first_k_dense_replace`` (no
per-layer list), ``qk_nope_head_dim`` + ``qk_rope_head_dim`` (no
``qk_head_dim``). The stack may be a SLICE of the published one: layer i here
is published layer ``layers_from + i``. With x ∈ R^{L×hidden_size}, N_a … N_d
RMSNorms with their own gains, ε = ``rms_norm_eps``, no bias anywhere,
positions 0 (class token), 1, … in raster order:

* layer i (``sandwich_norm: true``): ``x += N_b(Attn(N_a(x)))``; ``x +=
  N_d(FFN(N_c(x)))`` (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``). ``sandwich_norm`` false
  would be the ``glm_moe_dsa`` stack's pre-norm layer and is refused here
  rather than carried as a path no configuration runs.
* ``Attn`` (H = ``num_attention_heads``): ``c_q = RMSNorm(y W_qa)`` ∈
  R^``q_lora_rank``; ``[q_nope_h, q_r_h] = c_q W_qb`` for each head
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``). ``[c_kv, k_r] = y W_kva`` ∈
  R^(``kv_lora_rank`` + ``qk_rope_head_dim``); ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope_h, v_h (v_head_dim)] = c_kv W_kvb``. Rotary (θ = ``rope_theta``,
  dim j paired with dim j + rot/2) on every ``q_r_h`` and on the ONE ``k_r``,
  which all the heads share. ``s_ts = (q_nope_h,t · k_nope_h,s + q_r_h,t ·
  k_r,s) · (nope + rot)^−½`` for s ≤ t, softmax in float32, ``o_h = Σ_s p_ts
  v_h,s``, out ``= concat_h(o_h) W_o``. Computed PER HEAD and never
  assembled: ``ops.flash_attention.latent_attention`` (``fwd_latent`` on the
  TPU) takes the two parts of the score and the value head at its own width,
  each where its projection wrote it. The sampler keeps no cache, so the
  latent is a factorisation here (the absorbed form multiplies 576 + 512
  dims a pair against 192 + 128).
* ``FFN_i``: published layer ``< first_k_dense_replace``: the gated SiLU MLP
  at ``intermediate_size``; else ``moe.HeldExpertsMlp`` with
  ``score="sigmoid"`` and no selection bias, ``num_experts_per_tok`` a token,
  weights renormalised (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``, experts at ``moe_intermediate_size``, the shared
  one at ``n_shared_experts`` times that.

**The column order of two weights is not the published one**: ``q_b_proj``'s
columns are all the heads' nope parts, then all their rotated parts
(published: a head's ``[nope, rot]`` side by side), and ``kv_b_proj``'s all
the ``k_nope``, then all the ``v`` (published: a head's ``[k_nope, v]``), so
that every operand of the attention is an array of its own on whole lanes
(:func:`published_columns` is the permutation; ``glm.latent_projections``
is the code).

**The share**, as the ``glm_moe_dsa`` and ``laguna`` stacks have it:
``n_routed_experts`` is how many experts THIS chip holds,
``experts_held_from`` (default 0) the first of them, ``n_experts_routed``
(default: all held) the router's published width.

On the TPU the kernels (``fwd_latent``, ``moe_gmm``) have no backward yet and
say so by name; off the TPU every path is plain JAX and differentiates.
"""

from __future__ import annotations

from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.models.glm import _dense, latent_projections, published_index
from ddim_cold_tpu.models.hybrid import GatedMlp, RMSNorm
from ddim_cold_tpu.models.laguna import rotary_frequencies
from ddim_cold_tpu.models.moe import HeldExpertsMlp
from ddim_cold_tpu.ops import flash_attention

Dtype = Any


def _rope(c: Mapping[str, Any]) -> tuple:
    return rotary_frequencies({"rope_theta": c["rope_theta"]},
                              c["qk_rope_head_dim"])


def check_trunk(c: Mapping[str, Any]) -> None:
    """What this stack cannot run, refused at construction."""
    for key, want in (("sandwich_norm", True), ("attention_bias", False),
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


def published_columns(heads: int, first: int, second: int) -> np.ndarray:
    """The published column of each column of an up-projection as this stack
    holds it: ``W_here = W_published[:, published_columns(H, a, b)]`` for a
    weight whose published columns are a head's ``[a, b]`` parts side by side
    and whose columns here are all the heads' ``a`` parts, then all their
    ``b`` parts (``q_b_proj``: nope, rot; ``kv_b_proj``: nope, vd)."""
    head = np.arange(heads)[:, None] * (first + second)
    return np.concatenate([(head + np.arange(first)).ravel(),
                           (head + first + np.arange(second)).ravel()])


class DenseLatentAttention(nn.Module):
    """``Attn`` of the module docstring on the layer's normed input; the
    rotary pairing is the stack's (``rotate_half`` here, ``interleave`` in
    ``models/longcat.py``, which runs this module over rescaled latents)."""

    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    pairing: str = "rotate_half"

    @nn.compact
    def __call__(self, y):
        c = self.trunk
        n, L, width = y.shape
        H, nope, rot, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                            c["qk_rope_head_dim"], c["v_head_dim"])
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        _, (q_nope, q_r), k_r, (k_nope, v) = latent_projections(
            c, y, _rope(c), self.pairing, **kw)
        out = flash_attention.latent_attention(
            q_nope.reshape(n, L, H, nope), q_r.reshape(n, L, H, rot),
            k_nope.reshape(n, L, H, nope), k_r, v.reshape(n, L, H, vd),
            (nope + rot) ** -0.5)
        return _dense(width, "o_proj", **kw)(out.reshape(n, L, H * vd))


class PanguLayer(nn.Module):
    trunk: Mapping[str, Any]
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c, layer = self.trunk, published_index(self.trunk, self.index)
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], name=name, **kw)
        with jax.named_scope("trunk/mla"):
            out = DenseLatentAttention(c, name="self_attn", **kw)(
                norm("input_layernorm")(x))
            x = x + norm("post_attention_layernorm")(out)
        dense = layer < c["first_k_dense_replace"]
        with jax.named_scope("trunk/mlp" if dense else "trunk/moe"):
            y = norm("pre_mlp_layernorm")(x)
            if dense:
                out = GatedMlp(c, name="mlp", **kw)(y)
                return x + norm("post_mlp_layernorm")(out)
            out = HeldExpertsMlp(
                num_routed=c.get("n_experts_routed", c["n_routed_experts"]),
                top_k=c["num_experts_per_tok"],
                first_held=c.get("experts_held_from", 0),
                num_held=c["n_routed_experts"],
                hidden_features=c["moe_intermediate_size"],
                shared_features=(c["n_shared_experts"]
                                 * c["moe_intermediate_size"]),
                scaling=c.get("routed_scaling_factor", 1.0),
                norm_topk=c.get("norm_topk_prob", True),
                score="sigmoid", name="mlp", **kw)(y)
            return x + norm("post_mlp_layernorm")(out)


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of this stack."""
    return PanguLayer(trunk, i, dtype, param_dtype, name=name)
