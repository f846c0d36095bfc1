"""Seeded weights for a delta-attention / position-free latent-attention /
sigmoid-routed (``model_type: kimi_linear``) configuration, drawn on the
device one leaf at a time and rounded to the configuration's ``precision``
(3.90 B parameters in five layers at Kimi-Linear-48B-A3B-Instruct's widths
with 128 of 256 experts held in 4 expert layers: 7.81 GB in bfloat16; the
largest leaf, a bank of 128 x 2,304 x 1,024, is 0.6 GB and no float32 copy of
a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_kimi.py``); program and reference are given the same
tree. **Column order**: a latent-attention layer's ``q_proj`` holds all the
heads' nope columns, then all their second (``qk_rope_head_dim``) columns,
and ``kv_b_proj`` all the ``k_nope`` columns, then all the ``v`` columns, as
``models/kimi.py`` declares them (the published order has a head's parts side
by side; ``pangu.published_columns`` is the permutation, and a seeded normal
matrix is the same draw under it). Distributions as ``weights_glm.py``: linear
maps normal, std 0.02; ``o_proj`` and every ``down_proj`` divided by sqrt(2 x
layers) as residual branches conventionally are; the latent attention's
``q_proj`` std 2.2/sqrt(hidden_size), which spreads its logits near 1.5 over
a unit-rms input where 0.02 would leave them at 0.6; the norms' scales 1 +
N(0, 0.02); the router std 0.02 (logits of spread ~1 over unit-rms input),
``e_score_correction_bias`` N(0, 0.005) as ``weights_glm.py`` draws GLM's and
for its reason. The delta-attention mixer's own leaves as the family's layers
are initialised: ``A_log`` the log of a rate uniform in [1, 16], one a head;
``dt_bias`` the inverse softplus of a step drawn log-uniform in [1e-3, 1e-1],
one a key channel, so that a seeded head forgets at 0.001 to 1.6 a token
(memories from under a token to a thousand tokens, as a trained model's heads
may have); the depthwise convolutions uniform in +-1/sqrt(taps) (torch's
Conv1d default), no bias; the low-rank decay and gate paths and ``b_proj``
std 0.02 (the decay's logit moves by ~0.2 around ``dt_bias``, beta spreads
around one half, the gate sits near one half).

This file repeats ``weights_glm.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kimi import layer_kind
from benchmark.weights import seed_key
from benchmark.weights_hybrid import DTYPES, outer_specs  # noqa: F401
from benchmark.weights_laguna import _tree

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "hidden_act", "rms_norm_eps", "linear_attn_config",
    "mla_use_nope", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
    "moe_layer_freq", "num_experts", "num_experts_per_token",
    "num_shared_experts", "moe_intermediate_size", "moe_renormalize",
    "moe_router_activation_func", "routed_scaling_factor", "num_expert_group",
    "topk_group", "use_grouped_topk", "layers_from", "experts_held_from")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``num_experts`` is the count held here, and the router's published width
    goes beside it."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["num_experts_routed"] = config["source_values"]["num_experts"]
    return trunk


def layer_specs(trunk: dict, i: int) -> dict:
    """{path: (shape, kind, scale)} of layer i of the slice."""
    at = trunk["layers_from"] + i
    d = trunk["hidden_size"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    attn = lambda *path: ("self_attn",) + path
    gated = lambda prefix, width: {
        prefix + ("gate_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("up_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("down_proj", "kernel"): ((width, d), "normal", branch)}
    specs = {
        ("input_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("post_attention_layernorm", "scale"): ((d,), "one_plus", 0.02)}
    if layer_kind(trunk, i) == "kda":
        lin = trunk["linear_attn_config"]
        heads, hd, taps = (lin["num_heads"], lin["head_dim"],
                           lin["short_conv_kernel_size"])
        inner = heads * hd
        for part in "qkv":
            specs[attn(f"{part}_proj", "kernel")] = ((d, inner), "normal", 0.02)
            specs[attn(f"{part}_conv1d", "conv1d_kernel")] = (
                (taps, inner), "uniform", 1 / math.sqrt(taps))
        specs.update({
            attn("f_a_proj", "kernel"): ((d, hd), "normal", 0.02),
            attn("f_b_proj", "kernel"): ((hd, inner), "normal", 0.02),
            attn("dt_bias"): ((inner,), "dt_bias", (1e-3, 1e-1)),
            # u in (-1, 1); ``make`` turns it into log(8.5 + 7.5 u)
            attn("A_log"): ((heads,), "uniform", 1.0),
            attn("b_proj", "kernel"): ((d, heads), "normal", 0.02),
            attn("g_a_proj", "kernel"): ((d, hd), "normal", 0.02),
            attn("g_b_proj", "kernel"): ((hd, inner), "normal", 0.02),
            attn("o_norm", "scale"): ((hd,), "one_plus", 0.02),
            attn("o_proj", "kernel"): ((inner, d), "normal", branch),
        })
    else:
        heads = trunk["num_attention_heads"]
        nope, rot, vd = (trunk["qk_nope_head_dim"], trunk["qk_rope_head_dim"],
                         trunk["v_head_dim"])
        rank = trunk["kv_lora_rank"]
        specs.update({
            attn("q_proj", "kernel"): ((d, heads * (nope + rot)), "normal",
                                       2.2 / math.sqrt(d)),
            attn("kv_a_proj_with_mqa", "kernel"): ((d, rank + rot),
                                                   "normal", 0.02),
            attn("kv_a_layernorm", "scale"): ((rank,), "one_plus", 0.02),
            attn("kv_b_proj", "kernel"): ((rank, heads * (nope + vd)),
                                          "normal", 0.02),
            attn("o_proj", "kernel"): ((heads * vd, d), "normal", branch),
        })
    if at < trunk["first_k_dense_replace"]:
        specs.update(gated(("mlp",), trunk["intermediate_size"]))
        return specs
    held, width = trunk["num_experts"], trunk["moe_intermediate_size"]
    routed = trunk["num_experts_routed"]
    specs.update(gated(("mlp", "shared_expert"),
                       trunk["num_shared_experts"] * width))
    specs.update({
        ("mlp", "router"): ((d, routed), "normal", 0.02),
        ("mlp", "e_score_correction_bias"): ((routed,), "normal", 0.005),
        ("mlp", "gate_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "up_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "down_proj"): ((held, width, d), "normal", branch),
    })
    return specs


def parameters(trunk: dict, i: int) -> int:
    """How many numbers layer i of the slice holds."""
    return sum(math.prod(shape)
               for shape, _, _ in layer_specs(trunk, i).values())


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    tree = _tree(jax.random.fold_in(key, 0), outer_specs(config), dtype)
    for i in range(trunk["num_hidden_layers"]):
        layer = _tree(jax.random.fold_in(key, 1 + i), layer_specs(trunk, i),
                      dtype)
        mixer = layer["self_attn"]
        if "A_log" in mixer:  # a rate uniform in [1, 16], its log
            u = mixer["A_log"].astype(jnp.float32)
            mixer["A_log"] = jnp.log(8.5 + 7.5 * u).astype(dtype)
        tree[f"layers_{i}"] = layer
    return tree
