"""Seeded weights for a ``model_type: longcat_flash`` configuration (a double
layer of two rescaled-latent attentions and two dense MLPs with the expert
layer as a shortcut round the second half; a softmax router over the experts
with weights and the zero-compute ones), drawn on the device one leaf at a
time and rounded to the configuration's ``precision`` (4.97 B parameters in
four layers at LongCat-Flash-Omni's widths with 16 of 512 experts held: 9.94 GB
in bfloat16; the largest leaf, a bank of 16 x 6,144 x 2,048, is 0.40 GB and no
float32 copy of a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_longcat.py``); program and reference are given the same
tree. **Column order** as ``weights_pangu.py``: ``q_b_proj`` holds all the
heads' nope columns, then all their rotated columns, ``kv_b_proj`` all the
``k_nope`` then all the ``v`` (a seeded normal matrix is the same draw under
the permutation). Distributions as ``weights_pangu.py``: linear maps normal,
std 0.02; ``o_proj`` and every ``down_proj`` divided by sqrt(4 x layers) (a
published layer has two attentions and two dense MLPs: four residual branches
where the other stacks' have two); the norms' scales 1 + N(0, 0.02).
``q_b_proj`` std 0.013: the latents arrive RESCALED (``c_q`` at rms 2, ``c_kv``
at rms sqrt(12)), so a query element has std 2 x 0.013 x sqrt(1,536) = 1.02 and
a ``k_nope`` element sqrt(12) x 0.02 x sqrt(512) = 1.57; the nope part of a
logit then spreads by sqrt(128) x 1.02 x 1.57 / sqrt(192) = 1.31, the rotated
part (``k_r`` at 0.02 x sqrt(6,144) = 1.57, not rescaled) by 0.92, together 1.6
as the other latent cells have it; Pangu's 0.04, drawn for unit latents,
would spread them by 4.9. The router std 0.02: logits of
spread 0.02 x sqrt(6,144) = 1.57 over its unit-rms input, softmax over 768.

``e_score_correction_bias`` N(0, ``BIAS_STD``), **a quarter of the gap between
neighbouring top scores**: with logits N(0, 1.57^2) the 12th largest of 768
scores is r = 0.011 (the normaliser 768 x exp(1.57^2 / 2) = 2,634, the
0.984-quantile's logit 3.38) and its distance to the 13th 5.7e-4 (1 / (768 x
the normal density at 2.15) = 0.033 standard deviations of logit). A bias of
that order reorders picks near the threshold — it changes S — and, chosen
scores being taken from r and not from r + b, never a weight.

**What the router does with these weights** (my chip runs, PR 53; the
reference's own routing on the first forward, t = 1999, of seeds 2147490011,
2147490029 and 2147490047, all four layers, 110,604 picks a layer): identities
32.4-34.0 % of the picks (balanced: 33.3 = 256/768), experts held here
1.95-2.39 % (2,159-2,644 picks a layer; balanced: 2,304 = 16/768), experts
held elsewhere 63.9-65.2 %; of the 16 held experts the fullest saw 251 rows
and the emptiest 85 over the twelve (seed, layer) pairs (mean 144); no token
picked identities alone, 6,865-7,264 of the 9,217 tokens (74-79 %) picked none
of the 16 held here, and none more than 4 of them; a token's weights sum to
1.62 on average, 0.54 of it on identities.

This file repeats ``weights_pangu.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math

import jax

from benchmark.weights import seed_key
from benchmark.weights_hybrid import DTYPES, outer_specs  # noqa: F401
from benchmark.weights_laguna import _tree

#: std of the selection bias: a quarter of the gap between the 12th and the
#: 13th score (the docstring's arithmetic)
BIAS_STD = 1.5e-4

#: the published config.json's keys that size the trunk, ``model_type``
#: (assumed: the catalog's row keeps none) and the share's two
TRUNK_KEYS = (
    "model_type", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "attention_bias", "rms_norm_eps",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "attention_method", "mla_scale_q_lora",
    "mla_scale_kv_lora", "n_routed_experts", "zero_expert_num",
    "zero_expert_type", "moe_topk", "routed_scaling_factor", "layers_from",
    "experts_held_from")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``n_routed_experts`` is the count of experts with weights held here, and
    their published count goes beside it."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["n_experts_routed"] = config["source_values"]["n_routed_experts"]
    return trunk


def layer_specs(trunk: dict) -> dict:
    """{path: (shape, kind, scale)} of a layer: every layer has the same
    leaves."""
    d, heads = trunk["hidden_size"], trunk["num_attention_heads"]
    nope, rot, vd = (trunk["qk_nope_head_dim"], trunk["qk_rope_head_dim"],
                     trunk["v_head_dim"])
    q_rank, kv_rank = trunk["q_lora_rank"], trunk["kv_lora_rank"]
    held, width = trunk["n_routed_experts"], trunk["expert_ffn_hidden_size"]
    outputs = trunk["n_experts_routed"] + trunk["zero_expert_num"]
    branch = 0.02 / math.sqrt(4 * trunk["num_layers"])
    specs = {
        ("mlp", "router"): ((d, outputs), "normal", 0.02),
        ("mlp", "e_score_correction_bias"): ((outputs,), "normal", BIAS_STD),
        ("mlp", "gate_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "up_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "down_proj"): ((held, width, d), "normal", branch),
    }
    for half in (0, 1):
        attn = lambda *path: (f"self_attn_{half}",) + path
        dense = lambda *path: (f"mlps_{half}",) + path
        specs.update({
            (f"input_layernorm_{half}", "scale"): ((d,), "one_plus", 0.02),
            (f"post_attention_layernorm_{half}", "scale"): (
                (d,), "one_plus", 0.02),
            attn("q_a_proj", "kernel"): ((d, q_rank), "normal", 0.02),
            attn("q_a_layernorm", "scale"): ((q_rank,), "one_plus", 0.02),
            attn("q_b_proj", "kernel"): ((q_rank, heads * (nope + rot)),
                                         "normal", 0.013),
            attn("kv_a_proj_with_mqa", "kernel"): ((d, kv_rank + rot),
                                                   "normal", 0.02),
            attn("kv_a_layernorm", "scale"): ((kv_rank,), "one_plus", 0.02),
            attn("kv_b_proj", "kernel"): ((kv_rank, heads * (nope + vd)),
                                          "normal", 0.02),
            attn("o_proj", "kernel"): ((heads * vd, d), "normal", branch),
            dense("gate_proj", "kernel"): ((d, trunk["ffn_hidden_size"]),
                                           "normal", 0.02),
            dense("up_proj", "kernel"): ((d, trunk["ffn_hidden_size"]),
                                         "normal", 0.02),
            dense("down_proj", "kernel"): ((trunk["ffn_hidden_size"], d),
                                           "normal", branch),
        })
    return specs


def parameters(trunk: dict) -> int:
    """How many numbers a layer holds."""
    return sum(math.prod(shape) for shape, _, _ in layer_specs(trunk).values())


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    tree = _tree(jax.random.fold_in(key, 0), outer_specs(config), dtype)
    for i in range(trunk["num_layers"]):
        tree[f"layers_{i}"] = _tree(jax.random.fold_in(key, 1 + i),
                                    layer_specs(trunk), dtype)
    return tree
