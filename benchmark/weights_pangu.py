"""Seeded weights for a dense-causal latent-attention, sandwich-normed,
sigmoid-routed (``model_type: pangu_ultra_moe``) configuration, drawn on the
device one leaf at a time and rounded to the configuration's ``precision``
(3.11 B parameters in five layers at openPangu-Ultra-MoE-718B's widths with 8
of 256 experts held in 4 expert layers: 6.23 GB in bfloat16; the largest
leaf, the dense layer's ``gate_proj`` at 7,680 x 18,432, is 0.28 GB and no
float32 copy of a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_pangu.py``); program and reference are given the same
tree. **Column order**: ``q_b_proj`` holds all the heads' nope columns, then
all their rotated columns, and ``kv_b_proj`` all the ``k_nope`` columns, then
all the ``v`` columns, as ``models/pangu.py`` declares them (the published
order has a head's parts side by side; ``pangu.published_columns`` is the
permutation, and a seeded normal matrix is the same draw under it).
Distributions as ``weights_glm.py``: linear maps normal, std 0.02; ``o_proj``
and every ``down_proj`` divided by sqrt(2 x layers); ``q_b_proj`` std 0.04,
which spreads the attention logits near 1.6 over unit-rms latents; the norms'
scales 1 + N(0, 0.02); the router std 0.02 (logits of spread ~1.75 over
unit-rms input) with no selection bias: this stack has none. **The two
sandwich norms on the sub-layers' results are drawn at ``POST_NORM_GAIN`` x
(1 + N(0, 0.02))**, a twentieth: a sub-layer's result is re-normalised to
unit rms before it is added, and under seeded weights that result is nearly
the same vector for every token (a softmax over thousands of random keys is
an average), so at gain 1 a token-independent component of 1.7 units swamps
the 0.6 of token-specific rms the input stage gives, every token's router
logits order alike, and the 8 held experts see anything from 5,600 to 16,300
of a forward's 294,944 assignments by the seed (9,217 balanced; one expert up
to 8,067 rows of 9,217): ``moe_gmm``'s time and with it the cell's rate moved
by 0.95 % from seed to seed (PERF.md section 6, PR 39). A trained router is
balanced, and a depth-scaled sandwich norm starts its gains at the order of
1/sqrt(layers) times a constant under 1 (0.13 at 61 layers): at 0.05 the
token-specific part leads.

This file repeats ``weights_glm.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math

import jax

from benchmark.weights import seed_key
from benchmark.weights_hybrid import DTYPES, outer_specs  # noqa: F401
from benchmark.weights_laguna import _tree

#: the mean of the gains of the two norms on the sub-layers' results
POST_NORM_GAIN = 0.05

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "hidden_act", "attention_bias", "rms_norm_eps",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "sandwich_norm", "first_k_dense_replace",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
    "layers_from", "experts_held_from")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``n_routed_experts`` is the count held here, and the router's published
    width goes beside it."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["n_experts_routed"] = config["source_values"]["n_routed_experts"]
    return trunk


def layer_specs(trunk: dict, i: int) -> dict:
    """{path: (shape, kind, scale)} of layer i of the slice."""
    at = trunk["layers_from"] + i
    d, heads = trunk["hidden_size"], trunk["num_attention_heads"]
    nope, rot, vd = (trunk["qk_nope_head_dim"], trunk["qk_rope_head_dim"],
                     trunk["v_head_dim"])
    q_rank, kv_rank = trunk["q_lora_rank"], trunk["kv_lora_rank"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    attn = lambda *path: ("self_attn",) + path
    gated = lambda prefix, width: {
        prefix + ("gate_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("up_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("down_proj", "kernel"): ((width, d), "normal", branch)}
    specs = {
        (norm, "scale"): ((d,), "one_plus", 0.02)
        for norm in ("input_layernorm", "post_attention_layernorm",
                     "pre_mlp_layernorm", "post_mlp_layernorm")}
    specs.update({
        attn("q_a_proj", "kernel"): ((d, q_rank), "normal", 0.02),
        attn("q_a_layernorm", "scale"): ((q_rank,), "one_plus", 0.02),
        attn("q_b_proj", "kernel"): ((q_rank, heads * (nope + rot)),
                                     "normal", 0.04),
        attn("kv_a_proj_with_mqa", "kernel"): ((d, kv_rank + rot),
                                               "normal", 0.02),
        attn("kv_a_layernorm", "scale"): ((kv_rank,), "one_plus", 0.02),
        attn("kv_b_proj", "kernel"): ((kv_rank, heads * (nope + vd)),
                                      "normal", 0.02),
        attn("o_proj", "kernel"): ((heads * vd, d), "normal", branch),
    })
    if at < trunk["first_k_dense_replace"]:
        specs.update(gated(("mlp",), trunk["intermediate_size"]))
        return specs
    held, width = trunk["n_routed_experts"], trunk["moe_intermediate_size"]
    specs.update(gated(("mlp", "shared_expert"),
                       trunk["n_shared_experts"] * width))
    specs.update({
        ("mlp", "router"): ((d, trunk["n_experts_routed"]), "normal", 0.02),
        ("mlp", "gate_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "up_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "down_proj"): ((held, width, d), "normal", branch),
    })
    return specs


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    tree = _tree(jax.random.fold_in(key, 0), outer_specs(config), dtype)
    for i in range(trunk["num_hidden_layers"]):
        layer = _tree(jax.random.fold_in(key, 1 + i), layer_specs(trunk, i),
                      dtype)
        for norm in ("post_attention_layernorm", "post_mlp_layernorm"):
            layer[norm]["scale"] = (POST_NORM_GAIN * layer[norm]["scale"]
                                    ).astype(dtype)
        tree[f"layers_{i}"] = layer
    return tree
