"""Seeded weights for a latent-attention, sparse-selection, sigmoid-routed
(``model_type: glm_moe_dsa``) configuration, drawn on the device one leaf at a
time and rounded to the configuration's ``precision`` (3.64 B parameters at
GLM-5.2's widths with 16 of 256 experts held in 4 expert layers: 7.29 GB in
bfloat16; the largest leaf, ``o_proj`` at 16,384 x 6,144, is 0.2 GB and no
float32 copy of a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_glm.py``); program and reference are given the same
tree. Distributions: linear maps normal, std 0.02; ``o_proj`` and every
``down_proj`` divided by sqrt(2 x layers) as residual branches conventionally
are; the input and output stage as ``weights_hybrid.py`` draws it. Departures,
as there and for its reason (a check on seeded weights should exercise what
trained weights would): the norms' scales are 1 + N(0, 0.02) instead of 1 and
the indexer's layer norm has a drawn bias; ``q_b_proj`` is drawn with std
0.04, which spreads the attention logits near 1.6 over unit-rms latents where
0.02 would leave them at 0.8; ``e_score_correction_bias`` is N(0, 0.005): the
eight chosen scores lie above 0.95, where the sigmoid is flat (a unit of logit
is 0.05 of score), so this bias moves an expert's threshold by a tenth of a
logit and its load by a fifth, the residue a balancing bias is left with once
trained; N(0, 0.05), ten times that, moved loads 3.5-fold an expert and the
held sixteenth of the assignments by a fifth from seed to seed (PERF.md
section 6, PR 33). The router stays at std 0.02
(logits of spread ~1.6 over unit-rms input), and so does the indexer: its
selection does not depend on the scale of its scores.

This file repeats ``weights_laguna.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math

import jax

from benchmark.weights import seed_key
from benchmark.weights_hybrid import DTYPES, outer_specs  # noqa: F401
from benchmark.weights_laguna import _tree

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_act",
    "attention_bias", "rms_norm_eps", "q_lora_rank", "kv_lora_rank",
    "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "rope_interleave", "rope_parameters", "index_n_heads", "index_head_dim",
    "index_topk", "indexer_rope_interleave", "indexer_types",
    "mlp_layer_types", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "scoring_func", "topk_method", "n_group",
    "topk_group", "layers_from", "experts_held_from")


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
        ("input_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("post_attention_layernorm", "scale"): ((d,), "one_plus", 0.02),
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
    }
    if trunk["indexer_types"][at] == "full":
        j, dim = trunk["index_n_heads"], trunk["index_head_dim"]
        specs.update({
            attn("indexer", "wq_b", "kernel"): ((q_rank, j * dim),
                                                "normal", 0.02),
            attn("indexer", "wk", "kernel"): ((d, dim), "normal", 0.02),
            attn("indexer", "k_norm", "scale"): ((dim,), "one_plus", 0.02),
            attn("indexer", "k_norm", "bias"): ((dim,), "normal", 0.02),
            attn("indexer", "weights_proj", "kernel"): ((d, j), "normal", 0.02),
        })
    if trunk["mlp_layer_types"][at] == "dense":
        specs.update(gated(("mlp",), trunk["intermediate_size"]))
        return specs
    held, width = trunk["n_routed_experts"], trunk["moe_intermediate_size"]
    routed = trunk["n_experts_routed"]
    specs.update(gated(("mlp", "shared_expert"),
                       trunk["n_shared_experts"] * width))
    specs.update({
        ("mlp", "router"): ((d, routed), "normal", 0.02),
        ("mlp", "e_score_correction_bias"): ((routed,), "normal", 0.005),
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
        tree[f"layers_{i}"] = _tree(jax.random.fold_in(key, 1 + i),
                                    layer_specs(trunk, i), dtype)
    return tree
