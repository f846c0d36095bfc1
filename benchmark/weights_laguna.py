"""Seeded weights for a sparse-expert (``model_type: laguna``) configuration,
drawn on the device one leaf at a time and rounded to the configuration's
``precision`` (5.29 B parameters at Laguna-S-2.1's widths with 128 of 256
experts held in 4 expert layers: 10.57 GB in bfloat16; the largest leaf, a
bank of 128 x 3,072 x 1,024, is 0.8 GB and no float32 copy of a layer ever
exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_laguna.py``); program and reference are given the same
tree. Distributions: linear maps normal, std 0.02; ``o_proj`` and every
``down_proj`` divided by sqrt(2 x layers) as residual branches conventionally
are; the input and output stage as ``weights_hybrid.py`` draws it. Departures,
as there and for its reason (a check on seeded weights should exercise what
trained weights would): the norms' scales are 1 + N(0, 0.02) instead of 1, the
head's bias is drawn, and ``q_proj``/``k_proj`` are drawn with std
1.2/sqrt(hidden_size), which spreads the attention logits near 1.4 in the
window layers (times the square of YaRN's ``attention_factor`` in the full
ones) where 0.02 would leave every softmax all but uniform. The router stays
at std 0.02: logits of spread ~1.1 over unit-rms input, routing that is
neither uniform nor collapsed; the per-head gate likewise (gates spread
around one half).

This file repeats ``weights_hybrid.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math
from functools import partial

import jax

from benchmark.weights import _nest, seed_key
from benchmark.weights_hybrid import DTYPES, _leaf, outer_specs  # noqa: F401

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "model_type", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "attention_bias", "rms_norm_eps", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "shared_expert_intermediate_size",
    "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers", "gating",
    "sliding_window", "rope_parameters", "layer_types",
    "moe_apply_router_weight_on_input", "mlp_layer_types", "gating_types",
    "moe_routed_scaling_factor", "num_attention_heads_per_layer",
    "moe_router_logit_softcapping")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``num_experts`` is the count held here, and the share's other two numbers
    go beside it: the router's published width and the first expert held."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["num_experts_routed"] = config["source_values"]["num_experts"]
    trunk["experts_held_from"] = config["experts_held_from"]
    return trunk


def layer_specs(trunk: dict, i: int) -> dict:
    """{path: (shape, kind, scale)} of layer i."""
    d, hd = trunk["hidden_size"], trunk["head_dim"]
    heads = trunk["num_attention_heads_per_layer"][i]
    kv = trunk["num_key_value_heads"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    qk = 1.2 / math.sqrt(d)
    gated = lambda prefix, width: {
        prefix + ("gate_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("up_proj", "kernel"): ((d, width), "normal", 0.02),
        prefix + ("down_proj", "kernel"): ((width, d), "normal", branch)}
    specs = {
        ("input_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("post_attention_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("self_attn", "q_proj", "kernel"): ((d, heads * hd), "normal", qk),
        ("self_attn", "k_proj", "kernel"): ((d, kv * hd), "normal", qk),
        ("self_attn", "v_proj", "kernel"): ((d, kv * hd), "normal", 0.02),
        ("self_attn", "g_proj", "kernel"): ((d, heads), "normal", 0.02),
        ("self_attn", "o_proj", "kernel"): ((heads * hd, d), "normal", branch),
    }
    if trunk["mlp_layer_types"][i] == "dense":
        specs.update(gated(("mlp",), trunk["intermediate_size"]))
        return specs
    held, width = trunk["num_experts"], trunk["moe_intermediate_size"]
    specs.update(gated(("mlp", "shared_expert"),
                       trunk["shared_expert_intermediate_size"]))
    specs.update({
        ("mlp", "router"): ((d, trunk["num_experts_routed"]), "normal", 0.02),
        ("mlp", "gate_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "up_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "down_proj"): ((held, width, d), "normal", branch),
    })
    return specs


@partial(jax.jit, static_argnames=("shape", "kind", "scale", "dtype"))
def _draw(key, shape, kind, scale, dtype):
    return _leaf(key, shape, kind, scale).astype(dtype)


def _tree(key, specs: dict, dtype) -> dict:
    return _nest({
        path: _draw(jax.random.fold_in(key, i), shape, kind, scale, dtype)
        for i, (path, (shape, kind, scale)) in enumerate(sorted(specs.items()))})


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
