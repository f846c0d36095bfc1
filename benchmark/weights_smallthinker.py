"""Seeded weights for a ``model_type: smallthinker`` configuration (a router on
the layer's input, ReLU-gated experts with no shared one, rotary window layers
between position-free full ones), drawn on the device one leaf at a time and
rounded to the configuration's ``precision`` (3.19 B parameters in eight
layers at SmallThinker-21BA3B-Instruct's widths with all 64 experts of every
layer held: 6.38 GB in bfloat16; the largest leaf, a bank of 64 x 2,560 x 768,
is 0.25 GB and no float32 copy of a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_smallthinker.py``); program and reference are given the
same tree. Distributions as ``weights_laguna.py``: linear maps normal, std
0.02; ``o_proj`` and every ``down_proj`` divided by sqrt(2 x layers) as
residual branches conventionally are; the norms' scales 1 + N(0, 0.02);
``q_proj``/``k_proj`` std 1.2/sqrt(hidden_size), which spreads the attention
logits near 1.4 in both layer kinds (the default rotary has no factor) where
0.02 would leave every softmax over 4,096 or 16,130 keys all but uniform. The
router is drawn with std 0.03, not 0.02: what it reads is the residual stream
as it arrives, NOT normed (rms about two thirds here), so 0.03 spreads its
logits near 1 as 0.02 does over the unit-rms input the other stacks' routers
read: routing that is neither uniform nor collapsed.

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

#: the published config.json's keys that size the trunk, and ``model_type``
TRUNK_KEYS = (
    "model_type", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
    "rope_scaling", "rope_layout", "sliding_window_layout",
    "sliding_window_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_ffn_hidden_size",
    "moe_primary_router_apply_softmax", "norm_topk_prob")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``moe_num_primary_experts`` is the count held here, and the share's other
    two numbers go beside it: the router's published width (the held count
    where the key is not ``reduced``: every expert held) and the first expert
    held."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["moe_num_primary_experts_routed"] = config.get(
        "source_values", {}).get("moe_num_primary_experts",
                                 config["moe_num_primary_experts"])
    trunk["experts_held_from"] = config.get("experts_held_from", 0)
    return trunk


def layer_specs(trunk: dict) -> dict:
    """{path: (shape, kind, scale)} of a layer: every layer has the same
    leaves, the two kinds differ in what attention does with them."""
    d, hd = trunk["hidden_size"], trunk["head_dim"]
    heads, kv = trunk["num_attention_heads"], trunk["num_key_value_heads"]
    held, width = trunk["moe_num_primary_experts"], trunk["moe_ffn_hidden_size"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    qk = 1.2 / math.sqrt(d)
    return {
        ("input_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("post_attention_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("self_attn", "q_proj", "kernel"): ((d, heads * hd), "normal", qk),
        ("self_attn", "k_proj", "kernel"): ((d, kv * hd), "normal", qk),
        ("self_attn", "v_proj", "kernel"): ((d, kv * hd), "normal", 0.02),
        ("self_attn", "o_proj", "kernel"): ((heads * hd, d), "normal", branch),
        ("mlp", "router"): ((d, trunk["moe_num_primary_experts_routed"]),
                            "normal", 0.03),
        ("mlp", "gate_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "up_proj"): ((held, d, width), "normal", 0.02),
        ("mlp", "down_proj"): ((held, width, d), "normal", branch),
    }


def parameters(trunk: dict) -> int:
    """How many numbers a layer holds."""
    return sum(math.prod(shape) for shape, _, _ in layer_specs(trunk).values())


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    tree = _tree(jax.random.fold_in(key, 0), outer_specs(config), dtype)
    for i in range(trunk["num_hidden_layers"]):
        tree[f"layers_{i}"] = _tree(jax.random.fold_in(key, 1 + i),
                                    layer_specs(trunk), dtype)
    return tree
