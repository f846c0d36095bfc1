"""Seeded weights for a hybrid state-space configuration, drawn on the device
one layer at a time and rounded to the configuration's ``precision`` (2.87 B
parameters at Jamba2-3B's widths: 5.75 GB in bfloat16; a float32 copy of the
whole trunk never exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
(checked against ``model.init``'s structure and dtypes in
``tests/test_hybrid.py``); program and reference are given the same tree.
Distributions: linear maps normal, std 0.02 (the family's
``initializer_range``), ``out_proj`` and ``down_proj`` divided by
sqrt(2 x layers) as residual branches conventionally are; Mamba's published
initialisation for its own leaves: ``A_log = log(1..s)`` in every channel,
``D = 1``, ``dt_proj.bias`` the inverse softplus of a Delta drawn log-uniform
in [1e-3, 1e-1], the depthwise convolution and its bias uniform in
+-1/sqrt(d_conv) (torch's Conv1d default). The input and output stage as
``weights.py`` draws it. Departures, as in ``weights.py`` and for its reason
(a check on seeded weights should exercise what trained weights would): the
norms' scales are 1 + N(0, 0.02) instead of 1, the head's bias is drawn, and
``q_proj``/``k_proj`` are drawn with std 1.2/sqrt(hidden_size), which spreads
the attention logits near 1.4 where 0.02 would leave every softmax all but
uniform.

This file repeats ``weights.py``'s ``_make`` with three more kinds of leaf and
a storage type, because a ``model_config`` PR may edit no benchmark file
(PERF.md section 7 names the fold).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.hybrid import is_attention_layer
from benchmark.weights import _nest, seed_key

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "attn_layer_period",
    "attn_layer_offset", "mamba_expand", "mamba_d_state", "mamba_d_conv",
    "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias", "rms_norm_eps",
    "hidden_act", "num_experts", "sliding_window")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys."""
    return {k: config[k] for k in TRUNK_KEYS}


def outer_specs(config: dict) -> dict:
    """{path: (shape, kind, scale)} of the input and output stage."""
    d = int(config["hidden_size"])
    p = int(config["patch_size"])
    c = int(config.get("in_chans", 3))
    h, w = config["img_size"]
    fan_in = c * p * p
    return {
        ("patch_embed", "proj", "kernel"): ((fan_in, d), "uniform", 1 / math.sqrt(fan_in)),
        ("patch_embed", "proj", "bias"): ((d,), "uniform", 1 / math.sqrt(fan_in)),
        ("cls_token",): ((1, 1, d), "normal", 0.02),
        ("pos_embed",): ((1, (h // p) * (w // p) + 1, d), "normal", 0.02),
        ("time_embed", "embedding"): ((int(config["total_steps"]), d), "normal", 0.02),
        ("final_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("head", "kernel"): ((d, fan_in), "normal", 0.02),
        ("head", "bias"): ((fan_in,), "normal", 0.02),
    }


def layer_specs(trunk: dict, attention: bool) -> dict:
    """{path: (shape, kind, scale)} of one layer."""
    d, ff = trunk["hidden_size"], trunk["intermediate_size"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    specs = {
        ("input_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("pre_ff_layernorm", "scale"): ((d,), "one_plus", 0.02),
        ("feed_forward", "gate_proj", "kernel"): ((d, ff), "normal", 0.02),
        ("feed_forward", "up_proj", "kernel"): ((d, ff), "normal", 0.02),
        ("feed_forward", "down_proj", "kernel"): ((ff, d), "normal", branch),
    }
    if attention:
        kv = trunk["num_key_value_heads"] * (d // trunk["num_attention_heads"])
        qk = 1.2 / math.sqrt(d)
        specs.update({
            ("self_attn", "q_proj", "kernel"): ((d, d), "normal", qk),
            ("self_attn", "k_proj", "kernel"): ((d, kv), "normal", qk),
            ("self_attn", "v_proj", "kernel"): ((d, kv), "normal", 0.02),
            ("self_attn", "o_proj", "kernel"): ((d, d), "normal", branch),
        })
        return specs
    di = trunk["mamba_expand"] * d
    s, k, r = trunk["mamba_d_state"], trunk["mamba_d_conv"], trunk["mamba_dt_rank"]
    specs.update({
        ("mamba", "in_proj", "kernel"): ((d, 2 * di), "normal", 0.02),
        ("mamba", "conv1d_kernel"): ((k, di), "uniform", 1 / math.sqrt(k)),
        ("mamba", "x_proj", "kernel"): ((di, r + 2 * s), "normal", 0.02),
        ("mamba", "dt_layernorm", "scale"): ((r,), "one_plus", 0.02),
        ("mamba", "b_layernorm", "scale"): ((s,), "one_plus", 0.02),
        ("mamba", "c_layernorm", "scale"): ((s,), "one_plus", 0.02),
        ("mamba", "dt_proj", "kernel"): ((r, di), "normal", 0.02),
        ("mamba", "dt_proj", "bias"): ((di,), "dt_bias", (1e-3, 1e-1)),
        ("mamba", "A_log"): ((di, s), "a_log", 0.0),
        ("mamba", "D"): ((di,), "ones", 0.0),
        ("mamba", "out_proj", "kernel"): ((di, d), "normal", branch),
    })
    if trunk["mamba_conv_bias"]:
        specs[("mamba", "conv1d_bias")] = ((di,), "uniform", 1 / math.sqrt(k))
    if trunk["mamba_proj_bias"]:
        specs[("mamba", "in_proj", "bias")] = ((2 * di,), "normal", 0.02)
        specs[("mamba", "out_proj", "bias")] = ((d,), "normal", 0.02)
    return specs


def _leaf(key, shape, kind, scale):
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if kind == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "dt_bias":
        lo, hi = scale
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
    leaf = scale * jax.random.normal(key, shape, jnp.float32)
    return 1.0 + leaf if kind == "one_plus" else leaf


@partial(jax.jit, static_argnames=("specs", "dtype"))
def _make(key, specs, dtype):
    return _nest({
        path: _leaf(jax.random.fold_in(key, i), shape, kind, scale).astype(dtype)
        for i, (path, (shape, kind, scale)) in enumerate(specs)})


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    frozen = lambda specs: tuple(sorted(specs.items()))
    tree = _make(jax.random.fold_in(key, 0), frozen(outer_specs(config)), dtype)
    for i in range(trunk["num_hidden_layers"]):
        specs = layer_specs(trunk, is_attention_layer(trunk, i))
        tree[f"layers_{i}"] = _make(jax.random.fold_in(key, 1 + i),
                                    frozen(specs), dtype)
    return tree
