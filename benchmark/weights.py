"""Seeded weights for a configuration, made on the device in one jitted call.

The tree has the leaves and names the program's ``DiffusionViT`` declares
(checked against ``model.init``'s structure in ``tests/``), so the program and
the plain reference are given the same float32 tree and neither takes it from
the other. Distributions follow upstream's initialisation (normal, std 0.02;
the patch projection uniform in +-1/sqrt(fan_in)) with two departures, both so
that a check on seeded weights exercises what trained weights would: biases
and the LayerNorm offsets are drawn (std 0.02) instead of zero, and the qkv
kernels are drawn with std 1.2/sqrt(D), which gives attention logits a
spread near 1.4 instead of 0.15 -- with upstream's 0.02 every softmax is all
but uniform and attention cannot be told from a mean.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def arch_of(config: dict) -> dict:
    """The reference forward's keyword sizes from a configuration file."""
    return {"patch_size": int(config["patch_size"]), "depth": int(config["depth"]),
            "num_heads": int(config["num_heads"])}


def leaf_specs(config: dict) -> dict:
    """{path tuple: (shape, kind, scale)} for every parameter leaf."""
    d = int(config["embed_dim"])
    p = int(config["patch_size"])
    c = int(config.get("in_chans", 3))
    h, w = config["img_size"]
    n = (h // p) * (w // p)
    hidden = int(d * float(config.get("mlp_ratio", 1.0)))
    fan_in = c * p * p
    specs = {
        ("patch_embed", "proj", "kernel"): ((fan_in, d), "uniform", 1 / math.sqrt(fan_in)),
        ("patch_embed", "proj", "bias"): ((d,), "uniform", 1 / math.sqrt(fan_in)),
        ("cls_token",): ((1, 1, d), "normal", 0.02),
        ("pos_embed",): ((1, n + 1, d), "normal", 0.02),
        ("time_embed", "embedding"): ((int(config["total_steps"]), d), "normal", 0.02),
        ("norm", "scale"): ((d,), "one_plus", 0.02),
        ("norm", "bias"): ((d,), "normal", 0.02),
        ("head", "kernel"): ((d, c * p * p), "normal", 0.02),
        ("head", "bias"): ((c * p * p,), "normal", 0.02),
    }
    for i in range(int(config["depth"])):
        b = f"blocks_{i}"
        specs.update({
            (b, "norm1", "scale"): ((d,), "one_plus", 0.02),
            (b, "norm1", "bias"): ((d,), "normal", 0.02),
            (b, "norm2", "scale"): ((d,), "one_plus", 0.02),
            (b, "norm2", "bias"): ((d,), "normal", 0.02),
            (b, "attn", "qkv", "kernel"): ((d, 3 * d), "normal", 1.2 / math.sqrt(d)),
            (b, "attn", "qkv", "bias"): ((3 * d,), "normal", 0.02),
            (b, "attn", "proj", "kernel"): ((d, d), "normal", 0.02),
            (b, "attn", "proj", "bias"): ((d,), "normal", 0.02),
            (b, "mlp", "fc1", "kernel"): ((d, hidden), "normal", 0.02),
            (b, "mlp", "fc1", "bias"): ((hidden,), "normal", 0.02),
            (b, "mlp", "fc2", "kernel"): ((hidden, d), "normal", 0.02),
            (b, "mlp", "fc2", "bias"): ((d,), "normal", 0.02),
        })
    return specs


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole-number seed (the driver's pass 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


@partial(jax.jit, static_argnames=("specs",))
def _make(key, specs):
    flat = {}
    for i, (path, (shape, kind, scale)) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        if kind == "uniform":
            leaf = jax.random.uniform(k, shape, jnp.float32, -scale, scale)
        else:
            leaf = scale * jax.random.normal(k, shape, jnp.float32)
            if kind == "one_plus":
                leaf = 1.0 + leaf
        flat[path] = leaf
    return _nest(flat)


def make(config: dict, seed: int) -> dict:
    """The float32 parameter tree for ``config`` from ``seed``."""
    specs = tuple(sorted(leaf_specs(config).items()))
    return _make(seed_key(seed), specs)
