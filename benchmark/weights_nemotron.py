"""Seeded weights for a Mamba-2 / attention / latent-expert (``model_type:
nemotron_h``) configuration, drawn on the device one leaf at a time and
rounded to the configuration's ``precision`` (4.38 B parameters in eleven
layers at NVIDIA-Nemotron-3-Super-120B-A12B's widths with 128 of 512 experts
held in 5 expert layers: 8.76 GB in bfloat16; the largest leaf, a bank of 128
x 1,024 x 2,688, is 0.7 GB and no float32 copy of a layer ever exists).

The tree has the leaves and names the program's ``HybridDenoiser`` declares
for this stack (checked against ``model.init``'s structure and dtypes in
``benchmark/tests/test_nemotron.py``); program and reference are given the
same tree, every weight's columns in the published order (``in_proj``: ``[z,
xBC, dt]``; the convolution: ``[x, B, C]``). Distributions as
``weights_glm.py``: linear maps normal, std 0.02; ``out_proj``, ``o_proj``,
``fc2_latent_proj`` (the routed experts' way back to the stream; their own
``down_proj`` ends in the latent and stays at 0.02) and the shared expert's
``down_proj`` divided by sqrt(2 x layers) as residual branches conventionally
are; ``q_proj``/``k_proj`` std
1.2/sqrt(hidden_size), which spreads the attention logits near 1.4; the
norms' scales 1 + N(0, 0.02); the router std 0.02 (logits of spread ~1.3 over
unit-rms input), ``e_score_correction_bias`` N(0, 0.005) as ``weights_glm.py``
draws GLM's and for its reason (the chosen scores lie where the sigmoid is
flat). Mamba-2's published initialisation for its own leaves: ``A_log`` the
log of a rate uniform in [1, 16], one a head; ``D = 1``; ``dt_bias`` the
inverse softplus of a Delta drawn log-uniform in [``time_step_min``,
``time_step_max``] (no draw falls under ``time_step_floor``); the depthwise
convolution and its bias uniform in +-1/sqrt(conv_kernel) (torch's Conv1d
default).

**Every ``down_proj`` that follows a squared ReLU is CENTRED over its input**
(the shared expert's and the routed experts': each output column's mean over
the hidden units is taken out, a change of 1/sqrt(width) of a weight's std). A
squared ReLU's hidden units are all positive with nearly one mean (0.5 sigma^2
each), so an uncentred ``W_down`` maps that mean to ONE vector, added to every
token alike: 0.26 of the 0.63 of rms an expert layer adds. Every token's
router logits then share a component, the experts' loads come out
heavy-tailed (the busiest expert at 5 times the mean by the fourth expert
layer) and the held quarter of the experts saw 22 to 27 % of a layer's
assignments by the layer and the seed, which showed in ``moe_gmm``'s time and
moved the cell's rate by 0.47 % over six seeds (PERF.md section 6, PR 41).
A trained router is balanced — its selection bias exists for that — and a
trained ``W_down`` is free to cancel a constant: centred, the busiest expert
is at 1.5 times the mean and the held share at 0.248 +- 0.002.

This file repeats ``weights_glm.py``'s ``make`` with this stack's leaves,
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key
from benchmark.weights_hybrid import DTYPES, outer_specs  # noqa: F401
from benchmark.weights_laguna import _tree

#: the published config.json's keys that size the trunk
TRUNK_KEYS = (
    "model_type", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
    "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
    "use_conv_bias", "mamba_hidden_act", "mamba_proj_bias", "time_step_min",
    "time_step_max", "time_step_floor", "num_attention_heads",
    "num_key_value_heads", "head_dim", "attention_bias", "sliding_window",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "moe_intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "mlp_hidden_act", "mlp_bias",
    "use_bias", "norm_topk_prob", "routed_scaling_factor", "n_group",
    "topk_group", "layers_from", "experts_held_from")


def trunk_of(config: dict) -> dict:
    """The trunk's sizes from a configuration file, under the source's keys;
    ``n_routed_experts`` is the count held here, and the router's published
    width goes beside it."""
    trunk = {k: config[k] for k in TRUNK_KEYS}
    trunk["n_experts_routed"] = config["source_values"]["n_routed_experts"]
    return trunk


def layer_specs(trunk: dict, i: int) -> dict:
    """{path: (shape, kind, scale)} of layer i of the slice, whose kind is
    ``hybrid_override_pattern[i]``."""
    d = trunk["hidden_size"]
    branch = 0.02 / math.sqrt(2 * trunk["num_hidden_layers"])
    mixer = lambda *path: ("mixer",) + path
    specs = {("norm", "scale"): ((d,), "one_plus", 0.02)}
    kind = trunk["hybrid_override_pattern"][i]
    if kind == "M":
        heads, k = trunk["mamba_num_heads"], trunk["conv_kernel"]
        inner = heads * trunk["mamba_head_dim"]
        shared = 2 * trunk["n_groups"] * trunk["ssm_state_size"]  # B and C
        specs.update({
            mixer("in_proj", "kernel"): ((d, 2 * inner + shared + heads),
                                         "normal", 0.02),
            mixer("conv1d_kernel"): ((k, inner + shared), "uniform",
                                     1 / math.sqrt(k)),
            mixer("dt_bias"): ((heads,), "dt_bias", (
                max(trunk["time_step_min"], trunk["time_step_floor"]),
                trunk["time_step_max"])),
            # u in (-1, 1); ``make`` turns it into log(8.5 + 7.5 u)
            mixer("A_log"): ((heads,), "uniform", 1.0),
            mixer("D"): ((heads,), "ones", 0.0),
            mixer("norm", "scale"): ((inner,), "one_plus", 0.02),
            mixer("out_proj", "kernel"): ((inner, d), "normal", branch),
        })
        if trunk["use_conv_bias"]:
            specs[mixer("conv1d_bias")] = ((inner + shared,), "uniform",
                                           1 / math.sqrt(k))
    elif kind == "*":
        heads, kv, hd = (trunk["num_attention_heads"],
                         trunk["num_key_value_heads"], trunk["head_dim"])
        qk = 1.2 / math.sqrt(d)
        specs.update({
            mixer("q_proj", "kernel"): ((d, heads * hd), "normal", qk),
            mixer("k_proj", "kernel"): ((d, kv * hd), "normal", qk),
            mixer("v_proj", "kernel"): ((d, kv * hd), "normal", 0.02),
            mixer("o_proj", "kernel"): ((heads * hd, d), "normal", branch),
        })
    elif kind == "E":
        held, routed = trunk["n_routed_experts"], trunk["n_experts_routed"]
        latent, width = trunk["moe_latent_size"], trunk["moe_intermediate_size"]
        shared = trunk["moe_shared_expert_intermediate_size"]
        specs.update({
            mixer("router"): ((d, routed), "normal", 0.02),
            mixer("e_score_correction_bias"): ((routed,), "normal", 0.005),
            mixer("fc1_latent_proj", "kernel"): ((d, latent), "normal", 0.02),
            mixer("fc2_latent_proj", "kernel"): ((latent, d), "normal", branch),
            mixer("up_proj"): ((held, latent, width), "normal", 0.02),
            mixer("down_proj"): ((held, width, latent), "normal", 0.02),
            mixer("shared_expert", "up_proj", "kernel"): ((d, shared),
                                                          "normal", 0.02),
            mixer("shared_expert", "down_proj", "kernel"): ((shared, d),
                                                            "normal", branch),
        })
    else:
        raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}: 'M', '*' "
                         "and 'E' have weights here")
    return specs


@jax.jit
def _centred(w):
    """``w (..., hidden, out)`` with every output column's mean over the
    hidden units taken out, in float32, back in ``w``'s dtype."""
    wf = w.astype(jnp.float32)
    return (wf - wf.mean(-2, keepdims=True)).astype(w.dtype)


def make(config: dict, seed: int) -> dict:
    """The parameter tree for ``config`` from ``seed``, in its precision."""
    dtype = DTYPES[config["precision"]]
    trunk = trunk_of(config)
    key = seed_key(seed)
    tree = _tree(jax.random.fold_in(key, 0), outer_specs(config), dtype)
    for i in range(trunk["num_hidden_layers"]):
        layer = _tree(jax.random.fold_in(key, 1 + i), layer_specs(trunk, i),
                      dtype)
        mixer = layer["mixer"]
        if "A_log" in mixer:  # a rate uniform in [1, 16], its log
            u = mixer["A_log"].astype(jnp.float32)
            mixer["A_log"] = jnp.log(8.5 + 7.5 * u).astype(dtype)
        if "router" in mixer:  # see the docstring: what follows relu(.)^2
            mixer["down_proj"] = _centred(mixer["down_proj"])
            shared = mixer["shared_expert"]["down_proj"]
            shared["kernel"] = _centred(shared["kernel"])
        tree[f"layers_{i}"] = layer
    return tree
