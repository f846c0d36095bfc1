"""Operations and bytes a Mamba-2 / attention / latent-expert (``model_type:
nemotron_h``) configuration needs, from shapes alone: what ``costs.py`` is for
the ViT. A file of its own because a ``model_config`` PR may edit no benchmark
file (PERF.md section 7 names the fold).

Matmul operations only (2 per multiply-add). The scan is counted in its
chunked form at the TRUE token count — what a launch multiplies beyond that
(a head of 64 channels on a 128-wide pass, the masked half of a chunk's
``Q x Q`` scores, the tokens past the sequence in the last chunk) is not
credited. Attention is counted for the causal pairs; the experts for the rows
routed to the experts held here.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.costs_glm import causal_pairs

_ACT = {"bfloat16": 2, "float32": 4}


def layer_kinds(config: dict) -> list:
    """``M`` | ``*`` | ``E`` of each layer of the slice."""
    return list(config["hybrid_override_pattern"][:config["num_hidden_layers"]])


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average."""
    return (config["n_routed_experts"]
            / config["source_values"]["n_routed_experts"])


def scan_flops_a_token(config: dict) -> float:
    """One token of one Mamba-2 layer's scan over chunks of Q: a head's ``(C
    Bᵀ ∘ Λ) X̃`` is 2·Q·P, its ``C Sᵀ`` and its state update 2·N·P each; a
    group's ``C Bᵀ`` 2·Q·N, once for all its heads."""
    H, P, N, G, Q = (config["mamba_num_heads"], config["mamba_head_dim"],
                     config["ssm_state_size"], config["n_groups"],
                     config["chunk_size"])
    return float(H * (2 * Q * P + 4 * N * P) + G * 2 * Q * N)


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip. Per token: an ``M`` layer's two
    projections D·(2d + 2GN + H) + d·D and its scan; a ``*`` layer's four
    projections and heads·2·head_dim a causal pair; an ``E`` layer's router
    D·(its width), latent projections 2·D·l, shared expert 2·D·F_s and, of
    the num_experts_per_tok routed experts, the held share on average, 2·l·F
    each; plus the patch projection in and the head out."""
    n, d = tokens(config), config["hidden_size"]
    H, P, N, G = (config["mamba_num_heads"], config["mamba_head_dim"],
                  config["ssm_state_size"], config["n_groups"])
    inner = H * P
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    latent, width = config["moe_latent_size"], config["moe_intermediate_size"]
    c, p = config.get("in_chans", 3), config["patch_size"]
    macs = 2.0 * n * p * p * c * d
    for kind in layer_kinds(config):
        if kind == "M":
            macs += n * (d * (2 * inner + 2 * G * N + H) + inner * d)
            macs += n * scan_flops_a_token(config) / 2
        elif kind == "*":
            macs += n * d * hd * (2 * heads + 2 * kv)
            macs += heads * 2 * hd * causal_pairs(n)
        else:
            macs += n * (d * config["source_values"]["n_routed_experts"]
                         + 2 * d * latent
                         + 2 * d * config["moe_shared_expert_intermediate_size"]
                         + config["num_experts_per_tok"] * held_share(config)
                         * 2 * latent * width)
    return 2.0 * macs


def ssd_cost(config: dict, images: int, gated: bool = False) -> dict:
    """One launch of the chunked scan over ``images`` images at the TRUE
    token count: :func:`scan_flops_a_token` a token; x read and y written
    once at H·P columns, B and C once at G·N, in the compute type, and Δ once
    (float32, a head); with ``gated``, a launch that also applies the gate,
    z once too."""
    n = tokens(config)
    H, P, N, G = (config["mamba_num_heads"], config["mamba_head_dim"],
                  config["ssm_state_size"], config["n_groups"])
    wide = (3 if gated else 2) * H * P + 2 * G * N
    return {"flops": images * n * scan_flops_a_token(config),
            "bytes": float(images * n * (wide * _ACT[config["precision"]]
                                         + H * 4))}


def moe_gmm_cost(config: dict, rows: float, k: int, n: int) -> dict:
    """One launch of the grouped product ``(rows, k) @ (held, k, n)``, ONE
    product a launch (the experts are ungated): 2·rows·k·n operations; the
    rows read and the result written once, every held expert's ``(k, n)``
    weight once, in the compute type."""
    act = _ACT[config["precision"]]
    return {"flops": 2.0 * rows * k * n,
            "bytes": float(act * (rows * (k + n)
                                  + config["n_routed_experts"] * k * n))}
