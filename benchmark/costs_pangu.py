"""Operations and bytes a dense-causal latent-attention, sigmoid-routed
(``model_type: pangu_ultra_moe``) configuration needs, from shapes alone:
what ``costs.py`` is for the ViT. A file of its own because a
``model_config`` PR may edit no benchmark file (PERF.md section 7 names the
fold).

Matmul operations only (2 per multiply-add). Attention is counted for the
causal pairs at the head sizes the model has, ``nope + rot`` dims a score and
``vd`` a value: what a kernel multiplies beyond that (the 64 rotated dims on
a 128-deep pass, the diagonal chunks' masked half) is not credited. The
experts are counted for the rows routed to the experts held here.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.costs_glm import causal_pairs

_ACT = {"bfloat16": 2, "float32": 4}


def layer_kinds(config: dict) -> list:
    """``dense`` | ``sparse`` of each layer of the slice: published layer
    ``layers_from + i`` is dense below ``first_k_dense_replace``."""
    first = config.get("layers_from", 0)
    return ["dense" if first + i < config["first_k_dense_replace"] else "sparse"
            for i in range(config["num_hidden_layers"])]


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average."""
    return (config["n_routed_experts"]
            / config["source_values"]["n_routed_experts"])


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip. Per token and layer: the latent
    projections D·r_q + r_q·H·(nope + rot) + D·(r_kv + rot) + r_kv·H·(nope +
    vd) + H·vd·D; attention H·(nope + rot + vd) a causal pair; a dense MLP
    3·D·F; a sparse one the router D·(its width), the shared expert and, of
    the num_experts_per_tok routed experts, the held share on average, 3·D·F_e
    each; plus the patch projection in and the head out."""
    n, d, heads = tokens(config), config["hidden_size"], config["num_attention_heads"]
    nope, rot, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    r_q, r_kv = config["q_lora_rank"], config["kv_lora_rank"]
    width = config["moe_intermediate_size"]
    c, p = config.get("in_chans", 3), config["patch_size"]
    macs = 2.0 * n * p * p * c * d
    for kind in layer_kinds(config):
        macs += n * (d * r_q + r_q * heads * (nope + rot) + d * (r_kv + rot)
                     + r_kv * heads * (nope + vd) + heads * vd * d)
        macs += heads * (nope + rot + vd) * causal_pairs(n)
        if kind == "dense":
            macs += n * 3 * d * config["intermediate_size"]
        else:
            macs += n * (d * config["source_values"]["n_routed_experts"]
                         + 3 * d * width * config["n_shared_experts"]
                         + config["num_experts_per_tok"] * held_share(config)
                         * 3 * d * width)
    return 2.0 * macs


def flash_latent_fwd_cost(config: dict, images: int) -> dict:
    """One launch of the latent attention forward, ``images`` images of all
    the heads at the TRUE token count: 2·(nope + rot + vd) operations a head
    and causal pair; q (both parts), k_nope and v read and the context
    written once, and the ONE rotated key part of all the heads once, in the
    compute type."""
    n, heads = tokens(config), config["num_attention_heads"]
    nope, rot, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return {"flops": 2.0 * images * heads * (nope + rot + vd) * causal_pairs(n),
            "bytes": float(images * n * _ACT[config["precision"]]
                           * (heads * (2 * nope + rot + 2 * vd) + rot))}
