"""Operations and bytes a delta-attention / position-free latent-attention /
sigmoid-routed (``model_type: kimi_linear``) configuration needs, from shapes
alone: what ``costs.py`` is for the ViT. A file of its own because a
``model_config`` PR may edit no benchmark file (PERF.md section 7 names the
fold).

Matmul operations only (2 per multiply-add). The delta-rule scan is counted
in its chunked form at the TRUE token count and at the chunk length the
launch chose (``SCAN_CHUNK``; ``benchmark/tests/test_kimi.py`` holds it to
``ops.kda.CHUNK``) — what a launch multiplies beyond that (the masked half of
a chunk's pairs, the tokens past the sequence in the last chunk, its
float32 passes) is not credited. Latent attention is counted for the causal
pairs at the head sizes the model has; the experts for the rows routed to the
experts held here.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.costs_glm import causal_pairs

_ACT = {"bfloat16": 2, "float32": 4}
#: tokens a chunk of the ``kda_chunk`` launch
SCAN_CHUNK = 128


def layer_kinds(config: dict) -> list:
    """``(mixer, ffn)`` of each layer of the slice: ``kda`` | ``mla`` by the
    published number ``layers_from + i + 1`` in ``linear_attn_config``'s
    lists, ``dense`` | ``sparse`` by ``first_k_dense_replace``."""
    first, lists = config.get("layers_from", 0), config["linear_attn_config"]
    return [("kda" if first + i + 1 in lists["kda_layers"] else "mla",
             "dense" if first + i < config["first_k_dense_replace"]
             else "sparse") for i in range(config["num_hidden_layers"])]


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average."""
    return config["num_experts"] / config["source_values"]["num_experts"]


def scan_flops_a_token(config: dict) -> float:
    """One token of one delta-attention layer's scan over chunks of C, a
    head: the two pair products' visible halves C·d each, the triangular
    inverse C², its product with the corrected values and the pairs' with the
    result C·d each, the three products with the d x d state 2·d² each
    (ISSUE 45 counted a fifth C·d: the form that applies the inverse to keys
    and values apart; this launch applies it once)."""
    lin = config["linear_attn_config"]
    d, C = lin["head_dim"], SCAN_CHUNK
    return float(lin["num_heads"] * (4 * C * d + 6 * d * d + C * C))


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip. Per token: a delta layer's three
    projections and its way back 4·D·P, the two rank-d paths 2·(D·d + d·P),
    beta D·H, and its scan; a latent layer's D·H·(nope + rot) + D·(r + rot) +
    r·H·(nope + vd) + H·vd·D and H·(nope + rot + vd) a causal pair; a dense
    MLP 3·D·F; a sparse one the router D·(its width), the shared expert and,
    of the num_experts_per_token routed experts, the held share on average,
    3·D·F_e each; plus the patch projection in and the head out."""
    n, d = tokens(config), config["hidden_size"]
    lin = config["linear_attn_config"]
    hd, inner = lin["head_dim"], lin["num_heads"] * lin["head_dim"]
    heads, nope, rot, vd = (config["num_attention_heads"],
                            config["qk_nope_head_dim"],
                            config["qk_rope_head_dim"], config["v_head_dim"])
    rank, width = config["kv_lora_rank"], config["moe_intermediate_size"]
    c, p = config.get("in_chans", 3), config["patch_size"]
    macs = 2.0 * n * p * p * c * d
    for mixer, ffn in layer_kinds(config):
        if mixer == "kda":
            macs += n * (4 * d * inner + 2 * (d * hd + hd * inner)
                         + d * lin["num_heads"])
            macs += n * scan_flops_a_token(config) / 2
        else:
            macs += n * (d * heads * (nope + rot) + d * (rank + rot)
                         + rank * heads * (nope + vd) + heads * vd * d)
            macs += heads * (nope + rot + vd) * causal_pairs(n)
        if ffn == "dense":
            macs += n * 3 * d * config["intermediate_size"]
        else:
            macs += n * (d * config["source_values"]["num_experts"]
                         + 3 * d * width * config["num_shared_experts"]
                         + config["num_experts_per_token"] * held_share(config)
                         * 3 * d * width)
    return 2.0 * macs


def kda_cost(config: dict, images: int, gated: bool = False) -> dict:
    """One launch of the chunked delta-rule scan over ``images`` images at the
    TRUE token count: :func:`scan_flops_a_token` a token; q, k and v read and
    o written once at H·d columns in the compute type, g once in float32 (the
    type the launch takes it in), beta once (float32, a head); with ``gated``,
    a launch that also applies the output gate, the gate once too."""
    n, lin = tokens(config), config["linear_attn_config"]
    heads, inner = lin["num_heads"], lin["num_heads"] * lin["head_dim"]
    wide = (5 if gated else 4) * inner * _ACT[config["precision"]] + inner * 4
    return {"flops": images * n * scan_flops_a_token(config),
            "bytes": float(images * n * (wide + heads * 4))}
