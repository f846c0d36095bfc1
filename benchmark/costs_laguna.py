"""Operations and bytes a sparse-expert (``model_type: laguna``) configuration
needs, from shapes alone: what ``costs.py`` is for the ViT. A file of its own
because a ``model_config`` PR may edit no benchmark file (PERF.md section 7
names the fold).

Matmul operations only (2 per multiply-add). Attention scores and values are
counted INSIDE the mask, and the experts for the rows routed to the experts
held here: what a kernel computes beyond that (chunks it does not skip, tile
padding) is not credited.
"""

from __future__ import annotations

from benchmark.costs import tokens


def seen(n_tokens: int, window: int | None) -> int:
    """Sum over tokens t of the tokens t sees: min(t + 1, window)."""
    w = n_tokens if window is None else min(window, n_tokens)
    return w * (w + 1) // 2 + (n_tokens - w) * w


def window_of(config: dict, heads: int) -> int | None:
    """The window of the layers that have ``heads`` query heads (None: full
    attention): a launch's layer kind, read from its width."""
    i = config["num_attention_heads_per_layer"].index(heads)
    return (config["sliding_window"]
            if config["layer_types"][i] == "sliding_attention" else None)


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average."""
    return config["num_experts"] / config["source_values"]["num_experts"]


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip. Per token and layer: q and o
    2·D·H·hd, k and v 2·D·kv·hd, the gate D·H; the scores and values inside
    the mask 2·H·hd·(tokens seen); a dense MLP 3·D·F; a sparse one the router
    D·(its width), the shared expert 3·D·F_s and, of the num_experts_per_tok
    routed experts, the held share on average at 3·D·F_e each; plus the patch
    projection in and the head out."""
    n, d, hd = tokens(config), config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * hd
    c, p = config.get("in_chans", 3), config["patch_size"]
    routed = (config["num_experts_per_tok"] * held_share(config)
              * 3 * d * config["moe_intermediate_size"])
    macs = 2.0 * n * p * p * c * d
    for i in range(config["num_hidden_layers"]):
        heads = config["num_attention_heads_per_layer"][i]
        macs += n * (2 * d * heads * hd + 2 * d * kv + d * heads)
        macs += 2.0 * heads * hd * seen(n, window_of(config, heads))
        if config["mlp_layer_types"][i] == "dense":
            macs += n * 3 * d * config["intermediate_size"]
        else:
            macs += n * (d * config["source_values"]["num_experts"]
                         + 3 * d * config["shared_expert_intermediate_size"]
                         + routed)
    return 2.0 * macs


def flash_masked_fwd_cost(config: dict, images: int, heads: int) -> dict:
    """One launch of the masked attention forward over ``images`` images of
    ``heads`` query heads (hence its layer kind) at the TRUE token count: per
    image and head 4·hd·Σ_t min(t + 1, W) operations; q read and the context
    written once, k and v read once a K/V head, in the compute type."""
    n, hd = tokens(config), config["head_dim"]
    act = {"bfloat16": 2, "float32": 4}[config["precision"]]
    return {"flops": 4.0 * images * heads * hd * seen(n, window_of(config, heads)),
            "bytes": float(2 * images * n * hd * act
                           * (heads + config["num_key_value_heads"]))}


def moe_gmm_cost(config: dict, rows: float, k: int, n: int) -> dict:
    """One launch of the grouped expert product: ``rows`` rows really routed
    to the experts held, each ``(k,) @ (k, n)``; rows read and results written
    once, and the weights of the experts held read once, in the compute
    type."""
    act = {"bfloat16": 2, "float32": 4}[config["precision"]]
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((rows * (k + n) + config["num_experts"] * k * n)
                           * act)}
