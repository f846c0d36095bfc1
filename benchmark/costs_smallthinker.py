"""Operations and bytes a ``model_type: smallthinker`` configuration (a router
on the layer's input, ReLU-gated experts with no shared one, rotary window
layers between position-free full ones) needs, from shapes alone: what
``costs.py`` is for the ViT. A file of its own because a ``model_config`` PR
may edit no benchmark file (PERF.md section 7 names the fold).

Matmul operations only (2 per multiply-add). Attention scores and values are
counted INSIDE the mask at the true token count, and the experts for the rows
routed to the experts held here (all of them where every expert is held):
what a kernel computes beyond that (chunks it does not skip, the masked half
of a diagonal chunk, tile padding, K/V chunks fetched once a query head where
seven heads share them) is not credited. The ReLU's zeros are not discounted:
the products are dense.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.costs_laguna import seen

_ACT = {"bfloat16": 2, "float32": 4}


def window_of(config: dict, i: int) -> int | None:
    """The window of layer i (None: full attention)."""
    return (config["sliding_window_size"]
            if config["sliding_window_layout"][i] else None)


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average: 1
    where the configuration holds every expert."""
    routed = config.get("source_values", {}).get(
        "moe_num_primary_experts", config["moe_num_primary_experts"])
    return config["moe_num_primary_experts"] / routed


def forward_parts(config: dict) -> dict:
    """One image, one forward, on this chip, in operations by part. Per token
    and layer: ``projections`` q and o 2·D·H·hd, k and v 2·D·kv·hd;
    ``router`` D·(its width); ``experts`` the held share of the
    moe_num_active_primary_experts routed at 3·D·F each; per layer
    ``attn_window`` / ``attn_full`` 2·H·hd·(tokens seen inside the mask);
    ``stage`` the patch projection in and the head out."""
    n, d, hd = tokens(config), config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    c, p = config.get("in_chans", 3), config["patch_size"]
    depth = config["num_hidden_layers"]
    routed = config["moe_num_primary_experts"] / held_share(config)
    macs = {
        "projections": depth * n * 2.0 * d * (heads + kv) * hd,
        "router": depth * n * float(d) * routed,
        "experts": (depth * n * config["moe_num_active_primary_experts"]
                    * held_share(config) * 3.0 * d
                    * config["moe_ffn_hidden_size"]),
        "attn_window": 0.0, "attn_full": 0.0,
        "stage": 2.0 * n * p * p * c * d,
    }
    for i in range(depth):
        window = window_of(config, i)
        macs["attn_full" if window is None else "attn_window"] += (
            2.0 * heads * hd * seen(n, window))
    return {part: 2.0 * m for part, m in macs.items()}


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip: the sum of
    :func:`forward_parts`."""
    return sum(forward_parts(config).values())


def flash_masked_fwd_cost(config: dict, images: int, windowed: bool) -> dict:
    """One launch of the masked attention forward over ``images`` images, a
    window layer's (``windowed``) or a full layer's, at the TRUE token count:
    per image and head 4·hd·Σ_t min(t + 1, W) operations; q read and the
    context written once, k and v read once a K/V head, in the compute type
    (the cos and sin tables of a launch that turns q, 2 x 512 bytes a token,
    are not credited)."""
    n, hd = tokens(config), config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    window = config["sliding_window_size"] if windowed else None
    return {"flops": 4.0 * images * heads * hd * seen(n, window),
            "bytes": float(2 * images * n * hd * _ACT[config["precision"]]
                           * (heads + kv))}


def moe_gmm_cost(config: dict, rows: float, k: int, n: int,
                 products: int = 1) -> dict:
    """One launch of the grouped expert product: ``rows`` rows really routed
    to the experts held, each ``(k,) @ (k, n)``, ``products`` times over (2:
    the gate-up launch, which multiplies the rows it read once by two banks);
    rows read and results written once, and each bank of the experts held
    read once, in the compute type."""
    return {"flops": 2.0 * products * rows * k * n,
            "bytes": float((rows * (k + n) + products
                            * config["moe_num_primary_experts"] * k * n)
                           * _ACT[config["precision"]])}
