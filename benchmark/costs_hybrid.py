"""Operations and bytes a hybrid state-space configuration needs, from shapes
alone: what ``costs.py`` is for the ViT. A file of its own because a
``model_config`` PR may edit no benchmark file (PERF.md section 7 names the
fold).

Matmul operations only (2 per multiply-add) in ``hybrid_forward_flops``;
the scan's own count is ``ssm_scan_cost``'s.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.reference.hybrid import is_attention_layer


def mamba_layers(config: dict) -> int:
    return sum(not is_attention_layer(config, i)
               for i in range(config["num_hidden_layers"]))


def hybrid_forward_flops(config: dict) -> float:
    """One image, one forward. Per token: every layer's gated MLP 3·D·F; a
    Mamba mixer D·2d + d·(r + 2s) + r·d + d·D; an attention mixer 2·D² for q
    and o, 2·D·kv for k and v, and the causal scores and values 2·N·D at full
    (unmasked) size, which is what the dense XLA attention computes; plus the
    patch projection in and the head out."""
    n, d = tokens(config), config["hidden_size"]
    ff, layers = config["intermediate_size"], config["num_hidden_layers"]
    di = config["mamba_expand"] * d
    s, r = config["mamba_d_state"], config["mamba_dt_rank"]
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    c, p = config.get("in_chans", 3), config["patch_size"]
    mamba = d * 2 * di + di * (r + 2 * s) + r * di + di * d
    attn = 2 * d * d + 2 * d * kv + 2 * n * d
    n_mamba = mamba_layers(config)
    per_token = (layers * 3 * d * ff + n_mamba * mamba
                 + (layers - n_mamba) * attn + 2 * p * p * c * d)
    return 2.0 * n * per_token


def ssm_scan_cost(config: dict, images: int) -> dict:
    """One launch of the selective-scan kernel (one layer) over ``images``
    images at the TRUE token count: u, Delta and z read and y written once,
    d channels each, plus B and C, s states each, in the compute type; per
    token, channel and state 9 operations (Delta·A, exp, ·h, Delta·u, ·B, +,
    ·C, +, and the gate's share). Padding the kernel does for itself is not
    credited."""
    n = tokens(config)
    d = config["mamba_expand"] * config["hidden_size"]
    s = config["mamba_d_state"]
    act = {"bfloat16": 2, "float32": 4}[config["precision"]]
    return {"flops": 9.0 * images * n * d * s,
            "bytes": float(images * n * (4 * d + 2 * s) * act)}
