"""Operations and bytes the algorithms need, from shapes alone. Copied in
arithmetic from the program's ``utils/flops.py`` (which stays where it is for
the program's own use) so that no later change can move the yardstick.

Matmul operations only (2 per multiply-add); elementwise, softmax and
LayerNorm work is not counted, and neither is anything recomputed.
"""

from __future__ import annotations


def tokens(config: dict) -> int:
    h, w = config["img_size"]
    p = config["patch_size"]
    return (h // p) * (w // p) + 1  # + the class token


def vit_forward_flops(config: dict) -> float:
    """One image, one forward. Per block (width D, tokens N): qkv 3ND^2,
    scores and values 2N^2D, proj ND^2, MLP 2ND^2*ratio; plus the patch
    projection in and the head out, N*p^2*C*D each."""
    n, d = tokens(config), config["embed_dim"]
    ratio = float(config.get("mlp_ratio", 1.0))
    c, p = config.get("in_chans", 3), config["patch_size"]
    per_block = 3 * n * d * d + 2 * n * n * d + n * d * d + 2 * n * d * d * ratio
    return 2.0 * (config["depth"] * per_block + 2 * n * p * p * c * d)


def train_step_flops(config: dict, batch: int) -> float:
    """Forward and backward: three forwards' worth of matmuls per image."""
    return 3.0 * batch * vit_forward_flops(config)


def flash_fwd_cost(config: dict, batch: int) -> dict:
    """One call of the attention forward kernel over ``batch`` images: the
    score and value GEMMs at the true sequence length and head size (the
    kernel's padding is its own business), q, k, v read and the context
    written once in the compute type."""
    n, d = tokens(config), config["embed_dim"]
    act = {"bfloat16": 2, "float32": 4}[config["precision"]]
    return {"flops": 2.0 * 2 * batch * n * n * d,
            "bytes": 4.0 * batch * n * d * act}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    compute = cost["flops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
