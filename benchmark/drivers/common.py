"""What the drivers share: the program's model built from a configuration
file, and the comparison numbers."""

from __future__ import annotations

import numpy as np


def build_model(config: dict):
    """The program's ``DiffusionViT`` at the configuration's sizes and
    stated precision."""
    import jax.numpy as jnp

    from ddim_cold_tpu.models import DiffusionViT

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["precision"]]
    return DiffusionViT(
        img_size=tuple(config["img_size"]), patch_size=config["patch_size"],
        in_chans=config.get("in_chans", 3), embed_dim=config["embed_dim"],
        depth=config["depth"], num_heads=config["num_heads"],
        mlp_ratio=config.get("mlp_ratio", 1.0),
        total_steps=config["total_steps"],
        drop_rate=config["drop_rate"], attn_drop_rate=config["attn_drop_rate"],
        drop_path_rate=config["drop_path_rate"],
        use_flash=config.get("use_flash", False), dtype=dtype)


def rms(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2)))


def sample_rows(seed: int, n: int, count: int) -> list[int]:
    """``count`` distinct rows of ``n`` drawn from the seed, row 0 among them."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC4EC]))
    rest = rng.permutation(np.arange(1, n))[: max(0, min(count, n) - 1)]
    return [0] + sorted(int(i) for i in rest)
