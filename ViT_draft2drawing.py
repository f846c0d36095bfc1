#!/usr/bin/env python
"""Cold-sampling + zero-shot application entry point: ``python ViT_draft2drawing.py``.

Preserves the reference script's surface (ViT_draft2drawing.py:331-419): loads
the vit_tiny checkpoint from ``Saved_Models/20220822vit_tiny_diffusion/``,
renders the 6-level cold-diffusion sequence figure, then — given a draft image
— runs the zero-shot draft→drawing pipeline: encode the draft to each noise
level t_start ∈ range(1599, 2000, 50), DDIM-denoise with k=10, and tile the
nine variants into ``draft2img.png``. The slerp interpolation the reference
keeps commented out (ViT_draft2drawing.py:422-476) is live here behind
``--interpolate A B``.

Additions over the reference: ``--config/--checkpoint/--init-random`` (the
upstream snapshot ships no weights), ``--draft`` to point at any sketch, and
automatic TPU dispatch.
"""

import math
import os
import sys

import click

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def img2tensor(path: str, img_size):
    """Load an image file → NHWC float array in [−1, 1] (reference
    ViT_draft2drawing.py:331-339: resize then scale, no crop)."""
    import jax.numpy as jnp
    import numpy as np

    from ddim_cold_tpu.data.datasets import pil_loader
    from ddim_cold_tpu.data.resize import resize_bilinear

    img = np.asarray(pil_loader(path), np.float32) / 255.0
    img = resize_bilinear(img, tuple(img_size))
    return jnp.asarray(img * 2.0 - 1.0)[None]


@click.command()
@click.option("--config", "config_name", default="vit_tiny",
              help="Model config name (reference uses vit_tiny).")
@click.option("--checkpoint", default=None,
              help="Weights: torch .pkl or orbax dir "
                   "[default: Saved_Models/20220822vit_tiny_diffusion/bestloss.pkl].")
@click.option("--init-random", is_flag=True,
              help="Use random init instead of a checkpoint (smoke runs).")
@click.option("--draft", default=None,
              help="Draft/sketch image for the draft→drawing app.")
@click.option("--interpolate", nargs=2, default=None,
              help="Two images to slerp-interpolate between (C25).")
@click.option("--cold-n", default=49, help="Samples in the cold grid.")
@click.option("--seed", default=0, help="Sampling rng seed.")
@click.option("--eta", default=0.0,
              help="Stochastic-DDIM noise scale for the draft2img restarts "
                   "and the --interpolate decode (0 = the reference's "
                   "deterministic sampler).")
def main(config_name, checkpoint, init_random, draft, interpolate, cold_n,
         seed, eta):
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.models import MODEL_CONFIGS, DiffusionViT
    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.utils import checkpoint as ckpt
    from ddim_cold_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # repeat CLI runs reuse compiled XLA programs
    from ddim_cold_tpu.utils.image import get_next_path, grid_shape, save_grid

    model = DiffusionViT(total_steps=2000, **MODEL_CONFIGS[config_name])
    saved = os.path.join(HERE, "Saved_Models")
    run_dir = os.path.join(saved, "20220822vit_tiny_diffusion")
    os.makedirs(run_dir, exist_ok=True)

    if init_random:
        params = model.init(
            jax.random.PRNGKey(seed),
            jnp.zeros((1, *model.img_size, 3)), jnp.zeros((1,), jnp.int32),
        )["params"]
    else:
        path = checkpoint or os.path.join(run_dir, "bestloss.pkl")
        if os.path.isdir(path):
            target = model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, *model.img_size, 3)), jnp.zeros((1,), jnp.int32),
            )["params"]
            params = ckpt.restore_checkpoint(path, target)
        else:
            params = ckpt.load_torch_pkl(path, model.patch_size)

    print(f"devices: {jax.devices()}")

    # --- cold-diffusion sequence figure (reference :364-376) -----------------
    # levels follow the model's own size (t ∈ [1, log2(H)]): 6 for the
    # reference's 64px configs, 7 for 200px via the additive --config flag
    levels = int(math.log2(model.img_size[0]))
    seq = sampling.cold_sample(model, params, jax.random.PRNGKey(seed),
                               n=cold_n, levels=levels, return_sequence=True)
    frames = jnp.swapaxes(seq, 0, 1).reshape(-1, *seq.shape[2:])
    out = save_grid(frames, get_next_path(os.path.join(saved, "cold_sequence.png")),
                    nrows=cold_n, ncols=seq.shape[0])
    print(f"wrote {out}")

    grid = sampling.cold_sample(model, params, jax.random.PRNGKey(seed + 1),
                                n=cold_n, levels=levels)
    nrows, ncols = grid_shape(cold_n)
    out = save_grid(grid, get_next_path(os.path.join(saved, "cold_samples.png")),
                    nrows=nrows, ncols=ncols)
    print(f"wrote {out}")

    # --- zero-shot draft→drawing (reference :378-419) ------------------------
    if draft is not None:
        x = img2tensor(draft, model.img_size)
        variants = []
        t_starts = list(range(1599, 2000, 50))  # 9 restart levels (:393)
        for i, t_start in enumerate(t_starts):
            noisy = sampling.forward_noise(
                jax.random.PRNGKey(seed + 100 + i), x, t_start, model.total_steps)
            variants.append(sampling.sample_from(
                model, params, noisy, t_start=t_start, k=10, eta=eta,
                rng=jax.random.PRNGKey(seed + 200 + i))[0])
        tiles = jnp.stack([(x[0] + 1.0) / 2.0] + variants)
        out = save_grid(tiles, get_next_path(os.path.join(saved, "draft2img.png")),
                        nrows=2, ncols=5)
        print(f"wrote {out}")

    # --- slerp interpolation (reference :422-476, dormant upstream) ----------
    if interpolate:
        a = img2tensor(interpolate[0], model.img_size)[0]
        b = img2tensor(interpolate[1], model.img_size)[0]
        frames = sampling.slerp_interpolate(
            model, params, jax.random.PRNGKey(seed + 500), a, b,
            n_interp=8, t_start=1800, k=10, eta=eta)
        out = save_grid(frames, get_next_path(os.path.join(saved, "interpolation.png")),
                        nrows=1, ncols=8)
        print(f"wrote {out}")


if __name__ == "__main__":
    main()
