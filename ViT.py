#!/usr/bin/env python
"""Inference CLI: ``python ViT.py --sample_n 256 --acc_k 1``.

Preserves the reference CLI surface (ViT.py:258-316): renders the k=100
denoise-sequence figure and a 16×16 sample grid from the OxfordFlower config.
Device selection is automatic (TPU when present — the north-star "dispatch to
TPU backend when no GPU"). Additions: ``--config`` to pick any model config,
``--checkpoint`` to point at a torch ``.pkl`` or an orbax directory, and
``--init-random`` for smoke runs without weights (the reference hard-requires
``Saved_Models/OxfordFlower.pkl``, which is absent from the upstream snapshot).
"""

import os
import sys

import click

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


@click.command()
@click.option("--sample_n", default=256, help="Number of samples you'll get.")
@click.option("--acc_k", default=1, help="Number of steps jumped during sampling.")
@click.option("--config", "config_name", default="oxford_flower_64",
              help="Model config name (see ddim_cold_tpu.models.MODEL_CONFIGS).")
@click.option("--checkpoint", default=None,
              help="Weights: torch .pkl or orbax dir "
                   "[default: Saved_Models/OxfordFlower.pkl].")
@click.option("--init-random", is_flag=True,
              help="Use random init instead of a checkpoint (smoke runs).")
@click.option("--seed", default=0, help="Sampling rng seed.")
@click.option("--eta", default=0.0,
              help="Stochastic-DDIM noise scale (DDIM paper interpolation; "
                   "0 = the reference's deterministic sampler).")
def main(sample_n, acc_k, config_name, checkpoint, init_random, seed, eta):
    """Batch sampling + denoise-sequence figure (reference ViT.py main)."""
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
    os.makedirs(saved, exist_ok=True)

    if init_random:
        params = model.init(
            jax.random.PRNGKey(seed),
            jnp.zeros((1, *model.img_size, 3)), jnp.zeros((1,), jnp.int32),
        )["params"]
    else:
        path = checkpoint or os.path.join(saved, "OxfordFlower.pkl")
        if os.path.isdir(path):
            target = model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, *model.img_size, 3)), jnp.zeros((1,), jnp.int32),
            )["params"]
            params = ckpt.restore_checkpoint(path, target)
        else:
            params = ckpt.load_torch_pkl(path, model.patch_size)

    print(f"devices: {jax.devices()}")
    # multi-chip hosts shard the sample batch over a data mesh automatically
    # (the reference sampler is single-GPU; SPMD sampling is free here)
    mesh = None
    if jax.device_count() > 1 and sample_n % jax.device_count() == 0:
        from ddim_cold_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": jax.device_count()})

    n_seq = 6
    seq = sampling.ddim_sample(model, params, jax.random.PRNGKey(seed), k=100,
                               n=n_seq, return_sequence=True, eta=eta)
    # rows = samples, cols = trajectory frames (reference figure layout)
    frames = jnp.swapaxes(seq, 0, 1).reshape(-1, *seq.shape[2:])
    out = save_grid(frames, get_next_path(os.path.join(saved, "denoise_sequence.png")),
                    nrows=n_seq, ncols=seq.shape[0])
    print(f"wrote {out}")

    img = sampling.ddim_sample(model, params, jax.random.PRNGKey(seed + 1),
                               k=acc_k, n=sample_n, mesh=mesh, eta=eta)
    nrows, ncols = grid_shape(sample_n)
    out = save_grid(img, get_next_path(os.path.join(saved, "samples.png")),
                    nrows=nrows, ncols=ncols)
    print(f"wrote {out}")
    # for callers that drive the command in-process (click's
    # standalone_mode=False hands this back); the shell entry ignores it
    return seq, img


if __name__ == "__main__":
    main()
