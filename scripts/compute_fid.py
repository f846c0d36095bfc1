#!/usr/bin/env python
"""Compute FID for a trained run: model samples vs the validation images.

The north-star acceptance metric (BASELINE.json "FID within 0.5 of the CUDA
reference") — the reference itself never measures FID, and its pretrained
checkpoints are absent, so the number established here IS the baseline.

Weight provenance: this bench host has no network and no torchvision, so the
canonical pretrained InceptionV3 cannot be fetched. The extractor therefore
uses **seeded random weights** (`--inception-seed`, default 0): a fixed,
reproducible feature space. Random-feature FID is a valid distance for
comparing models/runs under the SAME extractor (and the converter itself is
validated layer-by-layer against a real torch forward in
tests/test_inception_parity.py, so dropping in the canonical ``.pth`` when
networked is pure data movement: ``--inception-pth``).

Writes ``results/<run>/fid.json`` and prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("run_dir", nargs="?", default=os.path.join(
        REPO, "Saved_Models", "20220822vit_tiny_diffusion"))
    ap.add_argument("--val-dir", default=None,
                    help="real-image folder for the FID reference stream [default: the run config's own val dataStorage]")
    ap.add_argument("--n-samples", type=int, default=1024)
    ap.add_argument("--n-real", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--sampler", choices=("cold", "ddim"), default="cold",
                    help="cold = the trained regime of the 20220822 run; "
                         "ddim uses stride --k")
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--inception-seed", type=int, default=0)
    ap.add_argument("--inception-pth", default=None,
                    help="optional torchvision inception_v3 .pth for "
                         "published-comparable numbers")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    from ddim_cold_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # repeat CLI runs reuse compiled XLA programs
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from ddim_cold_tpu.data import ColdDownSampleDataset, ShardedLoader
    from ddim_cold_tpu.eval import fid, inception
    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.utils.run_io import load_run

    # -- model from the run's own config + best checkpoint ------------------
    config, model, params = load_run(args.run_dir)
    if args.val_dir is None:
        from ddim_cold_tpu.utils.run_io import default_val_dir

        args.val_dir = default_val_dir(config, REPO)

    # -- extractor ----------------------------------------------------------
    if args.inception_pth:
        inc_model, inc_vars = inception.load_torch_inception(args.inception_pth)
        provenance = f"torchvision pth: {args.inception_pth}"
    else:
        inc_model, inc_vars = inception.init_variables(
            jax.random.PRNGKey(args.inception_seed))
        provenance = (f"seeded random init (PRNGKey({args.inception_seed})) — "
                      "no network for the canonical weights; converter "
                      "torch-parity-tested")

    # -- real stream: clean val images in [0,1] -----------------------------
    ds = ColdDownSampleDataset(args.val_dir, imgSize=tuple(config.image_size),
                               target_mode="direct")
    n_real_seen = 0

    def real_batches():
        nonlocal n_real_seen
        loader = ShardedLoader(ds, args.batch, shuffle=False, drop_last=True)
        for noisy, clean, t in loader:  # target of the direct mode is x0
            if n_real_seen >= args.n_real:
                break
            yield (clean + 1.0) / 2.0
            n_real_seen += clean.shape[0]

    # multi-chip hosts shard the sample batch over a data mesh (the samplers'
    # SPMD path); cold levels follow the run's image size — the trained
    # regime is t ∈ [1, log2(H)], not the 64px default
    mesh = None
    if jax.device_count() > 1 and args.batch % jax.device_count() == 0:
        from ddim_cold_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": jax.device_count()})
    levels = int(math.log2(config.image_size[0]))

    def sampler(rng, nb):
        if args.sampler == "cold":
            return sampling.cold_sample(model, params, rng, n=nb,
                                        levels=levels, mesh=mesh)
        return sampling.ddim_sample(model, params, rng, k=args.k, n=nb,
                                    mesh=mesh)

    value = fid.compute_fid(
        model, params, real_batches(), rng=jax.random.PRNGKey(1),
        n_samples=args.n_samples, sample_batch=args.batch,
        k=args.k, inception_model=inc_model, inception_variables=inc_vars,
        sampler=sampler,
    )

    run = os.path.basename(os.path.normpath(args.run_dir))
    out = {
        "metric": f"fid_{args.sampler}" + (f"_k{args.k}" if args.sampler == "ddim" else ""),
        "value": round(float(value), 4),
        "n_samples": args.n_samples,
        "n_real": n_real_seen,  # actually accumulated, not requested
        "extractor": provenance,
        "run": run,
    }
    out_dir = os.path.join(REPO, "results", run)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "fid.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
