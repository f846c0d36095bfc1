#!/usr/bin/env python
"""Publish a training run's evidence into the committed ``results/`` dir.

Round-1 verdict: the framework was unit-correct but shipped no proof that it
*trains* — no committed loss curve, no sample grids from trained weights.
This script turns a finished ``Saved_Models/<run>/`` into committable
artifacts:

* ``results/<run>/train.log`` + ``metrics.jsonl`` — the raw record (the
  reference's own train.log is the parity artifact, SURVEY.md C21);
* ``results/<run>/val_curve.png`` — our per-epoch val smooth-L1 overlaid
  against the reference's committed run
  (`/root/reference/Saved_Models/20220822vit_tiny_diffusion/train.log`:
  0.071 @ epoch 0 → best 0.0504). The datasets differ (procedural surrogate
  vs Oxford Flowers — the bench host has no network), so the overlay shows
  *convergence behavior*, not identical values;
* ``results/<run>/samples.png`` / ``cold_sequence.png`` — grids sampled from
  the run's ``bestloss.ckpt``;
* ``results/<run>/summary.json`` — machine-readable best/final losses.

Usage: python scripts/publish_run.py [run_dir] [--no-samples] [--cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_LOG = ("/root/reference/Saved_Models/20220822vit_tiny_diffusion/train.log")
EPOCH_RE = re.compile(r"epoch:\s*(\d+)\s+loss:\s*([0-9.]+)")


def parse_epoch_losses(log_path: str) -> dict[int, float]:
    """epoch → val loss; later lines win (the reference log contains a
    restart whose epochs overlap, multi_gpu_trainer resume semantics)."""
    out: dict[int, float] = {}
    with open(log_path) as f:
        for line in f:
            m = EPOCH_RE.search(line)
            if m:
                out[int(m.group(1))] = float(m.group(2))
    return out


def render_curve(ours: dict[int, float], ref: dict[int, float], path: str):
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, ax = plt.subplots(figsize=(7, 4.2), dpi=130)
    if ref:
        xs = sorted(ref)
        ax.plot(xs, [ref[x] for x in xs], color="#999999", lw=1.5,
                label="reference (torch/3090, Oxford Flowers)")
        ax.axhline(min(ref.values()), color="#999999", lw=0.8, ls="--",
                   label=f"reference best {min(ref.values()):.4f}")
    xs = sorted(ours)
    ax.plot(xs, [ours[x] for x in xs], color="#1666c0", lw=1.8,
            label="this framework (TPU, surrogate flowers)")
    ax.axhline(min(ours.values()), color="#1666c0", lw=0.8, ls="--",
               label=f"ours best {min(ours.values()):.4f}")
    ax.set_xlabel("epoch")
    ax.set_ylabel("val smooth-L1")
    ax.set_yscale("log")
    ax.set_title("Cold-diffusion vit_tiny 64px: val loss per epoch")
    ax.legend(fontsize=8)
    fig.tight_layout()
    fig.savefig(path)
    plt.close(fig)


def render_samples(run_dir: str, out_dir: str, *, n: int = 16, wd=None):
    """Grids from the run's best checkpoint: DDIM samples + the 6-step cold
    sequence (the reference's two acceptance figures, ViT.py:283-305,
    ViT_draft2drawing.py:364-376)."""
    import math

    import jax
    import numpy as np

    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.utils.image import save_grid
    from ddim_cold_tpu.utils.run_io import load_run

    config, model, params = load_run(run_dir)

    # cold-model grids in the run's trained regime: t ∈ [1, log2(H)] —
    # 6 levels for 64px, 7 for the 200px config (same rule as compute_fid)
    levels = int(math.log2(config.image_size[0]))
    side = int(np.sqrt(n))
    if wd is not None:  # first device op = the 200px sampler compile
        wd.mark("sample grid (first sampler compile)", budget_s=1800)
    cold = np.asarray(sampling.cold_sample(
        model, params, jax.random.PRNGKey(0), n=side * side, levels=levels))
    save_grid(cold, os.path.join(out_dir, "samples.png"),
              nrows=side, ncols=side)
    if wd is not None:  # n=4 differs from n=16: a second compile
        wd.mark("sequence grid (second sampler compile)", budget_s=1800)
    seq = np.asarray(sampling.cold_sample(
        model, params, jax.random.PRNGKey(1), n=4, levels=levels,
        return_sequence=True))
    # (levels, n, H, W, C) → rows = sample, cols = denoising level
    frames = seq.transpose(1, 0, 2, 3, 4).reshape(-1, *seq.shape[-3:])
    save_grid(frames, os.path.join(out_dir, "cold_sequence.png"),
              nrows=seq.shape[1], ncols=seq.shape[0])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("run_dir", nargs="?", default=os.path.join(
        REPO, "Saved_Models", "20220822vit_tiny_diffusion"))
    ap.add_argument("--no-samples", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    run = os.path.basename(os.path.normpath(args.run_dir))
    out_dir = os.path.join(REPO, "results", run)
    os.makedirs(out_dir, exist_ok=True)
    for name in ("train.log", "metrics.jsonl"):
        src = os.path.join(args.run_dir, name)
        if os.path.isfile(src):
            shutil.copy(src, out_dir)

    ours = parse_epoch_losses(os.path.join(args.run_dir, "train.log"))
    if not ours:
        raise SystemExit("no epoch lines in train.log — run unfinished?")
    ref = parse_epoch_losses(REF_LOG) if os.path.isfile(REF_LOG) else {}
    render_curve(ours, ref, os.path.join(out_dir, "val_curve.png"))

    if not args.no_samples:
        # stall guard: the curves/logs above are already published;
        # sampling is the only unbounded device work, so a stall still
        # leaves a partial artifact
        from ddim_cold_tpu.utils.platform import watchdog_stall_s
        from ddim_cold_tpu.utils.watchdog import StallWatchdog

        stall_s = watchdog_stall_s("DDIM_COLD_FID_STALL_S", 600.0)
        wd = StallWatchdog(stall_s, name="publish-run").start()
        render_samples(args.run_dir, out_dir, wd=wd)
        wd.done()

    summary = {
        "run": run,
        "epochs": len(ours),
        "val_loss_epoch0": ours.get(0),
        "val_loss_best": min(ours.values()),
        "val_loss_last": ours[max(ours)],
        "reference_best": min(ref.values()) if ref else None,
        "reference_epoch0": ref.get(0) if ref else None,
        "dataset": "procedural surrogate flowers (scripts/make_dataset.py; "
                   "bench host has no network for the real Oxford Flowers)",
    }
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps(summary))
    print(f"published → {out_dir}")


if __name__ == "__main__":
    main()
