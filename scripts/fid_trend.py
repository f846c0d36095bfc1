#!/usr/bin/env python
"""Per-checkpoint FID trend under one fixed seeded extractor.

Without canonical InceptionV3 weights (this host is zero-egress; converter
torch-parity-tested in tests/test_inception_parity.py, so dropping in the
canonical ``.pth`` later is pure data movement), a single random-feature FID
at small n is high-variance and orders nothing. This script makes the metric
mean something the only way available offline: compute FID for SEVERAL
checkpoints of the same run — plus a random-init anchor — under ONE fixed
extractor (same seed, same n), so the number demonstrably orders models
(random ≫ early ≫ late). Real-set statistics are computed once and shared by
every point.

Checkpoint sources, newest schema first:
* ``<run>/snapshots/epoch_N/`` — periodic copies of ``lastepoch.ckpt``
  collected while the trainer runs;
* ``<run>/bestloss.ckpt`` — the run's best-val params (labelled "best").

Writes ``results/<run>/fid_trend.json`` and prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def collect_points(run_dir: str, max_points: int):
    """→ ordered [(label, epoch|None, ckpt_path|None)] trend points: the
    random-init anchor, evenly-thinned snapshot epochs (first and last always
    kept: ``eval.fid.thin``), then the run's best checkpoint."""
    from ddim_cold_tpu.eval.fid import thin

    points = [("random", -1, None)]  # anchor: params as-initialized
    snap_dir = os.path.join(run_dir, "snapshots")
    if os.path.isdir(snap_dir):
        snaps = []
        for name in os.listdir(snap_dir):
            m = re.fullmatch(r"epoch_(\d+)", name)
            if m:
                snaps.append((int(m.group(1)), os.path.join(snap_dir, name)))
        snaps.sort()
        snaps = thin(snaps, max_points)
        points += [(f"epoch_{ep}", ep, path) for ep, path in snaps]
    best = os.path.join(run_dir, "bestloss.ckpt")
    if os.path.isdir(best):
        points.append(("best", None, best))
    return points


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("run_dir", nargs="?", default=os.path.join(
        REPO, "Saved_Models", "20220822vit_tiny_diffusion"))
    ap.add_argument("--val-dir", default=None,
                    help="real-image folder for the FID reference stream [default: the run config's own val dataStorage]")
    ap.add_argument("--n-samples", type=int, default=256,
                    help="samples per trend point (the headline fid.json uses "
                         "compute_fid.py's n=1024; trend points trade n for "
                         "breadth under the SAME extractor)")
    ap.add_argument("--n-real", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--inception-seed", type=int, default=0)
    ap.add_argument("--max-points", type=int, default=10,
                    help="evenly thin snapshot points beyond this count")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    from ddim_cold_tpu.utils.watchdog import StallWatchdog

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from ddim_cold_tpu.data import ColdDownSampleDataset, ShardedLoader
    from ddim_cold_tpu.eval import fid, inception
    from ddim_cold_tpu.ops import sampling
    from ddim_cold_tpu.utils import checkpoint as ckpt
    from ddim_cold_tpu.utils.run_io import load_run_template

    run_dir = args.run_dir
    config, model, template = load_run_template(run_dir)
    if args.val_dir is None:
        from ddim_cold_tpu.utils.run_io import default_val_dir

        args.val_dir = default_val_dir(config, REPO)

    points = collect_points(run_dir, args.max_points)

    # -- stall guard: a device call that never returns must not hold the
    # process forever. Partial trend points are still an artifact — they
    # order checkpoints.
    run = os.path.basename(os.path.normpath(run_dir))
    results = []

    def _write_partial(label, silent_s):
        # a DISTINCT filename: a stall must never clobber a previously
        # complete fid_trend.json (same temp-then-promote discipline as the
        # chain's bench_v2 stage)
        out_dir = os.path.join(REPO, "results", run)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "fid_trend.partial.json"), "w") as f:
            json.dump({"metric": "fid_trend_cold", "points": results,
                       "aborted": f"stalled {silent_s:.0f}s after {label!r} "
                                  "(stall watchdog)"}, f, indent=1)

    # shared arm-condition (utils/platform.watchdog_stall_s): env override,
    # else disarmed on an effective-cpu platform (comma-list aware), else 600s
    from ddim_cold_tpu.utils.platform import watchdog_stall_s

    stall_s = watchdog_stall_s("DDIM_COLD_FID_STALL_S", 600.0)
    wd = StallWatchdog(stall_s, on_abort=_write_partial,
                       name="fid-trend").start()

    # -- fixed extractor + shared real statistics ---------------------------
    wd.mark("inception init (first device compile)", budget_s=1800)
    inc_model, inc_vars = inception.init_variables(
        jax.random.PRNGKey(args.inception_seed))
    feature_fn, dim = fid.make_feature_fn(inc_model, inc_vars)
    ds = ColdDownSampleDataset(args.val_dir, imgSize=tuple(config.image_size),
                               target_mode="direct")
    n_real_seen = 0

    def real_batches():
        nonlocal n_real_seen
        loader = ShardedLoader(ds, args.batch, shuffle=False, drop_last=True)
        for _, clean, _ in loader:
            if n_real_seen >= args.n_real:
                break
            # the first yielded batch triggers the jitted Inception forward
            # compile (heavier than init_variables' compile) — it gets the
            # long-compile budget, not the default window
            wd.mark(f"real-batch {n_real_seen}/{args.n_real}",
                    budget_s=1800 if n_real_seen == 0 else None)
            yield (clean + 1.0) / 2.0
            n_real_seen += clean.shape[0]

    real = fid.stats_for_batches(real_batches(), feature_fn, dim)
    print(f"[fid-trend] real stats over {real.count} images", file=sys.stderr)

    levels = int(math.log2(config.image_size[0]))

    def load_point(path):
        if path is None:
            return template
        if os.path.basename(path).startswith("epoch_"):
            # two snapshot layouts exist: the trainer's snapshot_epochs option
            # writes bare params; out-of-band collectors copy lastepoch.ckpt,
            # which holds the full resume state with a "params" entry. Raw-
            # restore, unwrap if needed, cast onto the template's dtypes.
            raw = ckpt.restore_checkpoint(path)
            if isinstance(raw, dict) and "params" in raw and "opt_state" in raw:
                raw = raw["params"]
            return jax.tree.map(
                lambda t, v: np.asarray(v, np.asarray(t).dtype), template, raw)
        return ckpt.restore_checkpoint(path, template)  # bestloss: bare params

    first_sample = True
    for label, epoch, path in points:
        params = load_point(path)
        fake = fid.ActivationStats(dim)
        rng, remaining = jax.random.PRNGKey(1), args.n_samples  # same stream
        while remaining > 0:  # full batches: one sampler compile (static shape)
            keep = min(args.batch, remaining)
            rng, sub = jax.random.split(rng)
            wd.mark(f"sample-batch {label} {args.n_samples - remaining}"
                    f"/{args.n_samples}",
                    budget_s=1800 if first_sample else None)
            first_sample = False
            imgs = sampling.cold_sample(model, params, sub, n=args.batch,
                                        levels=levels)
            fake.update(np.asarray(feature_fn(imgs))[:keep])
            remaining -= keep
        value = fid.fid_from_stats(real, fake)
        results.append({"ckpt": label, "epoch": epoch,
                        "fid": round(float(value), 4)})
        print(f"[fid-trend] {label}: {value:.2f}", file=sys.stderr)

    wd.done()
    # per-point deltas against the noise band of the points before
    # (FID: lower is better), plus the run_meta provenance stamp
    out = {
        "metric": "fid_trend_cold",
        "points": fid.annotate_deltas(results, "fid", lower_is_better=True),
        "run_meta": fid.run_metadata(chip=str(jax.devices()[0].device_kind)),
        "n_samples": args.n_samples,
        "n_real": n_real_seen,
        "extractor": (f"seeded random init (PRNGKey({args.inception_seed})) — "
                      "no network for canonical weights; fixed across all "
                      "points, so values order models but are NOT comparable "
                      "to published FID numbers"),
        "run": run,
    }
    out_dir = os.path.join(REPO, "results", run)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "fid_trend.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
