#!/usr/bin/env python
"""Training entry point: ``python multi_gpu_trainer.py <ExpName>``.

Preserves the reference launcher's observable behavior (multi_gpu_trainer.py
:167-219): reads ``<ExpName>.yaml`` (script dir, then cwd), creates
``Saved_Models/<ExpName><framework>/``, copies the yaml in, derives
batch (AMP×2) and lr (·batch·devices/512), then trains. The per-GPU
``mp.Process`` spawn is gone — one process drives the whole mesh (SPMD); on
multi-host TPU, launch this same script once per host.
"""

import os
import shutil
import sys


def main(argv, base_dir=None):
    """``base_dir`` overrides where ``Saved_Models/`` is rooted (default: the
    script dir, matching the reference's ``SavedDir``); used by tests."""
    if len(argv) < 2:
        print("usage: python multi_gpu_trainer.py <ExpName>")
        return 2
    exp_name = argv[1]
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    base = base_dir or here

    from ddim_cold_tpu.config import load_config

    yaml_path = os.path.join(here, exp_name + ".yaml")
    if not os.path.isfile(yaml_path) or base_dir is not None:
        cand = os.path.abspath(exp_name + ".yaml")
        if os.path.isfile(cand):
            yaml_path = cand
    config = load_config(yaml_path, exp_name)

    from ddim_cold_tpu.train.trainer import run

    saved_dir = os.path.join(base, "Saved_Models")
    run_dir = os.path.join(saved_dir, config.run_name)
    if os.path.isdir(run_dir):
        print("Warning!Current folder already exist!")
    os.makedirs(run_dir, exist_ok=True)
    shutil.copy(yaml_path, run_dir)

    result = run(config, base)
    print(f"\nbest val loss {result.best_loss:.5f} after {result.steps} steps "
          f"→ {result.run_dir}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
