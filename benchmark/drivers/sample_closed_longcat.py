"""``sample_closed`` for a configuration of double layers whose experts are a
shortcut round two attentions and two dense MLPs, with zero-compute experts
behind the router's last outputs and rescaled latents (``model_type:
longcat_flash``): the same closed loop (its ``window`` and ``close``, by
import), with this configuration's model, weights and reference in ``setup``,
``check`` and ``control``.

Traffic file: {"driver": "sample_closed_longcat", "n": images per call,
"k": stride, "check_rows": rows of the last call compared with the
reference}.

A file of its own because a ``model_config`` PR may edit no benchmark file;
importing the program's ``models/longcat.py`` at the top is also what makes a
checkout without that module fail this cell at once (PERF.md section 7 names
the fold of the ``sample_closed*`` drivers).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import weights_longcat
from benchmark.drivers import common
from benchmark.drivers.sample_closed import _call, close, window  # noqa: F401
from benchmark.harness import Compared, log
from benchmark.weights import seed_key

from ddim_cold_tpu.models import longcat  # noqa: F401  (see the docstring)
from ddim_cold_tpu.models.hybrid import HybridDenoiser


def build_model(config: dict) -> HybridDenoiser:
    """The program's model at the configuration's sizes, computing and
    storing in its stated precision."""
    dtype = weights_longcat.DTYPES[config["precision"]]
    return HybridDenoiser(
        trunk=weights_longcat.trunk_of(config),
        img_size=tuple(config["img_size"]), patch_size=config["patch_size"],
        in_chans=config.get("in_chans", 3), total_steps=config["total_steps"],
        dtype=dtype, param_dtype=dtype)


def setup(run) -> dict:
    model = build_model(run.config)
    params = weights_longcat.make(run.config, run.seed)
    state = {"model": model, "params": params, "n": int(run.traffic["n"]),
             "k": int(run.traffic["k"]), "key": seed_key(run.seed, 1)}
    with run.spans.span("warmup"):
        _, out = _call(state, 0)  # the one shape the window uses
    if not np.isfinite(out).all():
        raise RuntimeError("warm-up call produced non-finite images")
    return state


def _reference(run, state, x_init, ops):
    from benchmark.reference import longcat as reference

    t0 = time.perf_counter()
    out = np.asarray(reference.sample(
        state["params"], x_init, k=state["k"],
        total_steps=run.config["total_steps"],
        trunk=weights_longcat.trunk_of(run.config),
        patch_size=run.config["patch_size"], ops=ops))
    log(f"reference trajectory of {len(x_init)} rows: "
        f"{time.perf_counter() - t0:.1f} s")
    return out


def check(run, state, result) -> list:
    """Rows of the window's last call against the reference's own
    trajectory from the same start noise."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import vit

    key, images = result["last"]
    n = state["n"]
    rows = np.asarray(common.sample_rows(run.seed, n,
                                         int(run.traffic["check_rows"])))
    h, w = state["model"].img_size
    x_init = jax.random.normal(key, (n, h, w, 3), jnp.float32)[rows]
    want = _reference(run, state, x_init, vit.EXACT)
    result["reference"] = (x_init, want)
    finite = bool(np.isfinite(images).all() and images.min() >= 0.0
                  and images.max() <= 1.0)
    return [
        Compared("images_finite_in_unit_range", 0.0 if finite else 1.0, 0.0),
        Compared("sample_rms_vs_reference", common.rms(images[rows], want),
                 run.cell.limits["sample_rms_vs_reference"]),
    ]


def control(run, state, result) -> list:
    """The reference one precision below the configuration's, put in the
    program's place on the rows ``check`` compared. Must fail a limit."""
    from benchmark.reference import lowprec

    x_init, want = result["reference"]
    below = lowprec.BY_NAME[lowprec.BELOW[run.config["precision"]]]
    got = _reference(run, state, x_init, below)
    return [Compared("sample_rms_vs_reference", common.rms(got, want),
                     run.cell.limits["sample_rms_vs_reference"])]
