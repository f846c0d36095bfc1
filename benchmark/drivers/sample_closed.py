"""Closed loop, one caller: back-to-back ``ddim_sample`` calls of ``n`` images
at stride ``k``, each fetched to the host before the next starts.

Traffic file: {"driver": "sample_closed", "n": images per call, "k": stride,
"check_rows": rows of the last call compared with the reference}.

The window runs whole calls until ``seconds`` have passed and is as long as
the calls it ran: ``sample_img_per_s`` is all their images over all that
time, so it does not move in steps of one call.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import weights
from benchmark.drivers import common
from benchmark.harness import Compared, log


def _call(state, index: int):
    import jax

    from ddim_cold_tpu.ops import sampling

    key = jax.random.fold_in(state["key"], index)
    out = sampling.ddim_sample(state["model"], state["params"], key,
                               k=state["k"], n=state["n"])
    return key, np.asarray(out)  # D2H: the caller holds the images


def setup(run) -> dict:
    model = common.build_model(run.config)
    params = weights.make(run.config, run.seed)
    state = {"model": model, "params": params, "n": int(run.traffic["n"]),
             "k": int(run.traffic["k"]), "key": weights.seed_key(run.seed, 1)}
    with run.spans.span("warmup"):
        _, out = _call(state, 0)  # the one shape the window uses
    if not np.isfinite(out).all():
        raise RuntimeError("warm-up call produced non-finite images")
    return state


def window(run, state, seconds: float) -> dict:
    calls, last = 0, None
    t0 = time.perf_counter()
    while True:
        with run.spans.span("ddim_sample"):
            last = _call(state, 1 + calls)
        calls += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    n = state["n"]
    log(f"{calls} calls of {n} images in {t1 - t0:.3f} s")
    steps = len(range(run.config["total_steps"] - 1, 0, -state["k"]))
    return {"attempted": calls, "failed": 0, "window_s": t1 - t0,
            "t0": t0, "t1": t1,
            "e2e": {"sample_img_per_s": calls * n / (t1 - t0)},
            "counters": {"calls": calls, "images": calls * n,
                         "scan_steps": calls * steps},
            "last": last}


def close(run, state) -> None:
    pass


def check(run, state, result) -> list:
    """Rows of the window's last call against the reference's own
    trajectory from the same start noise."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ddim

    key, images = result["last"]
    model, n = state["model"], state["n"]
    rows = common.sample_rows(run.seed, n, int(run.traffic["check_rows"]))
    h, w = model.img_size
    x_init = jax.random.normal(key, (n, h, w, 3), jnp.float32)[np.asarray(rows)]
    want = np.asarray(ddim.sample(
        state["params"], x_init, k=state["k"],
        total_steps=run.config["total_steps"],
        arch=weights.arch_of(run.config)))
    result["reference"] = (x_init, want)
    got = images[np.asarray(rows)]
    finite = bool(np.isfinite(images).all() and images.min() >= 0.0
                  and images.max() <= 1.0)
    return [
        Compared("images_finite_in_unit_range", 0.0 if finite else 1.0, 0.0),
        Compared("sample_rms_vs_reference", common.rms(got, want),
                 run.cell.limits["sample_rms_vs_reference"]),
    ]


def control(run, state, result) -> list:
    """The reference one precision below the configuration's, put in the
    program's place on the rows ``check`` compared. Must fail a limit."""
    from benchmark.reference import ddim, lowprec

    x_init, want = result["reference"]
    below = lowprec.BY_NAME[lowprec.BELOW[run.config["precision"]]]
    got = np.asarray(ddim.sample(
        state["params"], x_init, k=state["k"],
        total_steps=run.config["total_steps"],
        arch=weights.arch_of(run.config), ops=below))
    return [Compared("sample_rms_vs_reference", common.rms(got, want),
                     run.cell.limits["sample_rms_vs_reference"])]
