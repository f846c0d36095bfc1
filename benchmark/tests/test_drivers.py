"""Each driver end to end at a toy size from the fixture files, through the
same ``execute`` a real run uses (only the look for a chip is skipped), and
the same with the timed path broken underneath: `correct` must come out
false."""

import time

import numpy as np
import pytest

from benchmark import result_line
from benchmark.run import execute


def _line(toy_manifest, run, result, compared, setup_s, peak):
    expected = result_line.expected_metrics(toy_manifest, run.cell.name, False)
    line = result_line.build(
        correct=all(c.ok for c in compared), attempted=result["attempted"],
        failed=result["failed"], values=dict(result["e2e"], setup_s=setup_s),
        units=expected,
        device={"platform": "cpu", "kind": "cpu", "count": run.cell.chips,
                "memory_peak_bytes": max(peak, 1)})
    result_line.validate(line, expected, traced=False, chips=run.cell.chips)
    return line


@pytest.mark.parametrize("cell", ["toy_sample", "toy_serve", "toy_train",
                                  "toy_train_dp4"])
def test_driver_end_to_end(toy_manifest, toy_run, cell):
    run = toy_run(cell, seed=2**31 + 11)
    result, compared, setup_s, peak, _, _ = execute(run, t0=time.perf_counter())
    for c in compared:
        print(c)
    line = _line(toy_manifest, run, result, compared, setup_s, peak)
    assert line["correct"] is True, [str(c) for c in compared]
    assert line["failed"] == 0 and line["attempted"] >= 1


def test_broken_sampler_is_not_correct(toy_run, monkeypatch):
    """An answer altered where it is produced: the sampler returns its
    images with two rows swapped."""
    from ddim_cold_tpu.ops import sampling

    real = sampling.ddim_sample

    def swapped(*args, **kwargs):
        out = np.array(real(*args, **kwargs))
        out[[0, 1]] = out[[1, 0]]
        return out

    monkeypatch.setattr(sampling, "ddim_sample", swapped)
    run = toy_run("toy_sample")
    _, compared, *_ = execute(run, t0=time.perf_counter())
    assert not all(c.ok for c in compared)


def test_broken_serving_is_not_correct(toy_run, monkeypatch):
    """Tickets delivered with another request's noise: every request is
    served from seed + 1."""
    from ddim_cold_tpu.serve import router as router_mod

    real = router_mod.Router.submit

    def shifted(self, seed=None, n=1, **kwargs):
        return real(self, seed=seed + 1, n=n, **kwargs)

    monkeypatch.setattr(router_mod.Router, "submit", shifted)
    run = toy_run("toy_serve")
    _, compared, *_ = execute(run, t0=time.perf_counter())
    assert not all(c.ok for c in compared)


def test_step_that_returns_its_state_is_not_correct(toy_run, monkeypatch):
    """A train step that returns its state unchanged."""
    from ddim_cold_tpu.train import step as step_mod

    real = step_mod.make_train_step

    def frozen(*args, **kwargs):
        inner = real(*args, **kwargs)

        def step(state, batch, rng, rec):
            import jax

            kept = jax.tree.map(lambda x: x.copy(), state)
            _, loss, rec = inner(state, batch, rng, rec)
            return kept, loss, rec

        return step

    monkeypatch.setattr(step_mod, "make_train_step", frozen)
    run = toy_run("toy_train")
    _, compared, *_ = execute(run, t0=time.perf_counter())
    names = {c.name for c in compared if not c.ok}
    assert "param_change_worst_leaf" in names or "first_grad_worst_leaf" in names
