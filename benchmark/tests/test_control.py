"""The control of `correct`, kept at a size a test run can hold: the
reference one precision below the configuration's (float8 for bfloat16), put
in the program's place, must fail at least one limit of the cell, while the
program passes them all. (On the chip, at the cells' own sizes:
``benchmark/calibrate.py``.)"""

import time

import pytest

from benchmark import manifest as mf
from benchmark.run import execute


@pytest.mark.parametrize("cell", ["toy_sample", "toy_serve", "toy_train"])
def test_control_is_not_correct(toy_run, cell):
    run = toy_run(cell, seed=5)
    result, compared, _, _, _, state = execute(run, t0=time.perf_counter())
    assert all(c.ok for c in compared), [str(c) for c in compared]
    control = mf.load_driver(run.cell.driver).control(run, state, result)
    for c in control:
        print("control", c)
    assert not all(c.ok for c in control), [str(c) for c in control]
