"""Run by hand, off the chip:  JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
(not part of the repo's tier-1 suite). Four virtual CPU devices stand in for
a four-chip host."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture(scope="session")
def toy_manifest():
    from benchmark import manifest as mf

    return mf.load_manifest(FIXTURES)


@pytest.fixture
def toy_run(toy_manifest):
    """A ``Run`` of a fixture cell on CPU devices: the test, not an option
    of the command, lets the drivers run off the chip."""
    import jax

    from benchmark import manifest as mf
    from benchmark.harness import Run

    def make(cell_name: str, seed: int = 3, seconds: float = 1.0,
             traced: bool = False):
        cell = mf.Cell(toy_manifest, cell_name,
                       here=os.path.join(FIXTURES, "benchmark"))
        peaks = mf.peaks_for("TPU v5 lite")
        return Run(cell, seed, seconds, traced, jax.devices()[: cell.chips],
                   peaks)

    return make
