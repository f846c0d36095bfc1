"""chip_smoke.py off the chip: it must refuse, loudly and without a result.

What it does ON the chip is checked by running it there (see the verify
skill); here only the half of its contract a CPU can show."""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True], ids=["in_repo", "alone"])
def test_chip_smoke_refuses_without_a_chip(alone, tmp_path):
    """``JAX_PLATFORMS=cpu python chip_smoke.py`` — from the checkout, and
    from a directory that holds nothing else of the repo — exits non-zero,
    says why on stderr, and prints no ``"ok": true`` line. It never carries
    on with the CPU."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(script), env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr
