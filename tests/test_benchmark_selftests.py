"""The benchmark's own tests (``benchmark/tests``), one case a file.

The tier-1 command collects ``tests/`` only, so a program change that breaks an
import or a signature a benchmark driver uses would otherwise be found on the
chip. Each file runs in a fresh interpreter: ``benchmark/tests/conftest.py``
sets up its own four virtual devices, and ``tests/test_hybrid.py`` and
``benchmark/tests/test_hybrid.py`` share a module name.
"""

import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py")))
TIMEOUT_S = 300
if not FILES:  # an empty parameter set would be one silent skip
    raise RuntimeError("benchmark/tests/test_*.py matched nothing")


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(f) for f in FILES])
def test_benchmark_selftest_file(path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the child's conftest picks its own device count
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired as exc:
        out = (exc.stdout or b"")
        out = out.decode(errors="replace") if isinstance(out, bytes) else out
        pytest.fail(f"{os.path.relpath(path, ROOT)} ran past {TIMEOUT_S} s\n{out[-4000:]}")
    tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-60:])
    assert proc.returncode == 0, f"exit code {proc.returncode}\n{tail}"
