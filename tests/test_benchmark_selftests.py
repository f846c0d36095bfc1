"""The benchmark's own tests (``benchmark/tests``), one case a file.

The tier-1 command collects ``tests/`` only, so a program change that breaks an
import or a signature a benchmark driver uses would otherwise be found on the
chip. Each file runs in a fresh interpreter: ``benchmark/tests/conftest.py``
sets up its own four virtual devices, and ``tests/test_hybrid.py`` and
``benchmark/tests/test_hybrid.py`` share a module name.

``LEFT_OUT`` names the cases that a file's run deselects, each with the line
of it that the manifest has outgrown. It is not a place for a case that fails
for any other reason: ``test_a_case_left_out_fails_by_its_stale_line_alone``
runs each one and fails the gate once the case passes again, or fails
anywhere else than on that line.
"""

import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(glob.glob(os.path.join(ROOT, "benchmark", "tests", "test_*.py")))
TIMEOUT_S = 300
#: file -> {case: the one assertion of it that no longer holds}. PR 45's case
#: counts BENCHMARK.json's configurations and cells as PR 45 left them; PR 48
#: appends one of each, and a PR that adds a configuration may edit no file
#: under benchmark/. Everything else that case asserts is asserted on the
#: manifest as it stands by benchmark/tests/test_smallthinker.py::
#: test_the_cells_before_this_one_keep_their_lines. A `benchmark` PR deletes
#: the count from test_kimi.py and, with it, this entry (PERF.md section 7).
LEFT_OUT = {
    "test_kimi.py": {
        "test_the_new_cell_is_in_the_manifest_with_its_metrics":
            'assert (len(manifest["configs"]), len(manifest["workloads"])) '
            "== (8, 9)",
    },
}
if not FILES:  # an empty parameter set would be one silent skip
    raise RuntimeError("benchmark/tests/test_*.py matched nothing")


def _pytest(path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the child's conftest picks its own device count
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider",
             *args],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired as exc:
        out = (exc.stdout or b"")
        out = out.decode(errors="replace") if isinstance(out, bytes) else out
        pytest.fail(f"{os.path.relpath(path, ROOT)} ran past {TIMEOUT_S} s\n{out[-4000:]}")
    return proc.returncode, proc.stdout + proc.stderr


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(f) for f in FILES])
def test_benchmark_selftest_file(path):
    rel = os.path.relpath(path, ROOT)
    left_out = [f"--deselect={rel}::{case}"
                for case in LEFT_OUT.get(os.path.basename(path), ())]
    rc, out = _pytest(path, *left_out)
    tail = "\n".join(out.splitlines()[-60:])
    assert rc == 0, f"exit code {rc}\n{tail}"


@pytest.mark.parametrize(
    "name, case, stale_line",
    [(name, case, line) for name, cases in sorted(LEFT_OUT.items())
     for case, line in sorted(cases.items())])
def test_a_case_left_out_fails_by_its_stale_line_alone(name, case, stale_line):
    path = os.path.join(ROOT, "benchmark", "tests", name)
    rc, out = _pytest(f"{path}::{case}", "--tb=short")
    assert rc == 1, (
        f"{name}::{case} no longer fails (exit code {rc}): take it out of "
        f"LEFT_OUT\n{out[-2000:]}")
    failing = [line.strip() for line in out.splitlines()
               if line.startswith("    ") and "assert" in line]
    assert failing == [stale_line], out[-4000:]
