"""The command refuses to measure where it must: no TPU, too few chips, an
unknown device kind, or a checkout without the system under test. Each exits
non-zero and prints no result line."""

import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf

ROOT = mf.ROOT
ARGS = ["--workload", "flower200_sample_k20", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    proc = subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)
    return proc.returncode, proc.stdout


def test_no_accelerator_no_result():
    code, out = _run(ROOT)
    assert code == 3
    assert "{" not in out


def test_too_few_chips_is_refused(monkeypatch):
    import jax

    from benchmark import run

    monkeypatch.setattr(jax, "devices", lambda: [_Fake()])
    with pytest.raises(SystemExit) as e:
        run.find_devices(4)
    assert e.value.code == 3


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    from benchmark import run

    monkeypatch.setattr(jax, "devices", lambda: [_Fake(kind="TPU v9")])
    with pytest.raises(SystemExit) as e:
        run.find_devices(1)
    assert e.value.code == 3
    with pytest.raises(LookupError):
        mf.peaks_for("_source")


class _Fake:
    platform = "tpu"

    def __init__(self, kind="TPU v5 lite"):
        self.device_kind = kind


def test_checkout_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code, out = _run(tmp_path)
    assert code != 0
    assert "{" not in out


def test_memory_watch_keeps_the_largest_joint_reading():
    """in use + reserved read together: 10 + 0, then 2 + 5, never 10 + 5."""
    import time

    from benchmark.run import MemoryWatch

    class Chip:
        def __init__(self):
            self.readings = [(10, 0), (2, 5), (1, 1)]

        def memory_stats(self):
            a, b = self.readings.pop(0) if len(self.readings) > 1 else self.readings[0]
            return {"bytes_in_use": a, "bytes_reserved": b,
                    "peak_bytes_in_use": 10, "peak_bytes_reserved": 5}

    with MemoryWatch([Chip()]) as watch:
        time.sleep(4 * MemoryWatch.PERIOD_S)
    assert watch.peak == 10
