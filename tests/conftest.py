"""Test harness: 8 virtual CPU devices (SURVEY.md §4 'distributed without a cluster').

Must set XLA flags before jax is imported anywhere; pytest loads conftest
before collecting test modules, so this is the single chokepoint.
"""

import os

# the suite runs on the CPU backend whatever the ambient JAX_PLATFORMS says;
# the XLA flag must be set pre-import to get the 8 virtual devices
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Parity tests compare against float32 torch/numpy oracles; this JAX build's
# default matmul precision is reduced (the TPU-friendly default the framework
# keeps for training/bench), so pin full f32 dots for the test suite.
jax.config.update("jax_default_matmul_precision", "float32")

# The suite's wall time is dominated by ~30 jit compiles of tiny models; a
# persistent compilation cache makes re-runs (the common local case) start
# nearly compile-free. Fresh clones still pay the first-compile cost once.
# Same placement rule as the program (utils/platform.enable_compile_cache):
# JAX_COMPILATION_CACHE_DIR from outside wins, else the suite's own directory.
from ddim_cold_tpu.utils.platform import enable_compile_cache  # noqa: E402

enable_compile_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))

import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# In-process `trainer.run` exercises the full composite (pjit train steps +
# loader threads + logging + checkpoint I/O) inside the pytest interpreter.
# On some hosts that composite flakily corrupts the native heap and takes the
# whole pytest process down with SIGSEGV/SIGABRT, losing every result after
# it. Tests marked `isolated` therefore run in a fresh subprocess: a native
# crash becomes an ordinary test failure and the rest of the suite survives.
# The same corruption occasionally DEADLOCKS the child instead of crashing
# it; the subprocess timeout below exists to turn that wedge into the same
# ordinary failure before it eats the tier-1 wall budget: 150 s a child
# against the 1,470 s the gate's run is cut at (six workers, a file a worker;
# the command is .github/workflows/ci.yml's). A child of a healthy run takes
# 8–87 s under that load (PR 44), so a wedged one costs its worker ~100 s.
_ISOLATED_CHILD_ENV = "DDIM_COLD_TPU_ISOLATED_CHILD"
_ISOLATED_TIMEOUT_S = float(os.environ.get("DDIM_COLD_ISOLATED_TIMEOUT_S", "150"))
# Suite-wide cap on signal-death retries. A single flaky crash gets its one
# retry; a host where the native crash is DETERMINISTIC (dozens of isolated
# tests die every run) must not pay 2× child runtime per crash — 26 children
# twice over is most of a worker's share of the 1,470 s. Once the budget is
# spent, further signal deaths fail immediately, exactly as before the retry
# existed.
_retry_budget = int(os.environ.get("DDIM_COLD_ISOLATED_RETRIES", "3"))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "isolated: run this test in a fresh pytest subprocess so a native "
        "crash in the in-process trainer cannot kill the whole suite",
    )
    config.addinivalue_line("markers", "slow: long-running test (tier-2)")


def pytest_runtest_protocol(item, nextitem):
    if item.get_closest_marker("isolated") is None:
        return None
    if os.environ.get(_ISOLATED_CHILD_ENV):
        return None  # already inside the child; run normally
    hook = item.ihook
    hook.pytest_runtest_logstart(nodeid=item.nodeid, location=item.location)
    start = time.time()
    env = dict(os.environ, **{_ISOLATED_CHILD_ENV: "1"})
    cmd = [sys.executable, "-m", "pytest", "-q", "-x",
           "-p", "no:cacheprovider", item.nodeid]

    def attempt():
        """Run the child once → (returncode, output, timed_out)."""
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, env=env,
                cwd=str(item.config.rootpath), timeout=_ISOLATED_TIMEOUT_S,
            )
            return proc.returncode, (proc.stdout or "") + (proc.stderr or ""), False
        except subprocess.TimeoutExpired as exc:
            out = ((exc.stdout or b"").decode(errors="replace")
                   + f"\nisolated subprocess timed out after {_ISOLATED_TIMEOUT_S:g}s")
            return -1, out, True

    rc, out, timed_out = attempt()
    flaky_note = None
    global _retry_budget
    if rc < 0 and not timed_out and _retry_budget > 0:
        # The documented flaky-host class: the child was KILLED BY A SIGNAL
        # (SIGSEGV/SIGABRT from the native-heap corruption this runner exists
        # to contain). Retry exactly once — a real regression that crashes
        # deterministically crashes the retry too and still fails; ordinary
        # assertion failures (rc > 0) and deadlocks (the timeout path) are
        # never retried, so nothing real is masked.
        _retry_budget -= 1
        flaky_note = (f"first attempt died with signal {-rc}; "
                      "retried once (flaky-host native-crash class)")
        rc, out, timed_out = attempt()
    duration = time.time() - start
    if rc == 0 and re.search(r"\b1 skipped\b", out) and not re.search(r"\b1 passed\b", out):
        outcome = "skipped"
        longrepr = (str(item.path), item.location[1] or 0,
                    "skipped inside isolated subprocess")
    elif rc == 0:
        outcome, longrepr = "passed", None
    else:
        outcome = "failed"
        tail = "\n".join(out.splitlines()[-40:])
        why = (f"isolated subprocess died with signal {-rc}" if rc < 0
               else f"isolated subprocess exited with code {rc}")
        if flaky_note:
            why = f"{flaky_note}; retry then {why}"
        longrepr = f"{why}\n{tail}"
    keywords = {item.name: 1}
    sections = []
    if flaky_note:
        keywords["flaky-retry"] = 1
        sections.append(("flaky-retry", flaky_note))
    report = pytest.TestReport(
        nodeid=item.nodeid, location=item.location,
        keywords=keywords, outcome=outcome, longrepr=longrepr,
        when="call", sections=sections, duration=duration,
        start=start, stop=start + duration,
    )
    hook.pytest_runtest_logreport(report=report)
    # The in-process setup/teardown cycle was skipped, but earlier items'
    # module/class finalizers are still parked on the SetupState stack waiting
    # for "the next item" to tear them down. Pop everything nextitem doesn't
    # need, or the next in-process test errors at setup with "previous item
    # was not torn down properly".
    item.session._setupstate.teardown_exact(nextitem)
    hook.pytest_runtest_logfinish(nodeid=item.nodeid, location=item.location)
    return True


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(scope="session")
def kernel_traces():
    """The 200px kernel-entry traces (graftcheck's kernels/memory layers),
    built once per session — test_kernel_checks and test_memory_checks
    both walk them, and the abstract trace is the expensive part."""
    from ddim_cold_tpu.analysis import entries

    return entries.kernel_traces()


@pytest.fixture(scope="session")
def synthetic_image_dir(tmp_path_factory):
    """A 10-image jpg folder (the integration-test dataset, SURVEY.md §4)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("synthetic_jpgs")
    rs = np.random.RandomState(42)
    for i in range(10):
        arr = rs.randint(0, 255, size=(96, 80, 3), dtype=np.uint8)
        Image.fromarray(arr).save(root / f"{i}.jpg")
    return str(root)
