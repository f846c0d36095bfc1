"""StallWatchdog (utils/watchdog.py) — the bounded-liveness guard for code
that waits on the device.

os._exit semantics force subprocess tests: the abort path must kill a
process whose main thread never re-enters the interpreter.
"""

import subprocess
import sys
import time

import pytest

PRELUDE = """
import sys, time
sys.path.insert(0, {repo!r})
from ddim_cold_tpu.utils.watchdog import StallWatchdog
"""


def run_script(body, repo, timeout=30):
    code = PRELUDE.format(repo=repo) + body
    t0 = time.time()
    proc = subprocess.run([sys.executable, "-c", code], timeout=timeout,
                          capture_output=True, text=True)
    return proc, time.time() - t0


@pytest.fixture()
def repo():
    import os

    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_stall_aborts_with_partial_artifact(tmp_path, repo):
    marker = tmp_path / "partial.txt"
    body = f"""
def on_abort(label, silent):
    open({str(marker)!r}, "w").write(f"{{label}}|{{silent:.1f}}")
wd = StallWatchdog(0.4, on_abort=on_abort, name="t").start()
wd.mark("the-silent-op")
time.sleep(30)
"""
    proc, dt = run_script(body, repo)
    assert proc.returncode == 3
    assert dt < 10, f"abort took {dt:.1f}s for a 0.4s budget"
    assert marker.read_text().startswith("the-silent-op|")
    assert "STALL" in proc.stderr


def test_marks_keep_it_alive_and_done_disarms(repo):
    body = """
wd = StallWatchdog(0.6, name="t").start()
for i in range(8):
    wd.mark(f"step {i}")
    time.sleep(0.25)  # each window < 0.6s: never stalls
wd.done()
time.sleep(1.0)  # disarmed: silence after done() must not abort
print("finished")
"""
    proc, _ = run_script(body, repo)
    assert proc.returncode == 0
    assert "finished" in proc.stdout


def test_budget_stretches_one_window(repo):
    body = """
wd = StallWatchdog(0.3, name="t").start()
wd.mark("long first compile", budget_s=5.0)
time.sleep(1.2)  # > stall_s, < budget: must survive
wd.mark("fast op")           # budget does NOT carry to the next window
wd.done()
print("survived")
"""
    proc, _ = run_script(body, repo)
    assert proc.returncode == 0
    assert "survived" in proc.stdout


def test_disabled_when_nonpositive(repo):
    body = """
wd = StallWatchdog(0.0, name="t").start()  # stall_s <= 0: disarmed
time.sleep(0.5)
print("no thread, no abort")
"""
    proc, _ = run_script(body, repo)
    assert proc.returncode == 0


def test_soft_mode_calls_abort_without_exit(repo):
    """exit_code=None (the serving engine's mode): on stall the watchdog
    fires on_abort ONCE, stops itself, and the process lives on — waiters
    get failed by the hook instead of the host dying. In-process test: no
    os._exit to dodge."""
    from ddim_cold_tpu.utils.watchdog import StallWatchdog

    calls = []
    wd = StallWatchdog(0.2, exit_code=None,
                       on_abort=lambda label, silent: calls.append(label),
                       name="soft").start()
    wd.mark("wedged-op")
    deadline = time.time() + 10
    while not calls and time.time() < deadline:
        time.sleep(0.05)
    assert calls == ["wedged-op"]
    time.sleep(0.3)  # watchdog stopped itself: no second abort, no exit
    assert calls == ["wedged-op"]
    assert wd._state["done"]  # the thread retired after the one abort
