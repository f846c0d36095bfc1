#!/usr/bin/env python3
"""One process, one cell, once: load, warm up, measure, check, print, exit.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exit codes: 0 with the result line last on stdout; 2 bad arguments or a
manifest that names something missing; 3 no TPU, too few chips, or a device
kind without peaks; 4 a result line that would not be read (reason on
stderr, no line printed); 1 anything that raised.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark import result_line  # noqa: E402
from benchmark.harness import Run, View, log  # noqa: E402

#: what must be beside the benchmark for the system under test to be there
PROGRAM_MARK = os.path.join(ROOT, "ddim_cold_tpu", "__init__.py")


def find_devices(chips: int):
    """The cell's devices, or SystemExit(3) with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(_refuse(f"no accelerator: jax found {devices}"))
    if len(devices) < chips:
        sys.exit(_refuse(f"the cell needs {chips} chips, jax found {devices}"))
    try:
        peaks = mf.peaks_for(devices[0].device_kind)
    except LookupError as e:
        sys.exit(_refuse(str(e)))
    return devices[:chips], peaks


def _refuse(why: str) -> int:
    print(f"benchmark/run.py: {why}", file=sys.stderr, flush=True)
    return 3


def cache_dir() -> str:
    """JAX_COMPILATION_CACHE_DIR when set, else a fixed path in the checkout
    (the path is part of the cache key: one that moves never hits). The
    program's ``enable_compile_cache`` follows the same rule and keeps a
    directory already configured, so it takes this one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def open_cell(workload: str):
    """(cell, its devices, peaks, driver module) with the cache configured:
    what ``calibrate.py`` and ``sweep.py`` share with a run."""
    cell = mf.Cell(mf.load_manifest(), workload)
    devices, peaks = find_devices(cell.chips)
    cache_dir()
    return cell, devices, peaks, mf.load_driver(cell.driver)


class MemoryWatch:
    """Peak bytes on the fullest chip while the window runs. On this runtime
    ``bytes_in_use`` counts live arrays only; the loaded programs' temporaries
    sit in the allocator's reserved region, disjoint from it (bytes_limit =
    in use + reserved + free). So a chip holds in use + reserved, the two
    read together at one instant: polled from a thread for as long as the
    ``with`` block lasts, once more as it closes, and the largest kept. (The
    allocator's own ``peak_*`` counters cover the whole process and peak at
    different times: their sum is no moment's memory.)"""

    PERIOD_S = 0.05

    def __init__(self, devices):
        self.devices = list(devices)
        self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _read(self) -> None:
        for d in self.devices:
            stats = d.memory_stats() or {}
            self.peak = max(self.peak, int(stats.get("bytes_in_use", 0))
                            + int(stats.get("bytes_reserved", 0)))

    def _poll(self) -> None:
        while not self._stop.wait(self.PERIOD_S):
            self._read()

    def __enter__(self) -> "MemoryWatch":
        self._read()
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        self._read()
        for d in self.devices:
            log(f"memory of {d}: {d.memory_stats()}")


def _windows(run: Run, driver, state):
    """(result, reduced trace or None) of the run's window or windows."""
    from benchmark import trace_reduce

    if not run.traced:
        return driver.window(run, state, run.seconds), None
    # host-clock and counter metrics come from a window with the profiler
    # off (it slows host threads); the trace from a short window of its own.
    # A cell whose traffic file gives no counters_window_s reads both from
    # the traced window.
    untraced_s = min(run.seconds,
                     float(run.traffic.get("counters_window_s", 0)))
    result = (driver.window(run, state, untraced_s)
              if untraced_s > 0 else None)
    seconds = min(run.seconds, float(run.traffic.get("trace_window_s", 5.0)))
    trace_dir = os.path.join(ROOT, ".bench_trace", run.cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with trace_reduce.tracing(trace_dir) as session:
        traced_result = driver.window(run, state, seconds)
    reduced = trace_reduce.reduce(session, len(run.devices))
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = result or traced_result
    result["traced"] = traced_result
    return result, reduced


def execute(run: Run, *, t0: float):
    """Set-up, window, close, check. Returns (result, compared, setup_s,
    memory_peak_bytes, reduced trace or None, the driver's state as ``close``
    left it)."""
    driver = mf.load_driver(run.cell.driver)
    state = driver.setup(run)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.2f} s")
    try:
        with MemoryWatch(run.devices) as watch:
            result, reduced = _windows(run, driver, state)
    finally:
        driver.close(run, state)
    compared = driver.check(run, state, result)
    return result, compared, setup_s, watch.peak, reduced, state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    if not os.path.isfile(PROGRAM_MARK):
        print("benchmark/run.py: the system under test (ddim_cold_tpu/) is "
              "not in this checkout", file=sys.stderr)
        return 2
    manifest = mf.load_manifest()
    try:
        cell = mf.Cell(manifest, args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    traced = bool(args.trace)
    expected = result_line.expected_metrics(manifest, cell.name, traced)

    devices, peaks = find_devices(cell.chips)
    log(f"cache {cache_dir()}; devices {devices}")
    run = Run(cell, args.seed, args.seconds, traced, devices, peaks)
    result, compared, setup_s, peak, reduced, _ = execute(run, t0=_T0)

    for c in compared:
        print(c, flush=True)
    correct = bool(compared) and all(c.ok for c in compared)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if traced:
        view = View(run, result, reduced)
        values = {}
        for name in expected:
            value = mf.load_reader(name).read(view)
            if value is not None:
                values[name] = value
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
    else:
        values = dict(result["e2e"], setup_s=setup_s)
    try:
        line = result_line.build(
            correct=correct, attempted=result["attempted"],
            failed=result["failed"], values=values, units=expected,
            device=device, breakdown=breakdown)
        result_line.emit(line, expected, traced=traced, chips=cell.chips)
    except result_line.BadResultLine as e:
        print(f"benchmark/run.py: no result line: {e}", file=sys.stderr,
              flush=True)
        return 4
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
