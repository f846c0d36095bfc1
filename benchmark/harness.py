"""What a run hands to its driver and to the per-layer readers."""

from __future__ import annotations

import contextlib
import sys
import time


def log(msg: str) -> None:
    """Progress goes to stderr; stdout carries the compared numbers and, last,
    the result line."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class SpanLog:
    """The benchmark's own host spans around its calls into each layer: kept
    in memory as (name, start_s, end_s) on ``time.perf_counter`` and, in a
    traced run, also written into the profiler's trace as ``bench/<name>`` so
    that idle gaps of the device can be named by what the host was doing."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation("bench/" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def total(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> float:
        """Seconds spent in spans called ``name``, clipped to [lo, hi]."""
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for n, s, e in self.records if n == name)


class Run:
    """One process, one cell, once."""

    def __init__(self, cell, seed: int, seconds: float, traced: bool,
                 devices, peaks: dict):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = bool(traced)
        self.devices = list(devices)
        self.peaks = peaks
        self.spans = SpanLog(traced)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


class Compared:
    """One number of `correct` beside its limit (value <= limit passes)."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit

    def __str__(self) -> str:
        return (f"compared {self.name} = {self.value:.6g}  limit {self.limit:.6g}"
                f"  {'ok' if self.ok else 'FAIL'}")


class View:
    """What a per-layer reader may read: the window's result (counters and
    the driver's own numbers, taken with the profiler off where the cell has
    a counters window), the traced window's result, the host spans, the
    reduced trace, the cell and the peaks. A reader that finds nothing to read
    returns None and its metric is left out of the line."""

    def __init__(self, run: Run, result: dict, trace):
        self.run = run
        self.cell = run.cell
        self.config = run.cell.config
        self.traffic = run.cell.traffic
        self.peaks = run.peaks
        self.result = result
        self.counters = result.get("counters", {})
        #: the traced window's own result (the same one where the cell has
        #: no separate counters window)
        self.traced = result.get("traced", result)
        self.spans = run.spans
        self.trace = trace            # trace_reduce.Reduced or None
        self.window_s = result["window_s"]
