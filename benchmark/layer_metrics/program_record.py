"""The program's own record, as the readers of its layer metrics see it.

Reader and program share one process and one clock: the program's layer
spans (``ddim_cold_tpu.obs.spans.layer_spans()``) carry raw
``time.perf_counter_ns()`` readings, the window the harness hands a reader
(``view.result["t0"]``/``["t1"]``) is in ``time.perf_counter`` seconds. In a
traced run of a training cell that window is the untraced counters window, so
the spans read here were recorded with the profiler off.

A program without the recorder (a checkout from before it) gives ``None``
everywhere here, and the readers then return ``None``: nothing to read.
"""

from __future__ import annotations


def closed(name: str):
    """The program's closed layer spans called ``name`` (or, with a trailing
    ``*``, starting with what stands before it), oldest first; ``None`` when
    the program keeps no such record."""
    from ddim_cold_tpu.obs import spans

    record = getattr(spans, "layer_spans", None)
    if record is None:
        return None
    if name.endswith("*"):
        return [s for s in record()
                if s.t1 is not None and s.name.startswith(name[:-1])]
    return [s for s in record() if s.t1 is not None and s.name == name]


def window_ns(view):
    """(t0, t1) of the window in the spans' nanoseconds, or ``None``."""
    t0, t1 = view.result.get("t0"), view.result.get("t1")
    if t0 is None or t1 is None or t1 <= t0:
        return None
    return int(t0 * 1e9), int(t1 * 1e9)


def inside_s(spans, lo: int, hi: int) -> float:
    """Seconds of ``spans`` that fall inside [lo, hi]."""
    return sum(max(0, min(s.t1, hi) - max(s.t0, lo)) for s in spans) / 1e9


def ended_in(spans, lo: int, hi: int) -> list:
    return [s for s in spans if lo <= s.t1 <= hi]


def mean_ms(spans):
    """Mean length of ``spans`` in milliseconds, ``None`` of none."""
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) / 1e6


def union_s(spans) -> float:
    """Seconds covered by at least one of ``spans``: nested events (a trace
    inside a trace, a cache load inside a compile) count once."""
    total, end = 0, None
    for t0, t1 in sorted((s.t0, s.t1) for s in spans):
        if end is None or t0 > end:
            total, end = total + (t1 - t0), t1
        elif t1 > end:
            total, end = total + (t1 - end), t1
    return total / 1e9


def counter(name: str):
    """A program counter summed over the scopes that emit it, or ``None``."""
    from ddim_cold_tpu.obs import metrics

    values = [series[name] for series in metrics.snapshot().values()
              if name in series]
    return sum(values) if values else None


def compile_events():
    """The ``jax/*`` spans of the program's compile listener, or ``None``
    when there is no listener, or when the ring has dropped some (the
    ``runtime.compiles`` counter then knows of more backend compiles than
    the record holds, and a sum over the record would read low)."""
    events = closed("jax/*")
    if not events:
        return None
    held = sum(1 for s in events if s.name == "jax/backend_compile_duration")
    if (counter("runtime.compiles") or 0) > held:
        return None
    return events
