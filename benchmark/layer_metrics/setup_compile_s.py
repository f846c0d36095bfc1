"""Seconds of set-up in which JAX was tracing, lowering, compiling or loading
a program from the persistent cache: the union of the program's ``jax/*``
events (its ``jax.monitoring`` listener) that ended before the window opened.
Layer: runtime. Source: program counter (the listener's record, checked
against ``runtime.compiles``)."""

from benchmark.layer_metrics import program_record as rec


def read(view):
    window = rec.window_ns(view)
    events = rec.compile_events()
    if window is None or events is None:
        return None
    return rec.union_s([s for s in events if s.t1 <= window[0]])
