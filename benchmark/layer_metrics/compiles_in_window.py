"""XLA programs compiled, or loaded from the persistent cache, inside the
window (``jax/backend_compile_duration`` events that ended in it): 0 when
warm-up covered every shape. Layer: runtime. Source: program counter (the
listener's record, checked against ``runtime.compiles``)."""

from benchmark.layer_metrics import program_record as rec


def read(view):
    window = rec.window_ns(view)
    events = rec.compile_events()
    if window is None or events is None:
        return None
    return float(len(rec.ended_in(
        [s for s in events if s.name == "jax/backend_compile_duration"],
        *window)))
