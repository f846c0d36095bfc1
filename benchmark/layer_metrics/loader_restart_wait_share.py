"""Share of the window the training loop spent blocked on the placed-batch
queue for the first two batches of an epoch (``data/place/get_wait`` with
``batch`` 0 or 1): what the restart of both pipelines at each epoch boundary
costs, and what prefetching across it would give back. ``loader_wait_share``
times the whole wait from outside and cannot tell a restart from a loader
that is slow all epoch long. Layer: data. Source: program span."""

from benchmark.layer_metrics import program_record as rec

#: a pipeline restarts empty: its first batch is not made yet, its second
#: not placed yet, when the loop asks for them
RESTART_BATCHES = 2


def read(view):
    window = rec.window_ns(view)
    stage = rec.closed("data/place/*")
    if window is None or not stage:
        return None
    waits = [s for s in stage if s.name == "data/place/get_wait"
             and s.attrs.get("batch", RESTART_BATCHES) < RESTART_BATCHES]
    return 100.0 * rec.inside_s(waits, *window) / view.window_s
