"""Share of device busy time in which an all-reduce runs (the gradient
exchange of data-parallel training), per device the union of its all-reduce
events on the op and async-op lines, mean over the cell's devices.
Layer: parallel. Source: device trace."""

from benchmark.trace_reduce import COLLECTIVE


def read(view):
    if view.trace is None or view.trace.busy_s <= 0:
        return None
    seconds, count = view.trace.op_seconds(
        lambda text: bool(COLLECTIVE.match(text)), lines=("ops", "async"))
    if count == 0:
        return None
    return 100.0 * seconds / view.trace.busy_s
