"""Share of its roofline that the grouped expert product reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%moe_gmm`` (the ``name`` of its
``pallas_call``). A launch's result is ``[buffer rows, N]``: the buffer holds
every (row, expert) assignment of the call, ``images x tokens x
num_experts_per_tok`` rounded up to whole tiles, of which the assignments to
experts held here are multiplied; how many that is depends on the routing, so
the reader credits the held share of the assignments (``costs_laguna.
held_share``: a half; over 160 thousand assignments the routed count stays
within a per cent of it). N says which product it is: the hidden size is
``down_proj`` (K the expert width), anything else ``gate_proj`` or ``up_proj``
(K the hidden size). Operations and bytes from ``costs_laguna.moe_gmm_cost``:
tile padding, rows visited twice and the buffer's unused tail are not
credited. Compute-bound at ~640 rows an expert.
"""

import re

from benchmark import costs, costs_laguna

NAME = re.compile(r"^%moe_gmm(\.\d+)* = \(?\w+\[(\d+),(\d+)\]")


def events(view):
    """(buffer rows, N, seconds) of every ``%moe_gmm`` launch in the window."""
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if m and "tpu_custom_call" in text:
                yield int(m.group(2)), int(m.group(3)), (e - s) * 1e-9


def read(view):
    if view.trace is None:
        return None
    config = view.config
    a_row = costs.tokens(config) * config["num_experts_per_tok"]
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    least = took = 0.0
    for buffer_rows, n, seconds in events(view):
        rows = buffer_rows // a_row * a_row * costs_laguna.held_share(config)
        k = width if n == hidden else hidden
        least += costs.roofline_seconds(
            costs_laguna.moe_gmm_cost(config, rows, k, n), view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
