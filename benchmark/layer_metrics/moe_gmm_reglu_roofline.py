"""Share of its roofline that the grouped expert product reaches where the
experts are ReLU-gated and the chip holds every one of a layer.

Layer: kernels. Source: device trace. The ``%moe_gmm`` events as
``moe_gmm_roofline`` finds them (the same launch name; that metric's count is
another configuration's and stays as it is). A launch's result is ``[buffer
rows, N]``: the buffer holds every (row, expert) assignment of the call,
``images x tokens x moe_num_active_primary_experts`` rounded up to whole
tiles; the assignments to experts held here are multiplied, which is all of
them where every expert is held (``costs_smallthinker.held_share``: 1), so
the buffer's rows less its tile padding are the rows credited. N says which
launch it is: the hidden size is ``down_proj`` (K the expert width), ONE
product; the expert width is the gated first half (K the hidden size), which
multiplies the rows it read once by ``gate_proj`` AND ``up_proj`` and is
credited BOTH products (``moe_gmm_roofline`` credits that launch one, which
is why it reads low since PR 38: PERF.md section 7). Operations and bytes from
``costs_smallthinker.moe_gmm_cost``: tile padding and rows visited twice are
not credited, and no zero of the ReLU is discounted. Compute-bound at ~1,512
rows an expert. Where the configuration is not of this kind the reader finds
nothing to count by and returns None.
"""

from benchmark import costs, costs_smallthinker
from benchmark.layer_metrics import moe_gmm_roofline


def read(view):
    config = view.config
    if view.trace is None or "moe_ffn_hidden_size" not in config:
        return None
    a_row = costs.tokens(config) * config["moe_num_active_primary_experts"]
    hidden, width = config["hidden_size"], config["moe_ffn_hidden_size"]
    least = took = 0.0
    for buffer_rows, n, seconds in moe_gmm_roofline.events(view):
        rows = (buffer_rows // a_row * a_row
                * costs_smallthinker.held_share(config))
        k, products = (width, 1) if n == hidden else (hidden, 2)
        least += costs.roofline_seconds(
            costs_smallthinker.moe_gmm_cost(config, rows, k, n, products),
            view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
