"""Share of its roofline that the grouped expert product reaches where the
router is wider than the experts with weights and the chip holds few of them:
the launch at ~144 rows an expert, bound by the experts' own weights.

Layer: kernels. Source: device trace. The ``%moe_gmm`` events as
``moe_gmm_roofline`` finds them (the same launch name; that metric's count is
another configuration's and stays as it is: no kernel is new here). A launch's
result is ``[buffer rows, N]``: the buffer holds every (row, pick) assignment
of the call, ``images x tokens x moe_topk`` rounded up to whole tiles, of
which the assignments to experts held here are multiplied; how many that is
depends on the routing, so the reader credits the held share of the picks
(``costs_longcat.held_share``: 16 of the router's 768 outputs; picks of
zero-compute experts and of experts held elsewhere lie in the null group and
are neither multiplied nor credited). N says which launch it is: the hidden
size is ``down_proj`` (K the expert width), ONE product; the expert width is
the gated first half (K the hidden size), credited BOTH its products.
Operations and bytes from ``costs_longcat.moe_gmm_cost``, each held expert's
weights read once a launch: at 6,144 x 2,048 they are 96 % of the bytes, and
the launch is memory-bound (144 operations a byte against the chip's 240).
Tile padding, rows visited twice and the buffer's unused 98 % are not
credited. Where the configuration has no zero-compute experts the reader finds
nothing to count by and returns None.
"""

from benchmark import costs, costs_longcat
from benchmark.layer_metrics import moe_gmm_roofline


def read(view):
    config = view.config
    if view.trace is None or "zero_expert_num" not in config:
        return None
    a_row = costs.tokens(config) * config["moe_topk"]
    hidden, width = config["hidden_size"], config["expert_ffn_hidden_size"]
    least = took = 0.0
    for buffer_rows, n, seconds in moe_gmm_roofline.events(view):
        rows = buffer_rows // a_row * a_row * costs_longcat.held_share(config)
        k, products = (width, 1) if n == hidden else (hidden, 2)
        least += costs.roofline_seconds(
            costs_longcat.moe_gmm_cost(config, rows, k, n, products),
            view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
