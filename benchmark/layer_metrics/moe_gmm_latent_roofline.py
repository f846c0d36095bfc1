"""Share of its roofline that the grouped expert product reaches where the
experts are ungated and live in a latent of their own width.

Layer: kernels. Source: device trace. The ``%moe_gmm`` events as
``moe_gmm_roofline`` finds them (the same launch name; that metric's count is
another configuration's and stays as it is). A launch's result is ``[buffer
rows, N]``: the buffer holds every (row, expert) assignment of the call,
``images x tokens x num_experts_per_tok`` rounded up to whole tiles, of which
the assignments to experts held here are multiplied; how many that is depends
on the routing, so the reader credits the held share of the assignments
(``costs_nemotron.held_share``: a quarter). N says which product it is: the
latent width is ``down_proj`` (K the expert width), anything else ``up_proj``
(K the latent width). ONE product a launch, the first with the squared ReLU
on its result: operations and bytes from ``costs_nemotron.moe_gmm_cost``;
tile padding, rows visited twice and the buffer's unused three quarters are
not credited. Compute-bound at ~704 rows an expert. Where the configuration
has no latent the reader finds nothing to count by and returns None.
"""

from benchmark import costs, costs_nemotron
from benchmark.layer_metrics import moe_gmm_roofline


def read(view):
    config = view.config
    if view.trace is None or "moe_latent_size" not in config:
        return None
    a_row = costs.tokens(config) * config["num_experts_per_tok"]
    latent, width = config["moe_latent_size"], config["moe_intermediate_size"]
    least = took = 0.0
    for buffer_rows, n, seconds in moe_gmm_roofline.events(view):
        rows = (buffer_rows // a_row * a_row
                * costs_nemotron.held_share(config))
        k = width if n == latent else latent
        least += costs.roofline_seconds(
            costs_nemotron.moe_gmm_cost(config, rows, k, n), view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
