"""Share of its roofline that the sparse-attention indexer's score launch
reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%dsa_index`` (the ``name`` of its
``pallas_call``); a launch's result is the float32 scores ``[images, tokens in
whole blocks, the same]``. Operations and bytes from
``costs_glm.dsa_index_cost`` at the TRUE token count, causal pairs only: block
padding and the half of each diagonal tile above the diagonal are not
credited. Compute-bound (65,000 operations a 4-byte score).
"""

import re

from benchmark import costs_glm
from benchmark.layer_metrics import flash_selected_fwd_roofline as base

NAME = re.compile(r"^%dsa_index(\.\d+)* = \(?\w+\[(\d+),(\d+),(\d+)\]")


def read(view):
    return base.roofline(view, NAME, costs_glm.dsa_index_cost)
