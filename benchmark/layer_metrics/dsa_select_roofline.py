"""Share of its roofline that the sparse-attention threshold selection
reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%dsa_select`` (the ``name`` of its
``pallas_call``); a launch's result is the int8 selection ``[images, tokens in
whole blocks, the same]``. Bytes from ``costs_glm.dsa_select_cost``: the
causal pairs' float32 scores in, their int8 selection out, once; no matmul.
Memory-bound by that count, so the share says how far the 32 passes of
compare-and-count over the resident rows are from a single pass at the
memory's speed.
"""

import re

from benchmark import costs_glm
from benchmark.layer_metrics import flash_selected_fwd_roofline as base

NAME = re.compile(r"^%dsa_select(\.\d+)* = \(?\w+\[(\d+),(\d+),(\d+)\]")


def read(view):
    return base.roofline(view, NAME, costs_glm.dsa_select_cost)
