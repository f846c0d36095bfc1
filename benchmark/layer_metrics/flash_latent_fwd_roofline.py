"""Share of its roofline that the latent attention forward (a score in two
parts, the rotated key part shared by all the heads) reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%fwd_latent`` (the ``name`` of its
``pallas_call``; the ``%fwd_selected``, ``%fwd_masked`` and ``%fwd`` readers
do not see them). A launch's result is ``[images, tokens, heads x v_head_dim]``.
Operations and bytes from ``costs_pangu.flash_latent_fwd_cost`` at the TRUE
token count, for the causal pairs at the model's own head sizes: the 64
rotated dims cost the kernel a whole 128-deep pass of the 128-wide array, so
at 128 + 64 / 128 it cannot read above (192 + 128) / (256 + 128) = 83 %.
Compute-bound. Where the program has no such launch the reader finds nothing
and returns None.
"""

import re

from benchmark import costs_pangu
from benchmark.layer_metrics import flash_selected_fwd_roofline as base

NAME = re.compile(r"^%fwd_latent(\.\d+)* = \(?\w+\[(\d+),(\d+),(\d+)\]")


def read(view):
    return base.roofline(view, NAME, costs_pangu.flash_latent_fwd_cost)
