"""Share of the device's busy time in the traced window that the
sparse-attention indexer's two launches take: the scores (``%dsa_index``) and
the threshold selection (``%dsa_select``). Layer: kernels. Source: device
trace (the events of ``dsa_index_roofline`` and ``dsa_select_roofline`` over
``busy_s``). The indexer's projections are XLA GEMMs and are not in it."""

from benchmark.layer_metrics import (
    dsa_index_roofline, dsa_select_roofline, flash_selected_fwd_roofline as base)


def read(view):
    return base.time_share(view, dsa_index_roofline.NAME,
                           dsa_select_roofline.NAME)
