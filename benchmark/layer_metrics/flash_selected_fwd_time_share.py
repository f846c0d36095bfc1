"""Share of the device's busy time in the traced window that the attention
forward over the selection takes. Layer: kernels. Source: device trace (the
``%fwd_selected`` events of ``flash_selected_fwd_roofline`` over ``busy_s``)."""

from benchmark.layer_metrics import flash_selected_fwd_roofline as base


def read(view):
    return base.time_share(view, base.NAME)
