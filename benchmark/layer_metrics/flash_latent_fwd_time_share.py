"""Share of the device's busy time in the traced window that the latent
attention forward takes. Layer: kernels. Source: device trace (the
``%fwd_latent`` events of ``flash_latent_fwd_roofline`` over ``busy_s``)."""

from benchmark.layer_metrics import flash_latent_fwd_roofline as latent
from benchmark.layer_metrics import flash_selected_fwd_roofline as base


def read(view):
    return base.time_share(view, latent.NAME)
