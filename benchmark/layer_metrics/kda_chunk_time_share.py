"""Share of the device's busy time in the traced window that the chunked
gated delta-rule scan takes. Layer: kernels. Source: device trace (the
``%kda_chunk`` events of ``kda_chunk_roofline`` over ``busy_s``; one chip, so
the events' sum and their union are the same)."""

from benchmark.layer_metrics import kda_chunk_roofline


def read(view):
    if view.trace is None or view.trace.busy_s <= 0:
        return None
    took = sum(seconds for *_, seconds in kda_chunk_roofline.events(view))
    if took <= 0:
        return None
    return 100.0 * took / (view.trace.busy_s * view.trace.n_devices)
