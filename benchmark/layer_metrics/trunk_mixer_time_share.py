"""Share of the device's busy time in the traced window under the scopes of
the ``mixer`` layer (``trunk/mamba``, ``mamba2``, ``kda``): the scan's launch
and the projections, convolutions and gates around it. Layer: samplers.
Source: device trace joined with the program's scope map
(``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "mixer")
