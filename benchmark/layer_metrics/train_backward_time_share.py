"""Share of the device's busy time in the traced window on instructions whose
path goes through a ``transpose(``: the backward of every layer, the layers
outside the table included. Layer: trainer. Source: device trace joined with
the program's scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.backward_share(view)
