"""Share of the device's busy time in the traced window under ``trunk/mlp``
(the second norm, the MLP and its residual), forward and backward.
Layer: trainer. Source: device trace joined with the program's scope map
(``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "mlp")
