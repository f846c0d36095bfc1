"""Share of the device's busy time in the traced window under ``trunk/route``:
the router's logits, score, top-k, sort and group bounds. Layer: samplers.
Source: device trace joined with the program's scope map
(``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "route")
