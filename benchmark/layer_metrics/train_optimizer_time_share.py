"""Share of the device's busy time in the traced window under
``train/optimizer``: the clip, the AdamW update, the parameters' update and
the EMA shadow's. Layer: trainer. Source: device trace joined with the
program's scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "optimizer")
