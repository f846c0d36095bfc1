"""Share of the device's busy time in the traced window under ``trunk/mlp``:
the dense MLP sub-layers (in the ViT, ``block_tail`` with attention's output
projection in it). Layer: samplers. Source: device trace joined with the
program's scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "mlp")
