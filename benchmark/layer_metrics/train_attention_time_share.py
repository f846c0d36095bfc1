"""Share of the device's busy time in the traced window under the scopes of
the ``attention`` layer (``trunk/attn`` in the two ViT configurations):
forward and backward, the kernels included (``fwd`` and ``dqkv`` carry the
scope on their path). Layer: trainer. Source: device trace joined with the
program's scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "attention")
