"""Share of the device's busy time in the traced window under the scopes of
the ``attention`` layer (``trunk/attn``, ``attn_full``, ``attn_window``,
``mla``, ``dsa_index``): the launches and the projections, norms and turns
around them. Layer: samplers. Source: device trace joined with the program's
scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.share(view, "attention")
