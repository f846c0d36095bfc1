"""Share of the device's busy time in the traced window on instructions that
the program's scope map puts in a layer of its table (attention, mlp,
optimizer, ...): the gauge of the map itself. What is left is ``outside``
(patch embedding, head, loss, the cold degradation) or ``unmapped``. 0.0 on a
program without ``obs.scopes``. Layer: runtime. Source: device trace joined
with the program's scope map (``scope_record``)."""

from benchmark.layer_metrics import scope_record


def read(view):
    return scope_record.attributed_share(view)
