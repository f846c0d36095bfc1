"""Seconds of set-up the host spent placing the training state on the mesh:
the sum of the program's ``parallel/place_state`` spans
(``parallel.shard_train_state``: parameters, optimizer moments, the EMA
shadow) inside the stretch ``setup_s`` counts. 0.0 for a program that has no
such span: the time is then in ``setup_unattributed_s``. Layer: parallel.
Source: program span."""

from benchmark.layer_metrics import program_record as rec
from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    if setup is None:
        return None
    return rec.inside_s(setup.named("parallel/place_state"),
                        setup.lo, setup.hi)
