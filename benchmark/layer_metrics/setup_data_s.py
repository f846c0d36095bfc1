"""Seconds of set-up under the data layer's spans: the union of ``data/*``
(``data/dataset/open`` with the native decoder's ``data/native/load`` and
``data/native/build`` inside it, the decode pass's ``data/decode/work``, the
first batches' ``data/place/work`` and ``data/place/get_wait``) inside the
stretch ``setup_s`` counts. A compile inside a first placement is under it
too. Layer: data. Source: program span."""

from benchmark.layer_metrics import program_record as rec
from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    if setup is None:
        return None
    return rec.union_s(setup.named("data/"))
