"""Mean time the decode stage needs to make one host batch
(``data/decode/work``: index gather, decode or cache read, collate), over
the batches made in the window: what the loader costs a batch when nothing
makes it wait. Layer: data. Source: program span."""

from benchmark.layer_metrics import program_record as rec


def read(view):
    window = rec.window_ns(view)
    work = rec.closed("data/decode/work")
    if window is None or not work:
        return None
    return rec.mean_ms(rec.ended_in(work, *window))
