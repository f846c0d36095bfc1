"""Mean time the placing stage needs for one batch (``data/place/work``: the
driver's ``place`` callback, which calls ``parallel.shard_batch``, the host's
side of the host-to-device copy), over the batches placed in the window.
Layer: parallel. Source: program span."""

from benchmark.layer_metrics import program_record as rec


def read(view):
    window = rec.window_ns(view)
    work = rec.closed("data/place/work")
    if window is None or not work:
        return None
    return rec.mean_ms(rec.ended_in(work, *window))
