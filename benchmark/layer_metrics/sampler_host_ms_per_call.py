"""Host time of one sampler call outside the device's work: its
``sampler/init`` (start noise, placement) plus ``sampler/dispatch`` (the
jitted scan's call until it returns), mean over the ``sampler/call`` spans
inside the window. Layer: samplers. Source: program span."""

from benchmark.layer_metrics import program_record as rec


def read(view):
    window = rec.window_ns(view)
    calls = rec.closed("sampler/call")
    if window is None or not calls:
        return None
    lo, hi = window
    ids = {s.span_id for s in calls if lo <= s.t0 and s.t1 <= hi}
    if not ids:
        return None
    parts = [s for name in ("sampler/init", "sampler/dispatch")
             for s in rec.closed(name) if s.parent_id in ids]
    return sum(s.t1 - s.t0 for s in parts) / len(ids) / 1e6
