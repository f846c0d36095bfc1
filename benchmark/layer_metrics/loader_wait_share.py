"""Share of the window the training loop spent waiting in ``next(batch)``,
the benchmark's own span around the loader (decode, collate and the
host-to-device copy run ahead in threads; this is what they failed to hide).
Layer: data. Source: program span."""


def read(view):
    t0, t1 = view.result.get("t0"), view.result.get("t1")
    if t0 is None or view.window_s <= 0:
        return None
    return 100.0 * view.spans.total("next_batch", t0, t1) / view.window_s
