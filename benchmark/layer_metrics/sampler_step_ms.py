"""Device busy time in the traced window per scan step executed in it.
Layer: samplers. Source: device trace (busy) and the driver's own count of
scan steps in the traced window (calls x steps a call)."""


def read(view):
    steps = view.traced.get("counters", {}).get("scan_steps")
    if not steps or view.trace is None:
        return None
    return view.trace.busy_s / steps * 1e3
