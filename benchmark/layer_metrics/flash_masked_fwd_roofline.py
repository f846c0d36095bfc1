"""Share of its roofline that the masked attention forward reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%fwd_masked`` (the ``name`` of its
``pallas_call``; ``flash_fwd_roofline`` pins ``%fwd`` and does not see them).
A launch's result is ``[images, tokens, heads x head_dim]``: the head count
says which layer kind it is (``costs_laguna.window_of``). Operations and
bytes from ``costs_laguna.flash_masked_fwd_cost`` at the TRUE token count,
INSIDE the mask only: a kernel that computes chunks it could skip, or whole
chunks where the mask leaves half, reads low, as it should. Compute-bound.
"""

import re

from benchmark import costs, costs_laguna

NAME = re.compile(r"^%fwd_masked(\.\d+)* = \(?\w+\[(\d+),(\d+),(\d+)\]")


def events(view):
    """(images, heads, seconds) of every ``%fwd_masked`` launch."""
    hd = view.config["head_dim"]
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if m and "tpu_custom_call" in text:
                yield int(m.group(2)), int(m.group(4)) // hd, (e - s) * 1e-9


def read(view):
    if view.trace is None:
        return None
    least = took = 0.0
    for images, heads, seconds in events(view):
        least += costs.roofline_seconds(
            costs_laguna.flash_masked_fwd_cost(view.config, images, heads),
            view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
