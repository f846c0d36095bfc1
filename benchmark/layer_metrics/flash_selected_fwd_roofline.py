"""Share of its roofline that the attention forward over a per-query key
selection reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%fwd_selected`` (the ``name`` of its
``pallas_call``; ``flash_masked_fwd_roofline`` pins ``%fwd_masked`` and does
not see them). A launch's result is ``[images, tokens, heads x head_dim]``.
Operations and bytes from ``costs_glm.flash_selected_fwd_cost`` at the TRUE
token count, for the SELECTED pairs only: a kernel that multiplies every
causal chunk and masks reads at most selected / causal of its own efficiency
(39.5 % at 9,217 tokens), as it should. Compute-bound. Where the program has
no such launch the reader finds nothing and returns None.
"""

import re

from benchmark import costs, costs_glm

NAME = re.compile(r"^%fwd_selected(\.\d+)* = \(?\w+\[(\d+),(\d+),(\d+)\]")


def events(view, name=NAME):
    """(images, seconds) of every launch whose instruction ``name`` matches."""
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = name.match(text)
            if m and "tpu_custom_call" in text:
                yield int(m.group(2)), (e - s) * 1e-9


def roofline(view, name, cost):
    """100 x least time / time taken over the launches ``name`` matches, the
    least time by ``cost(config, images)``; None where there is none."""
    if view.trace is None:
        return None
    least = took = 0.0
    for images, seconds in events(view, name):
        least += costs.roofline_seconds(cost(view.config, images),
                                        view.peaks)[0]
        took += seconds
    return 100.0 * least / took if took > 0 else None


def time_share(view, *names):
    """100 x the launches' time over the device's busy time (one chip, so the
    events' sum and their union are the same)."""
    if view.trace is None or view.trace.busy_s <= 0:
        return None
    took = sum(seconds for name in names for _, seconds in events(view, name))
    if took <= 0:
        return None
    return 100.0 * took / (view.trace.busy_s * view.trace.n_devices)


def read(view):
    return roofline(view, NAME, costs_glm.flash_selected_fwd_cost)
