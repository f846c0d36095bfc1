"""Share of its roofline that the selective-scan kernel reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%ssm_scan`` (the ``name`` of its
``pallas_call``). Each event's batch is read from its result shape,
``[images, tokens, channels]``; the operations and bytes that many images
need come from ``costs_hybrid.ssm_scan_cost`` at the TRUE token count. The
share is the sum of the least times the chip could take over the sum of the
kernel's times. ``costs.roofline_seconds`` finds it memory-bound (0.2 ms a
launch at 4 x 1,025 x 5,120); what limits the kernel in practice is the
vector unit, which ``peaks.json`` does not list, so a share of 5 to 15 % is
what a sound kernel reads.
"""

import re

from benchmark import costs, costs_hybrid

NAME = re.compile(r"^%ssm_scan(\.\d+)* = \(?\w+\[(\d+),")


def events(view):
    """(images, seconds) of every ``%ssm_scan`` launch in the traced window."""
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if m and "tpu_custom_call" in text:
                yield int(m.group(2)), (e - s) * 1e-9


def read(view):
    if view.trace is None:
        return None
    least = took = 0.0
    for images, seconds in events(view):
        least += costs.roofline_seconds(
            costs_hybrid.ssm_scan_cost(view.config, images), view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
