"""Share of its roofline that the attention forward kernel reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%fwd`` (the last part of the scope
``flash_attention/fwd`` it is launched under). Each event's batch is read from
its first result shape, ``[images*heads, padded tokens, padded head size]``;
the operations and bytes that many images need come from
``costs.flash_fwd_cost`` at the TRUE token count and head size, so padding
the kernel does for itself is not credited. The share is the sum of the least
times the chip could take over the sum of the kernel's times; at these shapes
the compute peak bounds it (see ``costs.roofline_seconds``).
"""

import re

from benchmark import costs

NAME = re.compile(r"^%fwd(\.\d+)* = \(?\w+\[(\d+),")


def read(view):
    if view.trace is None:
        return None
    heads = view.config["num_heads"]
    least = took = 0.0
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if not m or "tpu_custom_call" not in text:
                continue
            images = int(m.group(2)) // heads
            least += costs.roofline_seconds(
                costs.flash_fwd_cost(view.config, images), view.peaks)[0]
            took += (e - s) * 1e-9
    if took <= 0:
        return None
    return 100.0 * least / took
