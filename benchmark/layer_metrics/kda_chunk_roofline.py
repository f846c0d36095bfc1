"""Share of its roofline that the chunked gated delta-rule scan reaches.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%kda_chunk`` (the ``name`` of its
``pallas_call``). Each event's batch is read from its result shape,
``[images, tokens, heads x channels]``; the operations and bytes that many
images need come from ``costs_kimi.kda_cost`` at the TRUE token count: the
scan's products a head, q, k, v and o once, g once in float32, beta once. A
launch that also applies the output gate reads it as well and is credited for
it: it is told by its operands, one more than the five of the launch that
does not (q, k, v, g, beta). By these counts the launch is memory-bound (0.99
ms against 0.48 ms of MXU work at 16,385 tokens); what limits it in practice
is the per-channel decays of a chunk's pairs on the vector unit and the chain
of small float32 products that inverts a chunk's triangle, neither of which
``peaks.json`` lists. Where the program has no such launch the reader finds
nothing and returns None.
"""

import re

from benchmark import costs, costs_kimi

NAME = re.compile(r"^%kda_chunk(\.\d+)* = \(?\w+\[(\d+),")
#: operands of the launch that leaves the gate to XLA
UNGATED_OPERANDS = 5


def events(view):
    """(images, gate applied, seconds) of every ``%kda_chunk`` launch in the
    traced window."""
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if m and "tpu_custom_call" in text:
                inside = text[text.index("custom-call(") + 12:].split(")")[0]
                operands = len(re.findall(r"%[\w.\-]+", inside))
                yield (int(m.group(2)), operands > UNGATED_OPERANDS,
                       (e - s) * 1e-9)


def read(view):
    if view.trace is None:
        return None
    least = took = 0.0
    for images, gated, seconds in events(view):
        least += costs.roofline_seconds(
            costs_kimi.kda_cost(view.config, images, gated), view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
