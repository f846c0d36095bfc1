"""Share of its roofline that the masked attention forward reaches where
window layers that turn q and full layers that turn nothing have the same
head count.

Layer: kernels. Source: device trace. The kernel's events are the
``tpu_custom_call`` instructions named ``%fwd_masked`` (the ``name`` of its
``pallas_call``). A launch's result is ``[images, tokens, heads x head_dim]``;
both layer kinds have the same heads, so the width cannot tell them apart
(``flash_masked_fwd_roofline`` tells Laguna's by it). The operands do: a
launch that turns q in place has five (q, k, v and the cos and sin tables of
the in-launch turn), which here is a window layer's (``rope_layout`` and
``sliding_window_layout`` agree layer by layer in the published config, and
the reader returns None for a configuration where they do not); a launch
without positions has three, a full layer's; a launch with any other count is
one this reader does not know, and the metric is then left off the line
(which a traced run of a cell that lists it is refused for) and no pairs are
guessed. Operations and bytes from
``costs_smallthinker.flash_masked_fwd_cost`` at the TRUE token count, INSIDE
the mask only: a kernel that computes chunks it could skip, whole chunks where
the mask leaves half, or fetches a K/V chunk once a query head where seven
share it, reads low, as it should. Compute-bound.
"""

import re

from benchmark import costs, costs_smallthinker

NAME = re.compile(r"^%fwd_masked(\.\d+)* = \(?\w+\[(\d+),")
#: whether the launch turns q, by its operands: q, k, v, or those and the
#: cos and sin tables of the turn
TURNED_BY_OPERANDS = {3: False, 5: True}


def events(view):
    """(images, q turned in the launch, seconds) of every ``%fwd_masked``
    launch in the traced window; ``turned`` is None for a launch of neither
    known operand count."""
    for ev in view.trace.devices.values():
        for s, e, text in ev["ops"]:
            m = NAME.match(text)
            if m and "tpu_custom_call" in text:
                # the operands' names, between the call's parenthesis and its
                # target: a trace writes each with its type and tiled layout
                # (``bf16[1,16130,512]{2,1,0:T(8,128)(2,1)} %fusion.7``), the
                # compiler's text the bare name, so no parenthesis is counted
                inside = text[text.index("custom-call(") + 12:
                              text.index("custom_call_target")]
                operands = len(re.findall(r"%[\w.\-]+", inside))
                yield (int(m.group(2)), TURNED_BY_OPERANDS.get(operands),
                       (e - s) * 1e-9)


def read(view):
    config = view.config
    if (view.trace is None or "rope_layout" not in config
            or config["rope_layout"] != config["sliding_window_layout"]):
        return None
    least = took = 0.0
    for images, turned, seconds in events(view):
        if turned is None:
            return None
        least += costs.roofline_seconds(
            costs_smallthinker.flash_masked_fwd_cost(config, images, turned),
            view.peaks)[0]
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
