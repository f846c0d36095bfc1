"""Share of the held-expert layer's traces in this process whose router read
another tensor than the experts — the layer's input, before attention — and
not the rows the experts read: 100 for a stack that puts its router first.
Layer: kernels. Source: program counter ``kernels.moe_route_source`` (keys
``layer_input``, ``expert_input``; +1 a trace of ``HeldExpertsMlp``). It is
what shows a later change that moves the router behind attention. Where the
program has no such counter, or never traced an expert layer, the reader
finds nothing and returns None."""

from ddim_cold_tpu.obs import metrics


def read(view):
    by_key: dict = {}
    for series in metrics.snapshot().values():
        for key, count in series.get("kernels.moe_route_source/by_key",
                                     {}).items():
            by_key[key] = by_key.get(key, 0) + count
    total = sum(by_key.values())
    if not total:
        return None
    return 100.0 * by_key.get("layer_input", 0) / total
