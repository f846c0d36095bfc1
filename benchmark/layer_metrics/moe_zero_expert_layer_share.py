"""Share of the held-expert layer's traces in this process whose router has
zero-compute outputs — picks that return the row times its weight and
multiply nothing: 100 for a stack whose every expert layer has them. Layer:
kernels. Source: program counter ``kernels.moe_zero_experts`` (keys
``identity``, ``none``; +1 a trace of ``HeldExpertsMlp``). It is what shows a
later change that drops the identity term or routes over the experts with
weights alone. Where the program has no such counter, or never traced an
expert layer, the reader finds nothing and returns None."""

from ddim_cold_tpu.obs import metrics


def read(view):
    by_key: dict = {}
    for series in metrics.snapshot().values():
        for key, count in series.get("kernels.moe_zero_experts/by_key",
                                     {}).items():
            by_key[key] = by_key.get(key, 0) + count
    total = sum(by_key.values())
    if not total:
        return None
    return 100.0 * by_key.get("identity", 0) / total
