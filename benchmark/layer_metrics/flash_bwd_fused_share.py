"""Share of the flash backward's traces in this process that took the one
``dqkv`` launch and not ``dq`` + ``dkv``: 100 in the data-parallel training
cell. Layer: kernels. Source: program counter ``kernels.flash_bwd_schedule``
(keys ``fused``, ``resident``, ``streamed``; +1 a trace of the backward). It
is what shows a later change that drops the cell's shape back to two
launches."""

from ddim_cold_tpu.obs import metrics


def read(view):
    by_key: dict = {}
    for series in metrics.snapshot().values():
        for key, count in series.get("kernels.flash_bwd_schedule/by_key",
                                     {}).items():
            by_key[key] = by_key.get(key, 0) + count
    total = sum(by_key.values())
    if not total:
        return None
    return 100.0 * by_key.get("fused", 0) / total
