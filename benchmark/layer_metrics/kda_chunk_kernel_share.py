"""Share of the gated delta-rule scan's traces in this process that took the
Pallas launch ``kda_chunk`` and not the chunked XLA form: 100 on the chip.
Layer: kernels. Source: program counter ``kernels.kda_schedule`` (keys
``kernel``, ``xla``; +1 a trace). It is what shows a later change that drops a
shape to the XLA path. Where the program has no such counter, or never traced
the scan, the reader finds nothing and returns None."""

from ddim_cold_tpu.obs import metrics


def read(view):
    by_key: dict = {}
    for series in metrics.snapshot().values():
        for key, count in series.get("kernels.kda_schedule/by_key",
                                     {}).items():
            by_key[key] = by_key.get(key, 0) + count
    total = sum(by_key.values())
    if not total:
        return None
    return 100.0 * by_key.get("kernel", 0) / total
