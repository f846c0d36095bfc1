"""Share of the ViT blocks' traces in this process whose token-wise half ran
as the two kernels (``ln_qkv``, ``block_tail``) and not as the XLA
composition: 100 in the 200px sampler cell. Layer: kernels. Source: program
counter ``kernels.block_tokenwise`` (keys ``kernel``, ``xla``; +1 a trace of a
``Block``). It is what shows a later change that drops the cell's shape back
to the composition."""

from ddim_cold_tpu.obs import metrics


def read(view):
    by_key: dict = {}
    for series in metrics.snapshot().values():
        for key, count in series.get("kernels.block_tokenwise/by_key",
                                     {}).items():
            by_key[key] = by_key.get(key, 0) + count
    total = sum(by_key.values())
    if not total:
        return None
    return 100.0 * by_key.get("kernel", 0) / total
