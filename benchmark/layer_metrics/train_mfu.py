"""Model FLOP/s utilisation of training: the operations the window's
optimizer steps require (forward and backward, nothing recomputed,
``costs.train_step_flops``) per second, over chips times the bf16 peak.
An end-to-end utilisation, not a kernel's roofline share.
Layer: trainer. Source: host clock and shapes."""

from benchmark import costs


def read(view):
    steps = view.counters.get("steps")
    batch = view.counters.get("global_batch")
    if not steps or not batch:
        return None
    flops = costs.train_step_flops(view.config, batch) * steps
    peak = view.peaks["bf16_flops_per_s"] * len(view.run.devices)
    return 100.0 * flops / view.window_s / peak
