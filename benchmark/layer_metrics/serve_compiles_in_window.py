"""Sampler programs the engine built inside the window; reads 0 when warm-up
covered every (config, bucket). Layer: serving (warmup). Source: program
counter (``Engine.stats["compiles"]`` after minus before)."""


def read(view):
    value = view.counters.get("compiles")
    return None if value is None else float(value)
