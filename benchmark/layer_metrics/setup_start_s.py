"""Seconds from the instant ``setup_s`` counts from to the opening of the
program's first span or event: the interpreter's and the imports' time and
the runtime's start (``jax.devices()``), before the program did anything.
Layer: runtime. Source: program span (``setup_record`` has the stretch)."""

from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    return None if setup is None else setup.start_s
