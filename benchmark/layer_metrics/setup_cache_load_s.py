"""Seconds of set-up spent loading programs from the persistent compile
cache: the sum of the program's ``jax/cache_retrieval_time_sec`` events
(read, deserialize, load onto the cell's devices) inside the stretch
``setup_s`` counts. Nests inside ``setup_compile_s``. Layer: runtime.
Source: program span (the listener's events; the record is checked against
``runtime.compiles``); ``setup_record`` has the stretch."""

from benchmark.layer_metrics import program_record as rec
from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    if setup is None:
        return None
    return rec.inside_s(setup.named("jax/cache_retrieval_time_sec"),
                        setup.lo, setup.hi)
