"""Seconds of set-up in which JAX was tracing Python to a jaxpr or lowering
one to StableHLO: the union of the program's ``jax/jaxpr_trace_duration`` and
``jax/jaxpr_to_mlir_module_duration`` events inside the stretch ``setup_s``
counts (a trace inside a trace counts once); paid warm and cold alike. Nests
inside ``setup_compile_s``. Layer: runtime. Source: program span (the
listener's events; the record is checked against ``runtime.compiles``)."""

from benchmark.layer_metrics import program_record as rec
from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    if setup is None:
        return None
    return rec.union_s(setup.named("jax/jaxpr_trace_duration",
                                   "jax/jaxpr_to_mlir_module_duration"))
