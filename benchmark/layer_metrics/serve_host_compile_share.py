"""Share of the window the process spent in JAX's tracing, lowering and
compiling. The sampler programs are warm (``serve_compiles_in_window`` says
so); what shows here is the engine's eager batch assembly, which builds a new
small program for every new combination of request sizes in a batch.
Layer: serving (engine). Source: program counter (``jax.monitoring``
compile-duration events summed by the benchmark over the window)."""


def read(view):
    seconds = view.counters.get("jax_compile_s")
    if seconds is None:
        return None
    return 100.0 * seconds / view.window_s
