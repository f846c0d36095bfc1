"""Seconds of the benchmark's own ``warmup`` (sampler) or ``first_steps``
(training) record that no span or event of the program covers: the host
blocked on the device's first run of the programs just loaded, and the
fetches after it. A sampler's ``sampler/call`` ends when the jitted scan's
call returns, so the whole warm-up call's device time is here; nothing in the
program waits for it, so nothing spans it. Layer: runtime. Source: program
span (the gaps between them; ``setup_record``)."""

from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    return None if setup is None else setup.first_run_wait_s
