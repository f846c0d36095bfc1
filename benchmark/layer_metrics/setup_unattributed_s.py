"""Seconds of set-up, after the program's first span opened, that neither a
span or event of the program nor the first run's wait covers: the
benchmark's ``weights.make``, model and mesh construction, and whatever the
program does in set-up without saying so. ``setup_start_s`` + the union of
the program's record + ``setup_first_run_wait_s`` + this = the stretch.
Layer: runtime. Source: program span (``setup_record``)."""

from benchmark.layer_metrics import setup_record


def read(view):
    setup = setup_record.of(view)
    return None if setup is None else setup.unattributed_s
