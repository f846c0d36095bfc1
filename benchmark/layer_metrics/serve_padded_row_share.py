"""Rows of padding among the rows the engine dispatched in the window.
Layer: serving (batching, engine). Source: program counter
(``Engine.stats``: padded_rows / (rows + padded_rows), after minus before)."""


def read(view):
    rows = view.counters.get("rows")
    padded = view.counters.get("padded_rows")
    if rows is None or padded is None or rows + padded <= 0:
        return None
    return 100.0 * padded / (rows + padded)
