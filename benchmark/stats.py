"""Order statistics the metrics use (linear interpolation between ranks, as
``numpy.percentile``; copied in arithmetic from the program's
``utils/profiling.latency_summary``)."""

from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float:
    arr = np.asarray(list(samples), dtype=np.float64)
    if arr.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(arr, q))
