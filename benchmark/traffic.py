"""The one general generator of open-loop traffic: a schedule of requests
from a traffic file's parameters and the seed.

A traffic file (``benchmark/traffic/<name>.json``) gives, for an open loop:

    "arrivals": {"process": "stratified_exponential", "rate_per_s": r}
    "images_per_request": {"1": 0.5, "2": 0.2, ...}   # size: probability

The schedule is a function of (parameters, seconds, seed) alone. It is NOT a
Poisson process: the count is fixed at rate x seconds, and every seed gets
the SAME multiset of gaps and of request sizes -- the n mid-quantiles of the
exponential distribution and the sizes in their stated proportions -- in
another order. Gaps are spread as a Poisson stream's are, but two seeds offer
the same work at the same mean rate and differ only in how it is
interleaved, so a run-to-run difference is the system's, not the draw's.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float      # seconds after the window opens
    images: int
    seed: int         # the request's own sampling seed, below 2**31


def _apportion(shares: dict, n: int) -> list[int]:
    """``n`` sizes in the proportions of ``shares`` (largest remainders)."""
    sizes = sorted((int(k), float(v)) for k, v in shares.items())
    total = sum(v for _, v in sizes)
    exact = [v / total * n for _, v in sizes]
    counts = [int(np.floor(e)) for e in exact]
    order = sorted(range(len(sizes)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return [s for (s, _), c in zip(sizes, counts) for _ in range(c)]


def schedule(params: dict, seconds: float, seed: int) -> list[Request]:
    arrivals = params["arrivals"]
    rate = float(arrivals["rate_per_s"])
    if arrivals["process"] != "stratified_exponential":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xA221]))
    # exponential gaps at their mid-quantiles, rescaled to fill the window
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    due = np.cumsum(rng.permutation(gaps))
    due -= due[0] * 0.5
    sizes = rng.permutation(_apportion(params["images_per_request"], n))
    seeds = rng.integers(0, 2**31 - 1, size=n)
    return [Request(i, float(due[i]), int(sizes[i]), int(seeds[i]))
            for i in range(n)]
