"""Observability: the program's span recorder, the process metrics
registry, and on-device step telemetry decoding.

* :mod:`ddim_cold_tpu.obs.spans` — the one span recorder, on
  ``time.perf_counter_ns``: layer spans (loader stages, sampler calls,
  engine batch stages, JAX compiles) always on in a bounded ring and
  mirrored into a live profiler session; per-request ticket traces
  (``Router.submit`` / ``Engine.submit`` → plan → assemble → dispatch →
  fetch → preview → finish, across hedges/failovers) opt-in.
* :mod:`ddim_cold_tpu.obs.metrics` — named counters/gauges/histograms the
  serving layers emit into; ``Engine.health()`` / ``Router.health()`` are
  rendered from it.
* :mod:`ddim_cold_tpu.obs.device` — static-shaped sampler-scan aux
  (adaptive-gate decisions, drift) decoded into per-ticket summaries.
* :mod:`ddim_cold_tpu.obs.attrib` — profiler-trace attribution: device
  time per named scope, flop/byte joins → achieved TFLOP/s, MFU, roofline
  class, fusion candidates (``bench --attrib``, scripts/attrib_report.py).
* :mod:`ddim_cold_tpu.obs.trend` — the BENCH_r*/MULTICHIP_r* trajectory
  loader + noise-banded regression gate (``python -m
  ddim_cold_tpu.obs.trend``).

``spans``, ``metrics``, ``attrib`` and ``trend`` are host-only (jax-free,
graftcheck A004); ``device`` imports jax lazily, so ``import
ddim_cold_tpu.obs`` is cheap anywhere the router/fleet layer runs.
"""

from ddim_cold_tpu.obs import attrib, device, metrics, spans, trend

__all__ = ["attrib", "device", "metrics", "spans", "trend"]
