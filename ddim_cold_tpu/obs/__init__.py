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

* :mod:`ddim_cold_tpu.obs.scopes` — which layer every compiled instruction
  belongs to: ``note`` at the dispatch sites keeps a program's shapes,
  ``scope_map()`` builds the map from the compiled module on demand. It
  imports jax and is not imported here: ``from ddim_cold_tpu.obs import
  scopes`` where a program is dispatched or a trace is reduced.

``spans`` and ``metrics`` are host-only (jax-free, graftcheck A004);
``device`` imports jax lazily, so ``import ddim_cold_tpu.obs`` is cheap
anywhere the router/fleet layer runs.
"""

from ddim_cold_tpu.obs import device, metrics, spans

__all__ = ["device", "metrics", "spans"]
