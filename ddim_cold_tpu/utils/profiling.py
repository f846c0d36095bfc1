"""Tracing/profiling + numeric-debug hooks (SURVEY.md §5 aux subsystems).

The reference has no profiler — only wall-clock prints (per-100-step
``time_cost`` and per-sampler-step elapsed, multi_gpu_trainer.py:135-138,
ViT.py:222-235). Here the equivalents are structural:

* ``start_trace(dir)`` / ``stop_trace()`` — a step-bounded ``jax.profiler``
  session (the trainer's ``profile_steps``; view in TensorBoard or Perfetto).
  ``obs/scopes.py``'s ``write`` puts ``scopes.json`` beside it: which layer
  each instruction of the timeline belongs to.
* the mirror — while a profiler session is live, every layer span
  ``obs/spans.py`` opens is also written into it as a ``ddim/<name>`` TraceAnnotation, on the
  host plane of the same ``.xplane.pb`` as the device's ops.
* the compile listener — every ``jax.monitoring`` compile and cache duration
  event becomes a closed ``jax/<event>`` span (``event``, ``fun``) under the
  span open on its thread, and a backend compile (or cache load) counts into
  ``runtime.compiles``. These events are the record of set-up — with
  ``parallel/place_state`` (``parallel/mesh.py``), ``data/dataset/open``
  (``data/datasets.py``), ``data/native/load|build`` (``data/native.py``)
  and the loader's and samplers' spans, all on the one recorder — and the
  readers under ``benchmark/layer_metrics/`` put every second of ``setup_s``
  down to a stage from them (PERF.md section 3): ``jaxpr_trace_duration``
  and ``jaxpr_to_mlir_module_duration`` are ``setup_trace_lower_s``;
  ``cache_retrieval_time_sec`` (a persistent-cache hit: read, deserialize,
  load; inside the backend event) is ``setup_cache_load_s``; all of them in
  union are ``setup_compile_s``; ``backend_compile_duration`` inside a window
  is ``compiles_in_window`` and, against ``runtime.compiles``, the check that
  the ring has dropped none; ``compile_time_saved_sec`` has no length
  (``saved_s``). What no span or event covers is ``setup_start_s``,
  ``setup_first_run_wait_s`` or ``setup_unattributed_s``. The listener adds
  no wait: it is called on the compiling thread, after the event, with the
  event's own duration.
* ``enable_nan_checks()`` — ``jax_debug_nans`` (the SPMD replacement for the
  reference's commented TORCH_DISTRIBUTED_DEBUG, with actually-useful
  semantics: fail at the op that produced the NaN).
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from ddim_cold_tpu.obs import metrics, spans


def start_trace(log_dir: str) -> None:
    """Step-bounded tracing (the trainer's ``profile_steps``): start here,
    ``stop_trace()`` when the window closes."""
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


def _mirror(name: str):
    """The span recorder's sink: with a profiler session live, an entered
    ``ddim/<name>`` annotation (the recorder exits it when the span ends);
    with none, one ``is_enabled()`` read and nothing else."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return None
    ann = jax.profiler.TraceAnnotation("ddim/" + name)
    ann.__enter__()
    return ann


#: the duration events of one XLA program's way from Python to the device:
#: trace, lower, then compile OR load from the persistent cache (that last
#: one holds ``cache_retrieval_time_sec`` when it was a load)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: not a stretch of time but what a cache hit spared: recorded with no
#: length, the seconds as an attribute
_TIME_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_EVENT_ROOTS = ("/jax/core/compile/", "/jax/compilation_cache/")

_runtime = metrics.scope("runtime")
_compiles = threading.local()  # .n: backend compiles seen on this thread


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if not event.startswith(_EVENT_ROOTS):
        return
    attrs = {"event": event}
    if "fun_name" in kwargs:
        attrs["fun"] = kwargs["fun_name"]
    if event == _TIME_SAVED:
        attrs["saved_s"], seconds = seconds, 0.0
    spans.event("jax/" + event.rsplit("/", 1)[-1], time.perf_counter_ns(),
                int(seconds * 1e9), **attrs)
    if event == _BACKEND_COMPILE:
        _compiles.n = compile_count() + 1
        _runtime.inc("runtime.compiles")


def compile_count() -> int:
    """XLA programs compiled (or loaded from the persistent cache) on the
    calling thread so far: difference it around a block to count the block's
    own compiles."""
    return getattr(_compiles, "n", 0)


def _install() -> None:
    """Once a process: ``jax.monitoring`` keeps every listener it is given,
    and a reload of this module (same globals) must not add a second."""
    global _installed
    if _installed:
        return
    import jax.monitoring as mon

    mon.register_event_duration_secs_listener(_on_duration)
    spans.set_sink(_mirror)
    _installed = True


_installed = globals().get("_installed", False)
_install()


def scope(name: str):
    """Named scope INSIDE traced code (``jax.named_scope``) — the compiled
    sibling of :func:`annotate`: the name lands on the ops themselves, so
    profiler timelines attribute kernel time to sampler stages
    (``ddim/model``, ``flash_attention/fwd``, ``sp/all_to_all``, …).
    Metadata-only: the printed jaxpr and its J006 signature hash are
    untouched, and numerics are bit-identical with or without it."""
    return jax.named_scope(name)


def enable_nan_checks(enable: bool = True) -> None:
    """Re-run suspect computations de-optimized and raise at NaN origin."""
    jax.config.update("jax_debug_nans", enable)


def latency_summary(samples_s) -> dict:
    """Order statistics over a list of latencies in seconds — the serving
    engine's per-request report (serve.Engine.stats)."""
    arr = np.asarray(list(samples_s), dtype=np.float64)
    if arr.size == 0:
        return {"n": 0, "count": 0, "p50_s": 0.0, "p95_s": 0.0, "p99_s": 0.0,
                "mean_s": 0.0, "max_s": 0.0}
    return {
        "n": int(arr.size),
        "count": int(arr.size),  # explicit alias: dashboards key on "count"
        "p50_s": float(np.percentile(arr, 50)),
        "p95_s": float(np.percentile(arr, 95)),
        "p99_s": float(np.percentile(arr, 99)),
        "mean_s": float(arr.mean()),
        "max_s": float(arr.max()),
    }
