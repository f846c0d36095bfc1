"""Process-local metrics registry — the single source of runtime counters.

Every serving-layer counter/gauge/histogram lives here instead of in
hand-maintained per-object dicts: engine, router, fleet, warmup, and the
fault-injection registry emit into named series, and ``Engine.health()`` /
``Router.health()`` snapshots are *rendered from* the registry (the legacy
``stats`` dict surfaces are read-only views over it).

Contracts (mirrors of ``utils/faults.py``'s site registry, enforced
statically by graftcheck GRAFT-A005):

* every emit site (``Scope.inc`` / ``Scope.gauge`` / ``Scope.observe``)
  passes a **literal** metric name,
* the name is **registered** in :data:`METRICS` below,
* each ``(name, key)`` pair appears at **one** emit site in the tree (a
  second site for the same name must carry a distinct literal ``key=``, the
  way a second ``faults.fire`` at one site carries a distinct tag).

Scopes separate instances sharing a process: each :class:`Engine` gets its
own scope (``engine#0``, ``engine#1``, …) so a 2-replica fleet's counters
never alias; a scope id is deterministic in construction order (no
wall-clock, no randomness — same run, same ids).

Host-only module (graftcheck A004): no jax / jax.numpy anywhere — the
registry must be importable (and near-free) from the router/fleet layer
that never touches a device.
"""

from __future__ import annotations

import itertools
import threading
from typing import Optional

#: The full metric registry: ``(name, kind, help)``. Emit sites may only
#: use names listed here (graftcheck GRAFT-A005, the A003 mirror); kinds are
#: checked at emit time too, so a gauge can never silently become a counter.
METRICS = (
    # -- engine (one scope per Engine instance) ---------------------------
    ("engine.compiles", "counter", "XLA programs built (ensure_program)"),
    ("engine.program_aliases", "counter",
     "program keys aliased to an equal-fingerprint executable (warmup dedup)"),
    ("engine.dispatches", "counter", "batches dispatched to the device"),
    ("engine.rows", "counter", "request rows served"),
    ("engine.padded_rows", "counter", "pad rows shipped for bucket alignment"),
    ("engine.max_queue_depth", "gauge", "high-water admission queue depth"),
    ("engine.preview_frames", "counter", "streamed x̂0 preview frames"),
    ("engine.latency_s", "hist", "per-ticket submit→deliver latency"),
    ("engine.queue_wait_s", "hist", "per-ticket submit→plan wait"),
    ("engine.assemble_compiles", "counter",
     "XLA programs compiled by batch assembly's eager ops"),
    ("engine.param_bytes", "gauge", "resident float param bytes"),
    ("engine.param_bytes_quant", "gauge", "resident int8 param bytes"),
    ("engine.retries", "counter", "transient dispatch retries"),
    ("engine.failed_batches", "counter", "batches failed (key: dispatch|plan)"),
    ("engine.failed_tickets", "counter", "tickets resolved with an error"),
    ("engine.quarantined", "counter", "requests quarantined by bisection"),
    ("engine.deadline_expired", "counter",
     "deadlines expired (key: dispatch|plan)"),
    ("engine.rejected", "counter", "submissions rejected (queue full)"),
    ("engine.skipped_batches", "counter", "planned batches skipped"),
    ("engine.stalls", "counter", "soft-watchdog stall events"),
    ("engine.cache_refresh_steps", "counter",
     "device-telemetry: adaptive-gate refresh steps observed"),
    ("engine.cache_reuse_steps", "counter",
     "device-telemetry: adaptive-gate reuse steps observed"),
    # -- warmup (emitted under the warmed engine's scope) -----------------
    ("warmup.new_compiles", "counter", "programs compiled during warmup"),
    ("warmup.deduped", "counter",
     "warmup keys served by aliasing instead of compiling"),
    ("warmup.programs", "gauge", "resident programs after warmup"),
    # -- router -----------------------------------------------------------
    ("router.submitted", "counter", "fleet requests admitted"),
    ("router.completed", "counter", "fleet requests completed"),
    ("router.failed", "counter", "fleet requests failed terminally"),
    ("router.rejected", "counter", "fleet requests rejected at admission"),
    ("router.rejected_by_tenant", "counter",
     "admission rejections per tenant (key: tenant)"),
    ("router.placements", "counter", "ticket placements onto replicas"),
    ("router.hedges", "counter", "hedged re-placements"),
    ("router.failovers", "counter", "failovers off evicted replicas"),
    ("router.replicas_spawned", "counter", "replicas spawned"),
    ("router.replicas_retired", "counter", "replicas retired"),
    ("router.spawn_failures", "counter", "replica spawn failures"),
    ("router.loop_errors", "counter", "supervision-loop errors"),
    # -- fleet ------------------------------------------------------------
    ("fleet.replica_transitions", "counter",
     "replica lifecycle transitions (key: state)"),
    # -- remote replicas (serve/remote.py, one scope per handle) ----------
    ("remote.rpc_calls", "counter", "RPC round trips issued (key: method)"),
    ("remote.crashes", "counter",
     "replica process deaths detected (exit or heartbeat loss)"),
    ("remote.heartbeat_misses", "counter", "heartbeat pings that timed out"),
    ("remote.protocol_errors", "counter",
     "server-pushed protocol_error events (a frame the replica refused)"),
    # -- autoscaler (serve/autoscale.py) ----------------------------------
    ("autoscale.ticks", "counter", "control-loop decisions evaluated"),
    ("autoscale.scale_ups", "counter", "target increments issued"),
    ("autoscale.scale_downs", "counter", "target decrements issued"),
    ("autoscale.target", "gauge", "router replica target after last tick"),
    # -- runtime (utils/profiling.py's compile listener); the readers under
    #    benchmark/layer_metrics/ check the span ring against it ------------
    ("runtime.compiles", "counter",
     "XLA programs compiled or loaded from the persistent cache"),
    # -- kernels (ops/flash_attention.py, counted once a trace) -----------
    ("kernels.flash_fwd_schedule", "counter",
     "flash forward traces by schedule (key: resident|streamed)"),
    ("kernels.flash_fwd_layout", "counter",
     "flash forward traces by operand layout (key: in_place|head_major)"),
    ("kernels.flash_bwd_schedule", "counter",
     "flash backward traces by schedule (key: fused, the one dqkv launch; "
     "else dq + dkv by dq's K/V chunking: resident|streamed)"),
    ("kernels.flash_bwd_layout", "counter",
     "flash backward traces by operand layout (key: in_place|head_major)"),
    ("kernels.flash_fwd_mask", "counter",
     "flash forward traces by mask (key: none|causal|window|selected)"),
    ("kernels.flash_fwd_fold", "counter",
     "masked- and selected-forward traces by the query heads of a K/V head "
     "ONE program folds a fetched chunk into (key: 1, or a divisor of the "
     "query heads a K/V head)"),
    ("kernels.flash_fwd_tail", "counter",
     "masked-, selected- and latent-forward traces by the rows the LAST q "
     "block's folds run on (key: <rows>/<q block> where the program holds "
     "the short folds; whole where that block's folds run on every row)"),
    ("kernels.flash_fwd_rotary", "counter",
     "selected- and masked-forward traces handed an unturned q, by where "
     "its rotation "
     "runs (key: kernel, in the launch on the q block it holds; xla, "
     "apply_rotary before it)"),
    ("kernels.flash_latent_schedule", "counter",
     "latent (two-part score) attention traces by path (key: kernel|xla)"),
    # -- kernels (ops/sparse_select.py, counted once a trace) -------------
    ("kernels.dsa_select_schedule", "counter",
     "sparse-attention key selection traces by path (key: kernel|xla)"),
    ("kernels.dsa_indexer_layers", "counter",
     "glm stack layers traced by indexer kind (key: full|shared)"),
    # -- kernels (ops/grouped_matmul.py, counted once a trace) ------------
    ("kernels.moe_gmm_schedule", "counter",
     "grouped expert matmul PRODUCTS traced, by path (key: kernel|xla): the "
     "gate-up launch counts its two"),
    ("kernels.moe_gate_up_schedule", "counter",
     "expert MLP first-half traces by path (key: fused, the one gate-up "
     "launch; xla, two ragged_dots and the product)"),
    # -- kernels (models/moe.HeldExpertsMlp, counted once a trace) --------
    ("kernels.moe_route_source", "counter",
     "held-expert layer traces by what the router read (key: expert_input, "
     "the rows the experts read; layer_input, another tensor handed in: "
     "route_from)"),
    ("kernels.moe_zero_experts", "counter",
     "held-expert layer traces by the router's zero-compute outputs (key: "
     "identity, outputs past the experts with weights whose pick returns the "
     "row times its weight; none, a router as wide as its experts)"),
    # -- kernels (ops/selective_scan.py, counted once a trace) ------------
    ("kernels.ssm_scan_schedule", "counter",
     "selective-scan traces by path (key: kernel|xla)"),
    # -- kernels (ops/ssd.py, counted once a trace) -------------------------
    ("kernels.ssd_schedule", "counter",
     "state-space-dual (chunked matrix-state) scan traces by path (key: "
     "kernel|xla)"),
    # -- kernels (ops/kda.py, counted once a trace) -------------------------
    ("kernels.kda_schedule", "counter",
     "gated delta-rule (per-channel decay) scan traces by path (key: "
     "kernel|xla)"),
    # -- kernels (ops/short_conv.py, counted once a trace) ------------------
    ("kernels.causal_conv_schedule", "counter",
     "short causal convolution (taps, SiLU, head-wise L2 norm) traces by "
     "path (key: kernel|xla)"),
    # -- kernels (models/vit.Block, counted once a trace) -----------------
    ("kernels.block_tokenwise", "counter",
     "ViT block traces by the path of the token-wise half (key: kernel|xla)"),
    # -- fault injection --------------------------------------------------
    ("faults.injected", "counter", "realized fault injections (key: site)"),
)

_KINDS = {name: kind for name, kind, _ in METRICS}


class _Series:
    """One (scope, name) series: a monotonic counter (optionally subdivided
    by a dynamic key), a last-value gauge, or a raw-sample histogram."""

    __slots__ = ("kind", "value", "by_key", "samples")

    def __init__(self, kind: str):
        self.kind = kind
        self.value = 0
        self.by_key: dict = {}
        self.samples: list = []

    @property
    def total(self):
        return self.value + sum(self.by_key.values())


class Scope:
    """A named emit handle: all series it touches are keyed by its id, so
    two engines in one process never share a counter."""

    def __init__(self, registry: "Registry", sid: str):
        self._reg = registry
        self.sid = sid

    # -- emit (the A005-linted surface: literal name first) ---------------
    def inc(self, name: str, value=1, key: Optional[str] = None) -> None:
        self._reg._emit(self.sid, name, "counter", value, key)

    def gauge(self, name: str, value) -> None:
        self._reg._emit(self.sid, name, "gauge", value, None)

    def observe(self, name: str, value) -> None:
        self._reg._emit(self.sid, name, "hist", value, None)

    # -- read -------------------------------------------------------------
    def value(self, name: str, default=0):
        s = self._reg._get(self.sid, name)
        if s is None:
            return default
        return s.total if s.kind == "counter" else s.value

    def raw(self, name: str):
        """Gauge value, or None when the gauge was never set (the legacy
        ``stats["param_bytes"] = None`` initial state)."""
        s = self._reg._get(self.sid, name)
        return None if s is None else s.value

    def by_key(self, name: str) -> dict:
        s = self._reg._get(self.sid, name)
        return dict(s.by_key) if s is not None else {}

    def samples(self, name: str) -> list:
        s = self._reg._get(self.sid, name)
        return list(s.samples) if s is not None else []

    def count(self, name: str) -> int:
        s = self._reg._get(self.sid, name)
        return len(s.samples) if s is not None else 0

    def snapshot(self) -> dict:
        return self._reg.snapshot().get(self.sid, {})


class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        # (sid, name) -> _Series. The lock guards the dict AND the mutable
        # innards of every series in it (by_key / samples): emit mutates
        # them, so every read surface copies them out under the lock too —
        # a render racing an emit must never iterate a dict mid-resize.
        self._series: dict = {}                         # guarded-by: _lock
        self._scope_ids = itertools.count()

    def scope(self, name: str) -> Scope:
        with self._lock:
            sid = f"{name}#{next(self._scope_ids)}"
        return Scope(self, sid)

    def _emit(self, sid, name, kind, value, key):
        want = _KINDS.get(name)
        if want is None:
            raise ValueError(f"unregistered metric {name!r} — add it to "
                             "obs.metrics.METRICS (graftcheck GRAFT-A005)")
        if want != kind:
            raise ValueError(f"metric {name!r} is a {want}, emitted as {kind}")
        with self._lock:
            s = self._series.get((sid, name))
            if s is None:
                s = self._series[(sid, name)] = _Series(kind)
            if kind == "counter":
                if key is None:
                    s.value += value
                else:
                    s.by_key[key] = s.by_key.get(key, 0) + value
            elif kind == "gauge":
                s.value = value
            else:
                s.samples.append(value)

    def _get(self, sid, name) -> Optional[_Series]:
        """A point-in-time COPY of the series, taken under the lock. The
        live object's by_key/samples are mutated by concurrent emits; the
        old code handed the live series out and let Scope readers copy its
        innards OUTSIDE the lock — a snapshot racing an emit could iterate
        a dict mid-resize (emit-vs-render consistency, graftcheck T-rules
        audit)."""
        with self._lock:
            s = self._series.get((sid, name))
            if s is None:
                return None
            c = _Series(s.kind)
            c.value = s.value
            c.by_key = dict(s.by_key)
            c.samples = list(s.samples)
            return c

    def snapshot(self) -> dict:
        """{scope_id: {name: value | {key: value} | [samples]}} — counters
        render their total (keyed subdivisions under ``name + "/by_key"``),
        gauges their last value, histograms their raw sample list. Rendered
        entirely under the lock: the per-series containers it reads are
        emit-mutable, so the copy and the render must be one atomic view
        (a snapshot taken mid-request never shows a counter without its
        by_key breakdown)."""
        out: dict = {}
        with self._lock:
            for (sid, name), s in self._series.items():
                dst = out.setdefault(sid, {})
                if s.kind == "counter":
                    dst[name] = s.total
                    if s.by_key:
                        dst[name + "/by_key"] = dict(s.by_key)
                elif s.kind == "gauge":
                    dst[name] = s.value
                else:
                    dst[name] = list(s.samples)
        return out

    def reset(self) -> None:
        """Drop every series (tests). Scope ids keep counting up, so scopes
        created before a reset never alias ones created after."""
        with self._lock:
            self._series.clear()


_REG = Registry()


def registry() -> Registry:
    return _REG


def scope(name: str) -> Scope:
    """A fresh uniquely-identified emit scope on the process registry."""
    return _REG.scope(name)


def snapshot() -> dict:
    return _REG.snapshot()


def reset() -> None:
    _REG.reset()
