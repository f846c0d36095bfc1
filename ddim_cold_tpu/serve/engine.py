"""AOT-compiled dispatch loop: the engine half of the serving subsystem.

Throughput comes from three structural moves, none of which touch the math:

* **Zero serve-time compiles** — every (config, bucket) pair is compiled
  ahead of time via the jitted scans' AOT path (``.lower(...).compile()``)
  and dispatch only ever calls those executables. A compiled executable can
  NOT retrace — a shape it wasn't built for raises instead of silently
  recompiling — so "no compiles after warmup" is structural, not hopeful.
  ``stats["compiles"]`` counts program builds; after ``warmup()`` it must
  not move.

* **Transfer/compute overlap** — batch assembly (per-request init draws, the
  guided path's H2D upload, padding, mesh placement) runs ``depth`` batches
  ahead in a background thread (the ``device_prefetch`` machinery from
  data/loader.py), while the main loop keeps a small in-flight window of
  dispatched batches and fetches batch n−w (D2H) while the device scans
  batch n. JAX dispatch is async, so the three phases pipeline.

* **Buffer donation** — the scans donate ``x_init`` and the step-cache
  carry (ops/sampling.py), so a dispatch peaks at one x-sized buffer, and
  the engine recycles the returned cache as the next batch's donated
  ``cache0`` (legal: the cache schedule's step 0 always refreshes, so stale
  contents are never read) — cached serving allocates its cache once per
  bucket, ever.

**Failure isolation.** Every pipeline stage (assembly → dispatch → fetch) is
wrapped so an exception fails only the tickets of the batch it struck — the
engine keeps serving subsequent batches. Retryable faults (the transfer/RPC
class, ``errors.RETRYABLE_EXCEPTIONS``) get capped exponential backoff with
the donated input rebuilt per attempt; a batch that fails deterministically
is BISECTED on request boundaries — each half re-assembles (padded to the
same compiled bucket, so recovery never compiles) and re-dispatches until
the poisoned request is isolated and quarantined
(:class:`~.errors.RequestQuarantinedError`, stage exception as cause) while
its innocent batchmates complete. Admission control bounds the queue
(``max_queue`` → :class:`~.errors.QueueFullError` at submit) and per-request
deadlines are enforced at plan AND dispatch time (expired requests fail fast
with :class:`~.errors.DeadlineExceeded` instead of occupying a bucket).
:meth:`Engine.drain` stops admission, flushes in-flight batches, and
deterministically fails queued tickets. A soft-mode
:class:`~ddim_cold_tpu.utils.watchdog.StallWatchdog` bounds every silent
device window (a device call that never returns raises no exception to
catch): on stall it fails in-flight and queued tickets
(partial results already fetched stand) instead of hanging every waiter.
Chaos coverage injects faults at the ``serve.*`` sites
(utils/faults.py); with faults disarmed the fast path executes
byte-identical device code.

**Bitwise contract.** Engine output rows are bitwise identical to a direct
``ddim_sample``/``cold_sample``/``sample_from`` call with the same request
rng: the engine draws each request's init at the request's OWN ``n`` with the
request's own key (exactly the draw the direct call makes — the values depend
on ``n``), and row slices of that draw keep their bits; every sampler row is
then computed independently of its batchmates (per-row trunk), so neither
coalescing, padding, splitting, nor bisection recovery changes a single bit.
This holds for the deterministic samplers only — which is why
``SamplerConfig`` has no ``eta`` (batch-shaped noise draws break row
invariance) — and exactly per-backend (a mesh reduces in a different order
than one device; same as training). A quant config keeps the same contract
against a direct call on the quantized model/params pair
(``model.clone(quant=...)`` + ``quant.quantize_params(params)`` — the
deterministic transform the engine itself applies).

**Sequence parallelism.** A config with ``sp_degree > 1`` compiles its
programs against a per-degree ``(data, seq)`` mesh over the local devices
(``make_mesh({"data": n_dev // sp_degree, "seq": sp_degree})``) with the
model cloned to run its attention through ``ulysses_self_attention`` /
``ring_self_attention`` (patch tokens sequence-sharded inside the
shard_map, the CLS/time conditioning replicated like every other
non-sequence activation). The registry key is unchanged — ``(config,
bucket)`` — because ``sp_mode``/``sp_degree`` are fields of the hashed
config, so sp and non-sp programs can never collide and never coalesce
into one batch. ``sp_mode='ulysses'`` falls back to the ring when the
head count does not divide by the seq axis (Ulysses' structural
requirement; the ring has none). Contract-wise: the degenerate
``sp_degree=1`` IS the default config (``SamplerConfig`` rejects
``sp_mode != 'none'`` at degree 1), so degree-1 dispatches are bitwise
the existing serve path by identity, not by luck; ``sp_degree > 1``
output matches the degree-1 program at float tolerance only — the
seq-axis collectives reduce in a different order, same caveat as the
data mesh vs one device.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.data.loader import device_prefetch
from ddim_cold_tpu.obs import device as obs_device
from ddim_cold_tpu.obs import metrics, spans
from ddim_cold_tpu.ops import sampling, step_cache
from ddim_cold_tpu.parallel.mesh import (ambient, batch_sharding,
                                         data_axis_size, make_mesh,
                                         shard_params)
from ddim_cold_tpu.serve.batching import (BatchPlan, Request, SamplerConfig,
                                          Ticket, plan_batches)
from ddim_cold_tpu.serve.errors import (RETRYABLE_EXCEPTIONS, DeadlineExceeded,
                                        EngineClosedError, EngineStalledError,
                                        QueueFullError, RequestFailedError,
                                        RequestQuarantinedError)
from ddim_cold_tpu.utils import faults
from ddim_cold_tpu.utils.platform import watchdog_stall_s
from ddim_cold_tpu.workloads import preview as workload_preview
from ddim_cold_tpu.workloads import tasks as workload_tasks
from ddim_cold_tpu.utils.profiling import compile_count, latency_summary
from ddim_cold_tpu.utils.watchdog import StallWatchdog

#: per-task batch inputs that ride along with x through assembly — sliced
#: per request row range, zero-padded, and placed exactly like the init
#: batch (Request.extras carries the host arrays; order here is the
#: program's positional argument order after x)
_EXTRA_INPUTS = {"inpaint": ("known", "mask")}


def _need_key(seed, rng) -> jax.Array:
    if rng is None:
        if seed is None:
            raise ValueError("this request's init/noise draw is keyed — "
                             "pass seed= or rng=")
        rng = jax.random.PRNGKey(int(seed))
    return rng


class Engine:
    """Bucketed continuous-batching sampler server.

    ::

        eng = Engine(model, params, mesh=mesh, buckets=(8, 32, 128))
        serve.warmup(eng, [SamplerConfig(k=10)])
        tickets = [eng.submit(seed=s, n=5) for s in range(40)]
        eng.run()
        imgs = tickets[0].result()   # (5, H, W, C) in [0, 1]

    ``submit`` is thread-safe and returns immediately; ``run`` drains the
    queue (requests submitted mid-run join the next planning round).
    ``drain()`` closes admission and fails anything still queued.
    """

    def __init__(self, model, params, mesh=None,
                 buckets: Sequence[int] = (8, 32, 128), *,
                 student_params=None,
                 prefetch_depth: int = 2, inflight: int = 2,
                 max_queue: Optional[int] = None,
                 max_retries: int = 2, retry_base_s: float = 0.05,
                 retry_cap_s: float = 1.0,
                 stall_s: Optional[float] = None,
                 replica_id: str = ""):
        self.model = model
        self.mesh = mesh
        # fleet identity: names this engine in fault tags ("replica:r0|" —
        # chaos specs can target one replica), failure messages, and the
        # health snapshot, so fleet-level failures are attributable
        self.replica_id = str(replica_id)
        self._rname = (f"replica {self.replica_id!r}" if self.replica_id
                       else "engine")
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive, got {buckets!r}")
        shards = data_axis_size(mesh)
        bad = [b for b in self.buckets if b % shards]
        if bad:
            raise ValueError(
                f"buckets {bad} do not divide the mesh data axis ({shards}); "
                "sharded placement needs even divisibility")
        self.params = shard_params(params, mesh) if mesh is not None else params
        # distilled few-step student (train/distill.py): same architecture,
        # different weights — shipped/pinned exactly like the teacher tree.
        # config.student routes _params_for here; the PROGRAM is shared with
        # the teacher at equal steps (params are a runtime argument), which
        # is what lets warmup dedup alias student configs for free.
        self.student_params = (shard_params(student_params, mesh)
                               if mesh is not None and student_params
                               is not None else student_params)
        self.prefetch_depth = int(prefetch_depth)
        self.inflight = max(1, int(inflight))
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 or None, got {max_queue}")
        self.max_queue = max_queue
        self.max_retries = int(max_retries)
        self.retry_base_s = float(retry_base_s)
        self.retry_cap_s = float(retry_cap_s)
        # stall budget for silent device windows (0 on a cpu backend unless
        # the env overrides — see utils/platform.watchdog_stall_s)
        self.stall_s = (watchdog_stall_s("DDIM_COLD_SERVE_STALL_S", 900.0)
                        if stall_s is None else float(stall_s))
        # any key works here: the deterministic scans never read noise_rng
        # (eta is pinned to 0.0 at program build — see module docstring)
        self._key0 = jax.random.PRNGKey(0)
        self._programs: dict = {}
        # (bucket, kind) -> recycled step-cache carry; kind per _cache_kind
        # (sp configs get their own kinds — a carry placed on a (data, seq)
        # mesh cannot be donated to a program compiled for another mesh)
        self._spare_caches: dict = {}
        # sequence parallelism: per-degree (data, seq) meshes over this
        # engine's devices, the sp model clones traced against them, and the
        # param trees re-placed on them (AOT executables are sharding-strict
        # — an sp program must see params on ITS mesh, not the engine's)
        self._sp_meshes: dict = {}   # sp_degree -> Mesh
        self._sp_models: dict = {}   # (mode, degree, quant) -> model clone
        self._sp_params: dict = {}   # (degree, quantized?, student?) -> tree
        # w8a16 serving (ops/quant.py): the int8 tree is built ONCE from the
        # float params on the first quant config and shipped/pinned like the
        # float tree — every quant dispatch reuses the same device buffers
        # (≈4× fewer trunk-param bytes over the link than the float tree).
        self._qparams = None
        self._qparams_student = None
        self._quant_models: dict = {}  # (quant, fused) -> model clone
        self._pending: list[Request] = []               # guarded-by: _lock
        # rid -> unresolved Request (stall fail set)
        self._open: dict = {}                           # guarded-by: _lock
        self._lock = threading.Lock()
        self._next_rid = 0                              # guarded-by: _lock
        self._closed = False                            # guarded-by: _lock
        self._stalled = False
        self._running = False
        self._wd: Optional[StallWatchdog] = None
        self._idle = threading.Event()
        self._idle.set()
        self._t0 = time.monotonic()
        # (monotonic time, label) of the last pipeline beacon — health()
        # surfaces its age as last_progress_s so a router can spot a wedged
        # replica from the snapshot alone, before the watchdog fires
        self._last_mark = (self._t0, "init")
        self.quarantined: list[int] = []  # rids bisection isolated
        #: obs emit handle (scope id ``engine#N`` — per instance, so a
        #: multi-replica fleet's counters never alias): every counter the
        #: old hand-rolled stats dict tracked now lives in the process
        #: metrics registry (obs/metrics.py); :attr:`stats` is a read-only
        #: legacy view rendered from it. Public so warmup() reports its
        #: compile counts under the engine it warmed.
        self.metrics = metrics.scope("engine")

    @property
    def stats(self) -> dict:
        """Legacy stats surface, rendered from the metrics registry — the
        same keys/semantics the hand-maintained dict had (``param_bytes``
        is None until the quant tree is built; ``latencies_s`` is the raw
        per-ticket sample list)."""
        m = self.metrics
        return {
            "compiles": m.value("engine.compiles"),
            "program_aliases": m.value("engine.program_aliases"),
            "dispatches": m.value("engine.dispatches"),
            "rows": m.value("engine.rows"),
            "padded_rows": m.value("engine.padded_rows"),
            "max_queue_depth": int(m.raw("engine.max_queue_depth") or 0),
            "preview_frames": m.value("engine.preview_frames"),
            "latencies_s": m.samples("engine.latency_s"),
            "queue_waits_s": m.samples("engine.queue_wait_s"),
            "assemble_compiles": m.value("engine.assemble_compiles"),
            "param_bytes": m.raw("engine.param_bytes"),
            "param_bytes_quant": m.raw("engine.param_bytes_quant"),
            "retries": m.value("engine.retries"),
            "failed_batches": m.value("engine.failed_batches"),
            "failed_tickets": m.value("engine.failed_tickets"),
            "quarantined": m.value("engine.quarantined"),
            "deadline_expired": m.value("engine.deadline_expired"),
            "rejected": m.value("engine.rejected"),
            "skipped_batches": m.value("engine.skipped_batches"),
            "stalls": m.value("engine.stalls"),
        }

    # ---------------------------------------------------------------- submit

    def submit(self, seed: Optional[int] = None, n: int = 1, *,
               rng: Optional[jax.Array] = None,
               x_init: Optional[np.ndarray] = None,
               mask: Optional[np.ndarray] = None,
               config: Optional[SamplerConfig] = None,
               deadline_s: Optional[float] = None,
               trace=None, **kwargs) -> Ticket:
        """Queue a sampling request; returns its :class:`Ticket`.

        Fresh starts pass ``seed`` (or a jax ``rng`` key) — the engine draws
        the same init the direct sampler would from that key. Guided requests
        pass ``x_init`` (an (n, H, W, C) or (H, W, C) encoded start; pair it
        with ``t_start`` — the ``sample_from`` path). Sampler options go in
        ``config`` or as keyword args (``k=, t_start=, cache_interval=, …``).

        Editing workloads (``config.task`` in workloads.EDIT_TASKS) reuse
        ``x_init`` as the task's image input: the known image (``inpaint``,
        with ``mask=`` selecting the pixels to preserve), the upsampled
        low-res start (``superres`` — see ``workloads.superres_init``), the
        draft to forward-noise (``draft``), or the (2, H, W, C) endpoint pair
        (``interp``, where ``n`` stays the path length). ``inpaint``,
        ``draft`` and ``interp`` also need ``seed``/``rng`` — their noise
        draw is keyed exactly like the direct workloads.* call, which is what
        keeps the bitwise contract.

        ``deadline_s`` bounds the request's total time in the engine: past
        it, the request fails fast with :class:`DeadlineExceeded` instead of
        occupying a bucket. Raises :class:`QueueFullError` when the bounded
        queue is at ``max_queue`` and :class:`EngineClosedError` after
        :meth:`drain`.

        ``trace`` (an ``obs.spans`` TraceContext/Span, or None) parents this
        request's span when tracing is enabled — the fleet router passes its
        placement-attempt span here so hedged attempts land in ONE trace.
        With no parent, the request starts a fresh trace.
        """
        if config is None:
            config = SamplerConfig(**kwargs)
        elif kwargs:
            raise ValueError(f"pass config OR keyword options, not both: {kwargs}")
        self._check_config(config)
        task = config.task
        if mask is not None and task != "inpaint":
            raise ValueError(
                f"mask= is the inpaint task's input (config.task={task!r})")
        extras = None
        if task == "sample":
            if x_init is not None:
                if config.sampler != "ddim":
                    raise ValueError(
                        "guided starts (x_init) are a DDIM path; "
                        "cold sampling has no encoded-start analogue")
                x_init = self._as_batch(x_init)
                n = x_init.shape[0]
                key = None
            else:
                key = _need_key(seed, rng)
        else:
            if x_init is None:
                raise ValueError(
                    f"task {task!r} needs x_init= — its image input "
                    "(inpaint: known image; superres: upsampled low-res; "
                    "draft: the draft; interp: the (2, H, W, C) endpoints)")
            x_init = self._as_batch(x_init)
            if task == "interp":
                # n stays the caller's path length; x_init is the pair
                if x_init.shape[0] != 2:
                    raise ValueError(
                        "interp x_init is the endpoint PAIR (2, H, W, C) — "
                        f"n= is the path length; got shape {x_init.shape}")
            else:
                n = x_init.shape[0]
            key = None if task == "superres" else _need_key(seed, rng)
            if task == "inpaint":
                if mask is None:
                    raise ValueError(
                        "inpaint needs mask= (binary, 1 = known pixel — "
                        "see workloads.normalize_mask)")
                extras = {"known": np.ascontiguousarray(x_init),
                          "mask": workload_tasks.normalize_mask(
                              mask, int(n), self.model.img_size)}
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
        deadline = (time.perf_counter() + deadline_s
                    if deadline_s is not None else None)
        req = Request(config=config, n=int(n), key=key, x_init=x_init,
                      ticket=Ticket(n), deadline=deadline, extras=extras)
        req.ticket._health_cb = self.health
        with self._lock:
            if self._closed:
                raise EngineClosedError(
                    "engine is drained — no new requests accepted")
            if self.max_queue is not None and len(self._pending) >= self.max_queue:
                self.metrics.inc("engine.rejected")
                raise QueueFullError(
                    f"queue at max_queue={self.max_queue} "
                    f"({len(self._pending)} pending) — request rejected "
                    "(overload backpressure; retry later or raise max_queue)")
            req.rid = self._next_rid
            self._next_rid += 1
            self._pending.append(req)
            self._open[req.rid] = req
            depth = len(self._pending)
        self.metrics.gauge(
            "engine.max_queue_depth",
            max(int(self.metrics.raw("engine.max_queue_depth") or 0), depth))
        if spans.enabled():
            req.ticket.span = spans.begin(
                "engine.request", parent=trace, rid=req.rid, n=req.n,
                replica=self.replica_id) or None
        return req.ticket

    @staticmethod
    def _as_batch(x_init) -> np.ndarray:
        x_init = np.asarray(x_init, np.float32)
        if x_init.ndim == 3:
            x_init = x_init[None]
        if x_init.ndim != 4:
            raise ValueError(f"x_init must be (n, H, W, C) or (H, W, C), "
                             f"got shape {x_init.shape}")
        return x_init

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._pending)

    # ------------------------------------------------------------- programs

    def ensure_program(self, config: SamplerConfig, bucket: int):
        """The ONLY compile site. Dispatch calls this too — a serve-time miss
        (a config/bucket warmup didn't cover) compiles and is counted, so
        ``stats['compiles']`` staying flat after warmup proves zero serve-time
        compiles."""
        key = (config, bucket)
        prog = self._programs.get(key)
        if prog is None:
            self._check_config(config)
            if config.sp_degree > 1:
                shards = data_axis_size(self._sp_mesh(config.sp_degree))
                if bucket % shards:
                    raise ValueError(
                        f"bucket {bucket} does not divide the sp config's "
                        f"data axis ({shards} = {self._n_devices()} devices "
                        f"/ sp_degree {config.sp_degree}); pick buckets that "
                        "divide it, or a larger sp_degree (which shrinks the "
                        "data axis)")
            faults.fire("serve.compile", tag=f"bucket:{bucket}|")
            self._mark(f"compile bucket={bucket}", budget_s=4 * self.stall_s)
            prog = self._build_program(config, bucket)
            self._programs[key] = prog
            self.metrics.inc("engine.compiles")
        return prog

    # -------------------------------------------------- sequence parallelism

    def _devices(self) -> list:
        """The devices sp meshes are built over: the engine mesh's devices
        when one was given (sp subdivides the same hardware), else every
        local device."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return jax.local_devices()

    def _n_devices(self) -> int:
        return len(self._devices())

    def _check_config(self, config: SamplerConfig) -> None:
        """A model whose trunk has no path for a sampler option refuses it by
        name (``models/hybrid.py``: quant, fused, the step caches, sp), at
        submit and before any compile, instead of failing on a shape."""
        refuse = getattr(self.model, "refuse_sampler_config", None)
        if refuse is not None:
            refuse(config)

    def _sp_mesh(self, degree: int):
        """The (data, seq) mesh for one sp_degree — built once, shared by
        every config at that degree (data-major, so each seq group is a
        contiguous ICI neighborhood)."""
        mesh = self._sp_meshes.get(degree)
        if mesh is None:
            devices = self._devices()
            if len(devices) % degree:
                raise ValueError(
                    f"sp_degree={degree} does not divide the "
                    f"{len(devices)} local device(s) — the (data, seq) mesh "
                    "needs a whole data axis; pick an sp_degree from the "
                    "divisors of the device count")
            mesh = make_mesh({"data": len(devices) // degree, "seq": degree},
                             devices=np.asarray(devices))
            self._sp_meshes[degree] = mesh
        return mesh

    def _mesh_for(self, config: SamplerConfig):
        """The mesh a config's programs run on: the engine's own mesh for
        the degree-1 (default) configs — the existing path, untouched — else
        the per-degree (data, seq) mesh."""
        if config.sp_degree == 1:
            return self.mesh
        return self._sp_mesh(config.sp_degree)

    def _sharding_for(self, config: SamplerConfig):
        """Batch sharding for a config's inputs, or None off-mesh."""
        mesh = self._mesh_for(config)
        return batch_sharding(mesh) if mesh is not None else None

    def _sp_attn_mode(self, config: SamplerConfig) -> str:
        """Resolve the attention strategy: 'ulysses' needs the head count
        divisible by the seq axis (it reshards heads<->sequence with
        all-to-alls — parallel/ulysses.py raises SeqParallelConfigError
        otherwise), so it falls back to the ring, which has no head
        constraint, instead of failing the warmup."""
        if (config.sp_mode == "ulysses"
                and self.model.num_heads % config.sp_degree):
            return "ring"
        return config.sp_mode

    def _model_for(self, config: SamplerConfig):
        """The model variant a config's programs trace: ``quant``, ``fused``,
        the sp mesh, and the sp axis names are all fields of the
        (hash-by-value) module, so quant/float, fused/unfused and sp/non-sp
        programs can never collide in jit/AOT caches. sp composes with quant
        and fused: the sp clone starts from the quant/fused clone (under sp
        the fused attention falls back in-model, but the fused Mlp still
        applies)."""
        base = self.model
        if config.quant or config.fused:
            key = (config.quant, config.fused)
            base = self._quant_models.get(key)
            if base is None:
                base = self._quant_models[key] = self.model.clone(
                    quant=config.quant, fused=config.fused)
        if config.sp_degree == 1:
            return base
        key = (config.sp_mode, config.sp_degree, config.quant, config.fused)
        model = self._sp_models.get(key)
        if model is None:
            from ddim_cold_tpu.models.vit import sp_clone

            model = self._sp_models[key] = sp_clone(
                base, self._sp_mesh(config.sp_degree),
                sp_mode=config.sp_mode)
        return model

    def _params_for(self, config: SamplerConfig):
        if config.student:
            if self.student_params is None:
                raise ValueError(
                    "config.student=True but this engine holds no student "
                    "tree — pass student_params= at construction (the "
                    "distilled checkpoint from train/distill.py)")
            float_tree = self.student_params
        else:
            float_tree = self.params
        if not config.quant:
            base = float_tree
        else:
            # one int8 tree per weight set (teacher / student), built lazily
            # on the first quant config that needs it and pinned for reuse
            attr = "_qparams_student" if config.student else "_qparams"
            base = getattr(self, attr)
            if base is None:
                from ddim_cold_tpu.ops import quant

                qp = quant.quantize_params(float_tree)
                base = (shard_params(qp, self.mesh)
                        if self.mesh is not None else qp)
                setattr(self, attr, base)
                if not config.student:
                    self.metrics.gauge("engine.param_bytes",
                                       quant.param_bytes(float_tree))
                    self.metrics.gauge("engine.param_bytes_quant",
                                       quant.param_bytes(base))
        if config.sp_degree == 1:
            return base
        # re-place (replicated) on the config's (data, seq) mesh, once per
        # (degree, quantization, weight set) — the sp executable rejects
        # params committed to a different mesh
        key = (config.sp_degree, bool(config.quant), bool(config.student))
        placed = self._sp_params.get(key)
        if placed is None:
            placed = self._sp_params[key] = shard_params(
                base, self._sp_mesh(config.sp_degree))
        return placed

    def _x_struct(self, bucket: int, config: SamplerConfig):
        H, W = self.model.img_size
        return jax.ShapeDtypeStruct((bucket, H, W, self.model.in_chans),
                                    jnp.float32,
                                    sharding=self._sharding_for(config))

    def _cache_struct(self, bucket: int, config: SamplerConfig):
        shape = (bucket, self.model.num_patches + 1, self.model.embed_dim)
        sharding = self._sharding_for(config)
        s = jax.ShapeDtypeStruct(shape, self.model.dtype, sharding=sharding)
        if config.cache_mode == "adaptive":
            # the drift gate's reference image rides the carry (f32,
            # x-shaped) — see ops/step_cache.init_cache
            H, W = self.model.img_size
            x_ref = jax.ShapeDtypeStruct(
                (bucket, H, W, self.model.in_chans), jnp.float32,
                sharding=sharding)
            return (s, s, x_ref)
        return (s, s)

    def _mask_struct(self, bucket: int, config: SamplerConfig):
        H, W = self.model.img_size
        return jax.ShapeDtypeStruct((bucket, H, W, 1), jnp.float32,
                                    sharding=self._sharding_for(config))

    def _program_spec(self, config: SamplerConfig, bucket: int):
        """The ``(jitted scan, positional args, static kwargs)`` triple this
        (config, bucket) lowers — the single source of program identity.
        :meth:`_build_program` compiles the triple; :meth:`program_fingerprint`
        traces the SAME triple to a jaxpr for warmup dedup, so the two can
        never disagree about what a key would compile.

        ``preview_every > 0`` selects the sequence-returning variant of the
        SAME scan — trajectory frames are the preview stream and the final
        frame is the result (bitwise the last-only output), so previews cost
        one program per (config, bucket) like everything else and zero extra
        compiles at serve time. ``task`` picks the scan family: inpaint has
        its own constrained scan; the other tasks reuse the plain programs
        (their task-ness lives entirely in the init, so e.g. draft and
        guided-sample configs with equal fields share an executable).
        ``steps > 0`` picks the few-step family (ops/sampling.py): one scan
        over the explicit step-index schedule per k, the final jump-to-clean
        update outside the scan — so k=1 lowers scan-free."""
        x = self._x_struct(bucket, config)
        model, params = self._model_for(config), self._params_for(config)
        seq = config.preview_every > 0
        if config.task == "inpaint":
            if config.cached:
                return _inpaint_cached_spec(
                    model, params, x, self._mask_struct(bucket, config),
                    self._key0, self._cache_struct(bucket, config), config,
                    seq)
            fn = (sampling._ddim_scan_inpaint_seq if seq
                  else sampling._ddim_scan_inpaint)
            return fn, (model, params, x, x,
                        self._mask_struct(bucket, config), self._key0), dict(
                k=config.k, t_start=config.t_start, eta=0.0, sequence=seq)
        if config.sampler == "cold":
            if config.cached:
                return _cold_cached_spec(model, params, x,
                                         self._cache_struct(bucket, config),
                                         config, seq)
            fn = sampling._cold_scan_seq if seq else sampling._cold_scan
            return fn, (model, params, x), dict(levels=config.levels,
                                                return_sequence=seq)
        if config.steps > 0:
            if config.cached:
                return _fewstep_cached_spec(
                    model, params, x, self._key0,
                    self._cache_struct(bucket, config), config, seq)
            fn = (sampling._ddim_scan_fewstep_seq if seq
                  else sampling._ddim_scan_fewstep)
            return fn, (model, params, x, self._key0), dict(
                steps=config.steps, t_start=config.t_start, eta=0.0,
                sequence=seq)
        if config.cached:
            if config.telemetry:
                return _ddim_cached_tel_spec(
                    model, params, x, self._key0,
                    self._cache_struct(bucket, config), config)
            return _ddim_cached_spec(model, params, x, self._key0,
                                     self._cache_struct(bucket, config),
                                     config, seq)
        fn = sampling._ddim_scan_sequence if seq else sampling._ddim_scan_last
        return fn, (model, params, x, self._key0), dict(
            k=config.k, t_start=config.t_start, eta=0.0)

    def _build_program(self, config: SamplerConfig, bucket: int):
        """AOT-compile the scan for this (config, bucket): trace with shape
        structs (no dummy allocation), compile, return the executable. The
        executable is called with the NON-static args only (params, x, …)."""
        fn, args, kwargs = self._program_spec(config, bucket)
        with ambient(self._mesh_for(config)):
            return fn.lower(*args, **kwargs).compile()

    def program_fingerprint(self, config: SamplerConfig, bucket: int):
        """Trace-only program identity: the constant-blind ``signature_hash``
        over the traced jaxpr + input avals, paired with a digest of every
        captured constant's bytes. Two (config, bucket) keys with equal
        fingerprints lower the SAME program — warmup dedups on this instead
        of compiling both (tracing costs milliseconds; XLA costs seconds).
        The consts digest is load-bearing: ``signature_hash`` is constant-
        blind by design (J006 uses that), but two configs whose scans bake
        different coefficient tables must NOT alias."""
        import hashlib

        from ddim_cold_tpu.analysis.jaxpr_checks import (iter_consts,
                                                         signature_hash)

        fn, args, kwargs = self._program_spec(config, bucket)
        with ambient(self._mesh_for(config)):
            traced = fn.trace(*args, **kwargs)
        sig = signature_hash(traced.jaxpr, traced.in_avals)
        h = hashlib.sha256()
        for c in iter_consts(traced.jaxpr):
            a = np.asarray(c)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
        return sig, h.hexdigest()

    def adopt_program(self, config: SamplerConfig, bucket: int,
                      src_key) -> None:
        """Alias an already-compiled executable under a second (config,
        bucket) key — warmup's dedup path, valid only when both keys'
        :meth:`program_fingerprint` match. Does not bump ``compiles``
        (nothing compiled); counted under ``engine.program_aliases``."""
        self._programs[(config, bucket)] = self._programs[src_key]
        self.metrics.inc("engine.program_aliases")

    # ------------------------------------------------------------- assembly

    def _request_init(self, req: Request) -> jax.Array:
        """The request's full init, drawn once at the request's own n —
        bitwise the direct sampler's draw (which depends on n); batches then
        take row slices (which don't). Editing tasks route through the SAME
        init builders the direct workloads.* functions use (one definition —
        the bitwise contract is structural)."""
        if req._x_full is None:
            H, W = self.model.img_size
            C = self.model.in_chans
            task = req.config.task
            if task == "draft":
                req._x_full = workload_tasks.draft_init(
                    req.key, jnp.asarray(req.x_init, jnp.float32),
                    req.config.t_start, self.model.total_steps)
            elif task == "interp":
                pair = jnp.asarray(req.x_init, jnp.float32)
                req._x_full = workload_tasks.interp_init(
                    req.key, pair[0], pair[1], req.n, req.config.t_start,
                    self.model.total_steps)
            elif task == "inpaint":
                # fresh noise start — x_init (the known image) rides along
                # as a batch extra, it is not the scan's initial state
                req._x_full = jax.random.normal(req.key, (req.n, H, W, C),
                                                jnp.float32)
            elif req.x_init is not None:
                req._x_full = jnp.asarray(req.x_init, jnp.float32)
            elif req.config.sampler == "cold":
                color = jax.random.normal(req.key, (req.n, 1, 1, C),
                                          jnp.float32)
                req._x_full = jnp.broadcast_to(color, (req.n, H, W, C))
            else:
                req._x_full = jax.random.normal(req.key, (req.n, H, W, C),
                                                jnp.float32)
        return req._x_full

    def _tag(self, plan: BatchPlan) -> str:
        """Fault/beacon tag: ``|``-separated fields naming the replica (when
        fleet-owned), the bucket, and every request in the batch
        (``match="req:3|"`` targets request 3; ``match="replica:r0|"``
        targets every batch of one replica)."""
        reqs = {id(req): req for req, *_ in plan.entries}
        head = f"replica:{self.replica_id}|" if self.replica_id else ""
        return (head + f"bucket:{plan.bucket}|"
                + "".join(f"req:{r.rid}|" for r in reqs.values()))

    def _assemble(self, plan: BatchPlan):
        """Background-thread H2D stage: build the padded bucket batch on
        device (init draws dispatch async; guided numpy starts upload here,
        overlapping the main loop's compute). Returns ``(plan, xs)`` with
        ``xs`` a tuple: the init batch first, then any per-task extras
        (``_EXTRA_INPUTS`` — inpaint's known/mask ride along, sliced and
        padded exactly like x; zero-padding rows carry mask 0, so they pass
        through the projection untouched).

        Batch-coupled (adaptive-gate) plans pad with ROW-0 REPLICAS of every
        input instead of zeros: the pad rows then evolve bit-identically to
        row 0, so their per-row drift equals row 0's and the gate's batch-max
        reduction is exactly what the direct unpadded call computes — the
        bitwise-vs-direct contract survives padding."""
        self._mark(f"assemble bucket={plan.bucket}")
        coupled = plan.config.batch_coupled

        def _pad(real_parts):
            first = real_parts[0]
            if coupled:
                return jnp.broadcast_to(
                    first[:1], (plan.padded_rows,) + first.shape[1:])
            return jnp.zeros((plan.padded_rows,) + first.shape[1:],
                             jnp.float32)

        # the eager slices, pads and concatenates below compile one small
        # XLA program for every new tuple of part shapes: counted, on this
        # (the assembling) thread
        compiles0 = compile_count()
        with self._stage("assemble", plan) as stage:
            faults.fire("serve.assemble", tag=self._tag(plan))
            parts = [self._request_init(req)[lo:hi]
                     for req, lo, hi, _ in plan.entries]
            if plan.padded_rows:
                parts.append(_pad(parts))
            x = (parts[0] if len(parts) == 1
                 else jnp.concatenate(parts, axis=0))
            sharding = self._sharding_for(plan.config)
            if sharding is not None:
                x = jax.device_put(x, sharding)
            xs = [x]
            for name in _EXTRA_INPUTS.get(plan.config.task, ()):
                cols = [jnp.asarray(req.extras[name][lo:hi], jnp.float32)
                        for req, lo, hi, _ in plan.entries]
                if plan.padded_rows:
                    cols.append(_pad(cols))
                e = (cols[0] if len(cols) == 1
                     else jnp.concatenate(cols, axis=0))
                if sharding is not None:
                    e = jax.device_put(e, sharding)
                xs.append(e)
        self.metrics.inc("engine.assemble_compiles",
                         compile_count() - compiles0)
        self._record_stage(plan, stage)
        return plan, tuple(xs)

    @staticmethod
    def _stage(name: str, plan: BatchPlan):
        """One live layer span a batch and pipeline stage
        (``engine/<name>``): always recorded, and the one the profiler's
        timeline shows."""
        return spans.layer("engine/" + name, bucket=plan.bucket)

    @staticmethod
    def _record_stage(plan: BatchPlan, stage, **attrs) -> None:
        """Attribute one per-batch pipeline stage to every request riding
        the batch: a retroactive closed copy of the batch's ``stage`` span
        (same measured window) under each request's trace — so a split
        request's trace shows the stage once per batch it rode, and a
        coalesced batch's window appears under every participant. No-op
        with ticket traces disabled."""
        if not spans.enabled():
            return
        name = stage.name.rpartition("/")[2]
        for req in {id(r): r for r, *_ in plan.entries}.values():
            spans.record(req.ticket.span, name, stage.t0, stage.t1,
                         bucket=plan.bucket, **attrs)

    def _assemble_safe(self, plan: BatchPlan):
        """Assembly with the exception CAPTURED, not raised — the prefetch
        generator must keep producing the other plans when one batch's
        assembly fails (device_prefetch forwards a raise to the consumer and
        stops, which would strand every later batch)."""
        try:
            plan, xs = self._assemble(plan)
            return plan, xs, None
        except Exception as exc:  # noqa: BLE001 — isolated per batch
            return plan, None, exc

    # ------------------------------------------------------------- dispatch

    def _cache_kind(self, config: SamplerConfig):
        """Spare-cache pool key suffix: delta/full/token all share the
        two-leaf (B, N+1, E) carry structure ("pair" — a recycled carry is
        interchangeable between them because every schedule's step 0
        refreshes before reading), while adaptive's third x_ref leaf needs
        its own pool. sp configs extend the key with their (mode, degree)
        identity: a carry committed to one mesh cannot be donated to a
        program compiled for another."""
        kind = "adaptive" if config.cache_mode == "adaptive" else "pair"
        if config.sp_degree > 1:
            return (kind, config.sp_mode, config.sp_degree)
        return kind

    def _take_cache(self, bucket: int, config: SamplerConfig):
        cache = self._spare_caches.pop((bucket, self._cache_kind(config)),
                                       None)
        if cache is None:
            H, W = self.model.img_size
            cache = step_cache.init_cache(bucket, self.model.num_patches + 1,
                                          self.model.embed_dim,
                                          self.model.dtype,
                                          mode=config.cache_mode,
                                          img_shape=(H, W,
                                                     self.model.in_chans))
            cache = step_cache.shard_cache(cache, self._mesh_for(config))
        return cache

    def _recycle_cache(self, bucket: int, config: SamplerConfig,
                       cache_out) -> None:
        self._spare_caches[(bucket, self._cache_kind(config))] = cache_out

    def prewarm_cache(self, config: SamplerConfig, bucket: int) -> None:
        """Pre-allocate the spare step-cache carry for a cached (config,
        bucket) on the config's mesh — warmup calls this next to
        ``ensure_program`` so the first cached dispatch donates a pool-owned
        buffer instead of paying the allocation inline (sp configs get their
        per-mesh carries prebuilt the same way; no-op when the pool already
        holds a compatible carry)."""
        if not config.cached:
            return
        key = (bucket, self._cache_kind(config))
        if key not in self._spare_caches:
            self._spare_caches[key] = self._take_cache(bucket, config)

    def _dispatch(self, plan: BatchPlan, xs):
        prog = self.ensure_program(plan.config, plan.bucket)
        params = self._params_for(plan.config)
        self._mark(f"dispatch bucket={plan.bucket}")
        with self._stage("dispatch", plan) as stage:
            faults.fire("serve.dispatch", tag=self._tag(plan))
            out = self._call_program(plan, prog, params, xs)
        self.metrics.inc("engine.dispatches")
        self.metrics.inc("engine.rows", plan.rows)
        self.metrics.inc("engine.padded_rows", plan.padded_rows)
        self._record_stage(plan, stage)
        return out

    def _call_program(self, plan: BatchPlan, prog, params, xs):
        """The batch's program with the arguments its task takes; cached
        configs take a spare cache carry and hand back the one returned."""
        if plan.config.task == "inpaint":
            x, known, m = xs
            if plan.config.cached:
                out, cache_out = prog(
                    params, x, known, m, self._key0,
                    self._take_cache(plan.bucket, plan.config))
                self._recycle_cache(plan.bucket, plan.config, cache_out)
            else:
                out = prog(params, x, known, m, self._key0)
        elif plan.config.sampler == "cold":
            x, = xs
            if plan.config.cached:
                out, cache_out = prog(
                    params, x, self._take_cache(plan.bucket, plan.config))
                self._recycle_cache(plan.bucket, plan.config, cache_out)
            else:
                out = prog(params, x)
        elif plan.config.cached:
            x, = xs
            if plan.config.telemetry:
                out, cache_out, aux = prog(
                    params, x, self._key0,
                    self._take_cache(plan.bucket, plan.config))
                out = (out, aux)
            else:
                out, cache_out = prog(
                    params, x, self._key0,
                    self._take_cache(plan.bucket, plan.config))
            self._recycle_cache(plan.bucket, plan.config, cache_out)
        else:
            x, = xs
            out = prog(params, x, self._key0)
        return out

    def _dispatch_retry(self, plan: BatchPlan, xs):
        """Dispatch with capped exponential backoff on the retryable fault
        class. The donated input is rebuilt per attempt when the failed call
        already consumed it (donation deletes the buffer even on error; only
        ``xs[0]`` — the scan state — is ever donated, the conditioning extras
        are not)."""
        delay = self.retry_base_s
        for attempt in range(self.max_retries + 1):
            try:
                return self._dispatch(plan, xs)
            except RETRYABLE_EXCEPTIONS:
                if attempt == self.max_retries:
                    raise
                self.metrics.inc("engine.retries")
                time.sleep(min(delay, self.retry_cap_s))
                delay = min(delay * 2, self.retry_cap_s)
                if getattr(xs[0], "is_deleted", lambda: False)():
                    _, xs, err = self._assemble_safe(plan)
                    if err is not None:
                        raise err
        raise AssertionError("unreachable: loop returns or raises")

    def _subplan(self, plan: BatchPlan, entries) -> BatchPlan:
        """A sub-batch of ``entries`` repacked densely at the SAME bucket —
        bisection recovery reuses the compiled program, it never compiles."""
        packed, offset = [], 0
        for req, lo, hi, _ in entries:
            packed.append((req, lo, hi, offset))
            offset += hi - lo
        return BatchPlan(config=plan.config, bucket=plan.bucket,
                         entries=tuple(packed), rows=offset)

    def _dispatch_safe(self, plan: BatchPlan, xs) -> list:
        """Dispatch with full failure isolation; returns the list of
        (plan, out) that actually went to the device.

        Deadlines are re-checked here (plan-time admission already filtered,
        but a request can expire while earlier batches run): expired entries
        fail fast, and a batch with no live entries left skips the device
        entirely. A deterministic batch failure bisects on request
        boundaries — halves re-assemble at the same bucket and recurse;
        a single-request batch that still fails is the poisoned one:
        quarantined, with the stage exception as cause."""
        now = time.perf_counter()
        for req, *_ in plan.entries:
            if req.deadline is not None and now > req.deadline \
                    and not req.ticket.done:
                self.metrics.inc("engine.deadline_expired", key="dispatch")
                self._fail_request(req, DeadlineExceeded(
                    f"request {req.rid} missed its deadline before dispatch "
                    f"on {self._rname} (expired {now - req.deadline:.3f}s "
                    "ago waiting for a bucket) — failing fast instead of "
                    "occupying one"))
        if all(req.ticket.failed for req, *_ in plan.entries):
            self.metrics.inc("engine.skipped_batches")
            return []
        try:
            return [(plan, self._dispatch_retry(plan, xs))]
        except Exception as exc:  # noqa: BLE001 — isolate, bisect, quarantine
            self.metrics.inc("engine.failed_batches", key="dispatch")
            reqs = list({id(r): r for r, *_ in plan.entries}.values())
            if len(reqs) == 1:
                req = reqs[0]
                if not req.ticket.done:
                    err = RequestQuarantinedError(
                        f"request {req.rid} deterministically fails its "
                        f"batch (bucket {plan.bucket}) on {self._rname} — "
                        "quarantined by bisection; batchmates completed "
                        "separately")
                    err.__cause__ = exc
                    self.quarantined.append(req.rid)
                    self.metrics.inc("engine.quarantined")
                    self._fail_request(req, err)
                return []
            results = []
            mid = len(reqs) // 2
            for part in (reqs[:mid], reqs[mid:]):
                ids = {id(r) for r in part}
                sub = self._subplan(
                    plan, [e for e in plan.entries if id(e[0]) in ids])
                sub, sx, err = self._assemble_safe(sub)
                if err is not None:
                    self._fail_plan(sub, err, "assembly (bisect)")
                    continue
                results += self._dispatch_safe(sub, sx)
            return results

    # ---------------------------------------------------------------- fetch

    def _finish(self, plan: BatchPlan, out) -> None:
        """D2H + delivery: one blocking fetch per batch, rows copied into
        each ticket's buffer; padding rows are simply never read. A fetch
        failure fails only this batch's tickets.

        Preview-enabled configs fetch the whole trajectory: the scheduled
        intermediate x̂0 frames stream to each ticket's preview buffer
        (``Ticket.previews()``) before the FINAL frame — bitwise the
        last-only program's output — is delivered as the result.

        Telemetry configs (``SamplerConfig.telemetry``) arrive here as
        ``(images, (branch, drift))``: the static-shaped step aux is fetched
        with the batch, decoded once (``obs.device.summarize``), attached to
        every participating ticket BEFORE delivery (a ``result()`` waiter
        wakes to a populated ``Ticket.telemetry``), and its refresh/reuse
        step counts emitted. Batch == request for the coupled adaptive case;
        the static modes' aux is identical for every batchmate anyway."""
        try:
            self._mark(f"fetch bucket={plan.bucket}")
            with self._stage("fetch", plan) as stage:
                aux = None
                if plan.config.telemetry:
                    out, (br, dr) = out
                    aux = (np.asarray(br), np.asarray(dr))
                host = np.asarray(out)
                host = faults.fire("serve.fetch", tag=self._tag(plan),
                                   payload=host)
        except Exception as exc:  # noqa: BLE001 — isolated per batch
            self._fail_plan(plan, exc, "fetch")
            return
        self._record_stage(plan, stage)
        if aux is not None:
            cfg = plan.config
            summary = obs_device.summarize(
                obs_device.StepTelemetry(branch=aux[0], drift=aux[1]),
                cache_interval=cfg.cache_interval, cache_mode=cfg.cache_mode,
                cache_threshold=cfg.cache_threshold or 0.0,
                cache_tokens=cfg.cache_tokens)
            self.metrics.inc("engine.cache_refresh_steps",
                             summary["refreshes"])
            self.metrics.inc("engine.cache_reuse_steps", summary["reuses"])
            for req in {id(r): r for r, *_ in plan.entries}.values():
                req.ticket.telemetry = summary
        every = plan.config.preview_every
        if every:
            try:
                with self._stage("preview", plan) as stage:
                    faults.fire("serve.preview", tag=self._tag(plan))
                    steps = host.shape[0] - 1  # frame 0 is the init
                    for j in workload_preview.preview_indices(steps, every):
                        frame = host[j]
                        for req, lo, hi, offset in plan.entries:
                            if req.ticket._preview(
                                    j, lo, hi,
                                    frame[offset:offset + (hi - lo)]):
                                self.metrics.inc("engine.preview_frames")
            except Exception as exc:  # noqa: BLE001 — isolated per batch
                self._fail_plan(plan, exc, "preview")
                return
            self._record_stage(plan, stage)
            host = host[-1]
        for req, lo, hi, offset in plan.entries:
            if req.ticket._deliver(lo, hi, host[offset:offset + (hi - lo)]):
                self.metrics.observe("engine.latency_s",
                                     req.ticket.latency_s)
                sp = req.ticket.span
                if sp is not None:
                    sp.end(rows=req.n, latency_s=req.ticket.latency_s)
                with self._lock:
                    self._open.pop(req.rid, None)

    # -------------------------------------------------------------- failure

    def _fail_request(self, req: Request, exc: BaseException) -> None:
        with self._lock:
            self._open.pop(req.rid, None)
        if req.ticket._fail(exc):
            self.metrics.inc("engine.failed_tickets")
            sp = req.ticket.span
            if sp is not None:
                sp.end(error=type(exc).__name__)

    def _fail_plan(self, plan: BatchPlan, exc: BaseException,
                   stage: str) -> None:
        """Fail exactly this batch's tickets, the stage exception as cause."""
        self.metrics.inc("engine.failed_batches", key="plan")
        for req in {id(r): r for r, *_ in plan.entries}.values():
            if req.ticket.done:
                continue
            err = RequestFailedError(
                f"batch {stage} failed for request {req.rid} "
                f"(bucket {plan.bucket}, {self._rname}): {exc!r}")
            err.__cause__ = exc
            self._fail_request(req, err)

    # ----------------------------------------------------- watchdog / drain

    def _mark(self, label: str, budget_s: Optional[float] = None) -> None:
        self._last_mark = (time.monotonic(), label)
        wd = self._wd
        if wd is not None:
            wd.mark(label, budget_s)

    def _on_stall(self, label: str, silent: float) -> None:
        """Soft watchdog abort: a device interaction went silent past the
        stall budget (wedged backend — no exception will ever surface). Fail
        every unresolved ticket so no waiter hangs; batches fetched before
        the stall keep their delivered results."""
        self._stalled = True
        self.metrics.inc("engine.stalls")
        err = EngineStalledError(
            f"{self._rname} made no progress for {silent:.1f}s after "
            f"{label!r} — wedged backend; in-flight and queued tickets "
            "failed, results fetched before the stall stand")
        with self._lock:
            open_reqs = list(self._open.values())
        for req in open_reqs:
            self._fail_request(req, err)

    def drain(self, timeout: Optional[float] = None) -> dict:
        """Graceful shutdown: stop admission (``submit`` raises
        :class:`EngineClosedError`), let an active :meth:`run` flush its
        in-flight batches, then deterministically fail everything still
        queued. Returns the final health snapshot plus ``"idle"``.

        When the idle wait TIMES OUT (``idle: False``) a :meth:`run` is
        still mid-flight, so the queued-request sweep is skipped — failing
        requests while their batches are on the device would race delivery
        and could resolve a ticket the pipeline is about to complete. The
        caller decides: wait again, or escalate (the fleet router treats a
        non-idle drain as a wedged replica).

        Idle-race audit (graftcheck T-rules): this sweep cannot double-fail
        or lose a request even when a :meth:`run` starts concurrently —
        both sides take the queue by SWAPPING ``_pending`` under ``_lock``
        (each request appears in exactly one swap), ``submit`` rejects once
        ``_closed`` is set under the same lock (nothing lands after either
        sweep), and a run() racing the idle wait fails its own swapped list
        through the same first-resolution-wins ``Ticket._fail`` path."""
        with self._lock:
            self._closed = True
        idle = self._idle.wait(timeout)
        if idle:
            with self._lock:
                pending, self._pending = self._pending, []
            for req in pending:
                self._fail_request(req, EngineClosedError(
                    f"{self._rname} drained with request {req.rid} "
                    "still queued"))
        report = self.health()
        report["idle"] = idle
        return report

    def health(self) -> dict:
        """Live health snapshot (also rendered into Ticket timeout
        messages): queue/engine state, failure counters (read from the
        obs metrics registry — this dict is a view, not a second source of
        truth), and realized fault injections by site. ``last_stage`` /
        ``stalled_for_s`` name the last pipeline beacon and its age — the
        structured "where is it stuck" answer a timed-out waiter needs."""
        with self._lock:
            depth = len(self._pending)
            open_n = len(self._open)
            mark_t, mark_label = self._last_mark
        now = time.monotonic()
        s = self.stats
        lat = latency_summary(s["latencies_s"])
        wait = latency_summary(s["queue_waits_s"])
        return {
            "replica": self.replica_id,
            "queue_depth": depth,
            "open_tickets": open_n,
            # per-ticket submit→deliver latency percentiles — the load
            # signal the fleet autoscaler scales on (serve/autoscale.py)
            "latency_p50_s": lat["p50_s"],
            "latency_p95_s": lat["p95_s"],
            "latency_p99_s": lat["p99_s"],
            # submit→plan wait, and the eager assembly ops' compiles: the
            # two host costs PR 23 found bounding the served rate
            "queue_wait_p50_s": wait["p50_s"],
            "queue_wait_p95_s": wait["p95_s"],
            "assemble_compiles": s["assemble_compiles"],
            "max_queue": self.max_queue,
            "uptime_s": now - self._t0,
            "last_progress_s": now - mark_t,
            "last_stage": mark_label,
            "stalled_for_s": round(now - mark_t, 3),
            "running": self._running,
            "closed": self._closed,
            "stalled": self._stalled,
            "compiles": s["compiles"],
            "dispatches": s["dispatches"],
            "retries": s["retries"],
            "failed_batches": s["failed_batches"],
            "failed_tickets": s["failed_tickets"],
            "quarantined": s["quarantined"],
            "deadline_expired": s["deadline_expired"],
            "rejected": s["rejected"],
            "skipped_batches": s["skipped_batches"],
            "stalls": s["stalls"],
            "faults_by_site": faults.snapshot()["by_site"],
        }

    # ------------------------------------------------------------------ run

    def run(self) -> dict:
        """Drain the queue: plan → assemble (background) → dispatch → fetch,
        pipelined. Returns a report for this drain (throughput over real
        rows — padding is excluded from img/s by construction). Failures
        never escape a batch: see the module docstring's isolation story."""
        t0 = time.perf_counter()
        s0 = self.stats
        compiles0 = s0["compiles"]
        counters0 = {k: s0[k] for k in
                     ("retries", "failed_tickets", "quarantined")}
        rows = padded = batches = 0
        n_lat0 = self.metrics.count("engine.latency_s")
        self._stalled = False
        self._running = True
        self._idle.clear()
        wd = None
        if self.stall_s > 0:
            wd = StallWatchdog(self.stall_s, exit_code=None,
                               on_abort=self._on_stall, name="engine")
            self._wd = wd
            wd.start()
        try:
            while not self._stalled:
                with self._lock:
                    pending, self._pending = self._pending, []
                    closed = self._closed
                if closed:
                    for req in pending:
                        self._fail_request(req, EngineClosedError(
                            f"{self._rname} drained with request {req.rid} "
                            "still queued"))
                    break
                if not pending:
                    break
                live = self._admit(pending)
                if not live:
                    continue
                self._mark(f"plan {len(live)} requests")
                with spans.layer("engine/plan", requests=len(live)) as stage:
                    plans = plan_batches(live, self.buckets)
                    stage.set(batches=len(plans))
                for req in live:
                    # submit → plan: the wait the queue added
                    submit = int(req.ticket.submit_time * 1e9)
                    self.metrics.observe("engine.queue_wait_s",
                                         (stage.t0 - submit) / 1e9)
                    spans.record(req.ticket.span, "queue_wait",
                                 submit, stage.t0)
                    spans.record(req.ticket.span, "plan", stage.t0, stage.t1,
                                 batches=len(plans))
                inflight: deque = deque()
                for plan, xs, err in device_prefetch(
                        plans, self._assemble_safe,
                        depth=self.prefetch_depth, stage=None):
                    if self._stalled:
                        break
                    if err is not None:
                        self._fail_plan(plan, err, "assembly")
                        continue
                    for item in self._dispatch_safe(plan, xs):
                        inflight.append(item)
                        batches += 1
                        rows += item[0].rows
                        padded += item[0].padded_rows
                    while len(inflight) > self.inflight:
                        self._finish(*inflight.popleft())
                while inflight:
                    self._finish(*inflight.popleft())
        finally:
            self._running = False
            if wd is not None:
                wd.done()
                self._wd = None
            self._idle.set()
        wall = time.perf_counter() - t0
        s1 = self.stats
        completed = self.metrics.samples("engine.latency_s")[n_lat0:]
        return {
            "batches": batches,
            "rows": rows,
            "padded_rows": padded,
            "wall_s": wall,
            "img_per_sec": rows / wall if wall > 0 else 0.0,
            "latency": latency_summary(completed),
            "compiles": s1["compiles"] - compiles0,
            "max_queue_depth": s1["max_queue_depth"],
            "stalled": self._stalled,
            **{k: s1[k] - v0 for k, v0 in counters0.items()},
        }

    def _admit(self, pending) -> list:
        """Plan-time deadline gate: expired requests fail fast HERE, before
        they cost a bucket slot or an assembly."""
        now = time.perf_counter()
        live = []
        for req in pending:
            if req.deadline is not None and now > req.deadline:
                self.metrics.inc("engine.deadline_expired", key="plan")
                self._fail_request(req, DeadlineExceeded(
                    f"request {req.rid} missed its deadline while queued "
                    f"on {self._rname} (expired {now - req.deadline:.3f}s "
                    "before planning)"))
            else:
                live.append(req)
        return live


def _ddim_cached_spec(model, params, x, key, cache, config: SamplerConfig,
                      seq: bool = False):
    fn = (sampling._ddim_scan_cached_seq if seq
          else sampling._ddim_scan_cached)
    return fn, (model, params, x, key, cache), dict(
        k=config.k, t_start=config.t_start,
        eta=0.0, cache_interval=config.cache_interval,
        cache_mode=config.cache_mode,
        cache_threshold=config.cache_threshold,
        cache_tokens=config.cache_tokens or None, sequence=seq)


def _ddim_cached_tel_spec(model, params, x, key, cache,
                          config: SamplerConfig):
    return sampling._ddim_scan_cached_tel, (model, params, x, key, cache), \
        dict(k=config.k, t_start=config.t_start,
             eta=0.0, cache_interval=config.cache_interval,
             cache_mode=config.cache_mode,
             cache_threshold=config.cache_threshold,
             cache_tokens=config.cache_tokens or None)


def _fewstep_cached_spec(model, params, x, key, cache,
                         config: SamplerConfig, seq: bool = False):
    fn = (sampling._ddim_scan_fewstep_cached_seq if seq
          else sampling._ddim_scan_fewstep_cached)
    return fn, (model, params, x, key, cache), dict(
        steps=config.steps, t_start=config.t_start, eta=0.0,
        cache_interval=config.cache_interval,
        cache_mode=config.cache_mode,
        cache_threshold=config.cache_threshold,
        cache_tokens=config.cache_tokens or None, sequence=seq)


def _cold_cached_spec(model, params, x, cache, config: SamplerConfig,
                      seq: bool = False):
    fn = (sampling._cold_scan_cached_seq if seq
          else sampling._cold_scan_cached)
    return fn, (model, params, x, cache), dict(
        levels=config.levels, return_sequence=seq,
        cache_interval=config.cache_interval,
        cache_mode=config.cache_mode,
        cache_threshold=config.cache_threshold,
        cache_tokens=config.cache_tokens or None)


def _inpaint_cached_spec(model, params, x, mask, key, cache,
                         config: SamplerConfig, seq: bool = False):
    # known shares x's struct: both are (bucket, H, W, C) f32 batch-sharded
    fn = (sampling._ddim_scan_inpaint_cached_seq if seq
          else sampling._ddim_scan_inpaint_cached)
    return fn, (model, params, x, x, mask, key, cache), dict(
        k=config.k, t_start=config.t_start, eta=0.0,
        cache_interval=config.cache_interval,
        cache_mode=config.cache_mode,
        cache_threshold=config.cache_threshold,
        cache_tokens=config.cache_tokens or None, sequence=seq)
