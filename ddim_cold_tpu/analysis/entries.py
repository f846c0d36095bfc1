"""The traced-entry registry: which real hot paths graftcheck proves.

Each :class:`Entry` names one jitted entry point and the abstract arguments
(``ShapeDtypeStruct``) to trace it with — the SAME functions the samplers,
the trainer and the serving engine dispatch, at the tiny model geometry
``tests/test_serve.py`` uses (so the serve-sweep signature check covers
exactly the warmed ``(SamplerConfig, bucket)`` pairs that suite proves
empirically). Tracing is abstract end to end: params come from
``jax.eval_shape(model.init, ...)``, quantized params from
``eval_shape(quantize_params, ...)`` — no parameter is ever materialized.

Geometry is small but structurally faithful — every check here is about
graph *structure* (dtypes, aliasing, constants, callbacks, trace identity),
which does not change with width/depth, only with code.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ddim_cold_tpu.analysis import jaxpr_checks
from ddim_cold_tpu.analysis.findings import Finding

#: tests/test_serve.py's model geometry — keep in sync (test_analysis.py
#: asserts equality so the serve sweep and the empirical guard can't drift)
TINY = dict(img_size=(16, 16), patch_size=8, embed_dim=32, depth=2,
            num_heads=4, total_steps=2000)
K = 500    # the 4-reverse-step stride test_serve.py warms
N = 4      # batch rows for the non-serve entries

#: the warmed (SamplerConfig, buckets) sweep tests/test_serve.py +
#: tests/test_quant.py + tests/test_workloads.py cover — built lazily
#: (SamplerConfig import). Entries must differ STRUCTURALLY (trip count,
#: function identity, quant, sequence flag, avals) — signature_hash does
#: not see constant values, so e.g. two t_starts with the same step count
#: would collide by design, not by bug.
def serve_sweep():
    from ddim_cold_tpu.serve.batching import SamplerConfig

    # Bucket policy: the bucket axis enters every program the same way (a
    # batch-dim substitution), so two-bucket stability/distinctness is
    # proven by ONE (4, 8) witness per scan family — ddim, cold, inpaint,
    # the sequence variant, fewstep (plus the warmed pairs tests pin).
    # Every other entry traces at (4,) only: each extra bucket is a full
    # extra trace in BOTH J006 worlds, and the single-bucket entries'
    # program structure is already bucket-proven by their family witness.
    sweep = [
        ("ddim_k500", SamplerConfig(k=K), (4, 8)),
        ("ddim_k500_ci2", SamplerConfig(k=K, cache_interval=2), (4,)),
        # cache_mode="full" (whole-trunk reuse steps) had NO sweep entry
        # until the X001 sweep-completeness rule flagged it: a legal,
        # serveable mode with zero J006 coverage
        ("ddim_k500_ci2_full",
         SamplerConfig(k=K, cache_interval=2, cache_mode="full"), (4,)),
        # adaptive/token caching (ISSUE 8). ONE adaptive threshold value in
        # the whole sweep: signature_hash is constant-blind, so a second
        # threshold would collide by design. Distinct token_k values ARE
        # structurally distinct (the gathered (B, k, E) aval differs).
        ("ddim_k500_adapt",
         SamplerConfig(k=K, cache_interval=2, cache_mode="adaptive",
                       cache_threshold=0.05), (4,)),
        ("ddim_k500_adapt_qxla",
         SamplerConfig(k=K, cache_interval=2, cache_mode="adaptive",
                       cache_threshold=0.05, quant="xla"), (4,)),
        # device-telemetry variants (ISSUE 11): same cached samplers with a
        # per-step (branch, drift) aux — the extra scan outputs make them
        # structurally distinct from their plain counterparts
        ("ddim_k500_ci2_tel",
         SamplerConfig(k=K, cache_interval=2, telemetry=True), (4,)),
        ("ddim_k500_adapt_tel",
         SamplerConfig(k=K, cache_interval=2, cache_mode="adaptive",
                       cache_threshold=0.05, telemetry=True), (4,)),
        ("ddim_k500_tok3",
         SamplerConfig(k=K, cache_interval=2, cache_mode="token",
                       cache_tokens=3), (4,)),
        ("ddim_k500_tok2",
         SamplerConfig(k=K, cache_interval=2, cache_mode="token",
                       cache_tokens=2), (4,)),
        ("cold_l4_adapt",
         SamplerConfig(sampler="cold", levels=4, cache_interval=2,
                       cache_mode="adaptive", cache_threshold=0.05), (4,)),
        ("inpaint_k500_ci2",
         SamplerConfig(task="inpaint", k=K, cache_interval=2), (4,)),
        ("inpaint_k500_tok3",
         SamplerConfig(task="inpaint", k=K, cache_interval=2,
                       cache_mode="token", cache_tokens=3), (4,)),
        ("cold_l4", SamplerConfig(sampler="cold", levels=4), (4, 8)),
        ("ddim_k500_t999", SamplerConfig(k=K, t_start=999), (4,)),
        ("ddim_k500_qxla", SamplerConfig(k=K, quant="xla"), (4,)),
        # editing workloads (ddim_cold_tpu/workloads) + preview variants:
        # trip counts at K=500/T=2000 — t=None→4, t1200→3, t999→2, t400→1
        ("ddim_k500_pv2", SamplerConfig(k=K, preview_every=2), (4, 8)),
        ("ddim_k500_ci2_pv2",
         SamplerConfig(k=K, cache_interval=2, preview_every=2), (4,)),
        ("inpaint_k500", SamplerConfig(task="inpaint", k=K), (4, 8)),
        ("inpaint_k500_qxla",
         SamplerConfig(task="inpaint", k=K, quant="xla"), (4,)),
        ("inpaint_k500_pv2",
         SamplerConfig(task="inpaint", k=K, preview_every=2), (4,)),
        ("inpaint_k500_ci2_pv2",
         SamplerConfig(task="inpaint", k=K, cache_interval=2,
                       preview_every=2), (4,)),
        ("superres_l3",
         SamplerConfig(task="superres", sampler="cold", levels=3), (4,)),
        ("superres_l3_ci2",
         SamplerConfig(task="superres", sampler="cold", levels=3,
                       cache_interval=2), (4,)),
        # cached+preview crossings (X001): each scan family's cached
        # SEQUENCE variant is a distinct program (_*_cached_seq) the sweep
        # previously never traced — cold here, inpaint and fewstep below
        ("superres_l3_ci2_pv1",
         SamplerConfig(task="superres", sampler="cold", levels=3,
                       cache_interval=2, preview_every=1), (4,)),
        ("superres_l3_pv1",
         SamplerConfig(task="superres", sampler="cold", levels=3,
                       preview_every=1), (4,)),
        ("draft_k500_t1200",
         SamplerConfig(task="draft", k=K, t_start=1200), (4,)),
        ("draft_k500_t1200_ci2",
         SamplerConfig(task="draft", k=K, t_start=1200, cache_interval=2),
         (4,)),
        ("interp_k500_t400",
         SamplerConfig(task="interp", k=K, t_start=400), (4,)),
        # few-step distilled family (ISSUE 17): scan over steps-1 schedule
        # updates + the final jump-to-clean forward OUTSIDE the scan, so
        # steps=1 lowers scan-free and every k is structurally distinct
        # from the stride family's equal-trip-count scans. NO student
        # variants here: a student config runs the teacher's program on
        # different params (warmup dedup relies on exactly that), so a
        # student entry would be a deliberate J006 collision.
        ("ddim_fs1", SamplerConfig(steps=1), (4, 8)),
        ("ddim_fs2", SamplerConfig(steps=2), (4,)),
        ("ddim_fs4", SamplerConfig(steps=4), (4,)),
        ("ddim_fs4_ci2", SamplerConfig(steps=4, cache_interval=2), (4,)),
        ("ddim_fs4_ci2_pv1",
         SamplerConfig(steps=4, cache_interval=2, preview_every=1), (4,)),
        ("ddim_fs2_pv1", SamplerConfig(steps=2, preview_every=1), (4,)),
        ("ddim_fs1_qxla", SamplerConfig(steps=1, quant="xla"), (4,)),
    ]
    # sequence-parallel program family (sp_mode/sp_degree — the engine's
    # (data, seq)-mesh executables). Gated on the PROCESS's device count:
    # the graftcheck CLI world runs at 1 CPU device (no sp geometry exists
    # there), the pytest world at 8 via conftest's
    # --xla_force_host_platform_device_count. The gate is deterministic
    # within a process, so both J006 worlds see the same sweep and hash
    # stability is preserved — each world is internally consistent.
    n_dev = jax.device_count()
    if n_dev >= 2 and n_dev % 2 == 0:
        sweep += [
            # ulysses vs ring at the same geometry must hash distinctly
            # (all_to_all pair vs ppermute scan inside the shard_map jaxpr)
            ("ddim_k500_sp2u",
             SamplerConfig(k=K, sp_mode="ulysses", sp_degree=2), (4, 8)),
            ("ddim_k500_sp2r",
             SamplerConfig(k=K, sp_mode="ring", sp_degree=2), (4,)),
            # static (non-adaptive) caching composes with sp — the carry
            # rides the same (data, seq) mesh
            ("ddim_k500_ci2_sp2u",
             SamplerConfig(k=K, cache_interval=2, sp_mode="ulysses",
                           sp_degree=2), (4,)),
        ]
    if n_dev >= 8 and n_dev % 8 == 0:
        # TINY's 4 heads do not divide a seq axis of 8: this entry proves
        # the ulysses→ring fallback traces (and hashes) at the all-local
        # geometry — distinct from sp2r because the mesh differs
        sweep.append(
            ("ddim_k500_sp8u_fallback",
             SamplerConfig(k=K, sp_mode="ulysses", sp_degree=8), (8,)))
    return sweep


@dataclass
class Entry:
    """One traced entry point. ``jitted(*static_args, *dyn_args, **kwargs)``
    is the exact dispatch; ``path`` is where findings point."""

    name: str
    path: str
    jitted: Any
    dyn_args: tuple
    static_args: tuple = ()
    kwargs: dict = field(default_factory=dict)
    donates: bool = False
    #: layer hints: ``tokens`` = the logical token count (the P003/M002
    #: padding checks charge padded extents against it), ``rows`` = batch
    #: rows, ``memory`` = run the M-rules' liveness walk over this entry
    meta: dict = field(default_factory=dict)

    def _call(self, *dyn):
        return self.jitted(*self.static_args, *dyn, **self.kwargs)

    def trace(self):
        return jax.make_jaxpr(self._call)(*self.dyn_args)

    def out_shapes(self):
        return jax.eval_shape(self._call, *self.dyn_args)

    def args_info(self):
        return self.jitted.lower(*self.static_args, *self.dyn_args,
                                 **self.kwargs).args_info


class Context:
    """One independently constructed (model, abstract params) world. The
    signature check builds two and demands identical trace hashes — flax
    modules hash by field values, so a fresh instance MUST retrace to the
    same program or serving would recompile on every engine restart."""

    def __init__(self):
        from ddim_cold_tpu.models import DiffusionViT
        from ddim_cold_tpu.ops import quant

        self.model = DiffusionViT(**TINY)
        H, W = self.model.img_size
        self.key = jax.random.PRNGKey(0)
        x2 = jax.ShapeDtypeStruct((2, H, W, self.model.in_chans), jnp.float32)
        t2 = jax.ShapeDtypeStruct((2,), jnp.int32)
        self.params = jax.eval_shape(self.model.init, self.key, x2,
                                     t2)["params"]
        self.qmodel = self.model.clone(quant="xla")
        self.qparams = jax.eval_shape(quant.quantize_params, self.params)
        self._sp_meshes: dict = {}
        self._sp_models: dict = {}

    def sp_mesh(self, degree: int):
        """The (data, seq) mesh for one sp_degree — the same geometry
        Engine._sp_mesh builds (data-major over every visible device)."""
        from ddim_cold_tpu.parallel.mesh import make_mesh

        mesh = self._sp_meshes.get(degree)
        if mesh is None:
            n = jax.device_count()
            mesh = make_mesh({"data": n // degree, "seq": degree})
            self._sp_meshes[degree] = mesh
        return mesh

    def sp_model(self, config):
        """The sp model clone a config's programs trace — routed through
        models.sp_clone, the SAME resolver the engine uses, so the sweep's
        ulysses→ring fallback can never diverge from serving's."""
        from ddim_cold_tpu.models.vit import sp_clone

        key = (config.sp_mode, config.sp_degree, config.quant)
        model = self._sp_models.get(key)
        if model is None:
            base = self.qmodel if config.quant else self.model
            model = self._sp_models[key] = sp_clone(
                base, self.sp_mesh(config.sp_degree),
                sp_mode=config.sp_mode)
        return model

    def x(self, n: int):
        H, W = self.model.img_size
        return jax.ShapeDtypeStruct((n, H, W, self.model.in_chans),
                                    jnp.float32)

    def cache(self, n: int, mode: str = "delta"):
        from ddim_cold_tpu.ops import step_cache

        H, W = self.model.img_size
        return jax.eval_shape(
            lambda: step_cache.init_cache(n, self.model.num_patches + 1,
                                          self.model.embed_dim,
                                          self.model.dtype, mode=mode,
                                          img_shape=(H, W,
                                                     self.model.in_chans)))

    def mask(self, n: int):
        H, W = self.model.img_size
        return jax.ShapeDtypeStruct((n, H, W, 1), jnp.float32)


def build_entries(ctx: Context) -> list[Entry]:
    from ddim_cold_tpu.ops import quant, sampling
    from ddim_cold_tpu.train.step import create_train_state, make_train_step

    SAMP = "ddim_cold_tpu/ops/sampling.py"
    m, p, key = ctx.model, ctx.params, ctx.key
    x = ctx.x(N)
    ddim_kw = dict(k=K, t_start=None, eta=0.0)
    entries = [
        Entry("ddim_scan_last", SAMP, sampling._ddim_scan_last,
              (p, x, key), (m,), dict(ddim_kw), donates=True),
        Entry("ddim_scan_guided", SAMP, sampling._ddim_scan_last,
              (p, x, key), (m,), dict(ddim_kw, t_start=999), donates=True),
        Entry("ddim_scan_sequence", SAMP, sampling._ddim_scan_sequence,
              (p, x, key), (m,), dict(ddim_kw)),
        Entry("ddim_scan_cached", SAMP, sampling._ddim_scan_cached,
              (p, x, key, ctx.cache(N)), (m,),
              dict(ddim_kw, cache_interval=2, cache_mode="delta",
                   sequence=False), donates=True),
        Entry("ddim_scan_cached_adaptive", SAMP, sampling._ddim_scan_cached,
              (p, x, key, ctx.cache(N, "adaptive")), (m,),
              dict(ddim_kw, cache_interval=2, cache_mode="adaptive",
                   cache_threshold=0.05, sequence=False), donates=True),
        Entry("ddim_scan_cached_tel", SAMP, sampling._ddim_scan_cached_tel,
              (p, x, key, ctx.cache(N, "adaptive")), (m,),
              dict(ddim_kw, cache_interval=2, cache_mode="adaptive",
                   cache_threshold=0.05), donates=True),
        Entry("ddim_scan_cached_token", SAMP, sampling._ddim_scan_cached,
              (p, x, key, ctx.cache(N, "token")), (m,),
              dict(ddim_kw, cache_interval=2, cache_mode="token",
                   cache_tokens=3, sequence=False), donates=True),
        Entry("ddim_scan_inpaint_cached", SAMP,
              sampling._ddim_scan_inpaint_cached,
              (p, x, x, ctx.mask(N), key, ctx.cache(N)), (m,),
              dict(ddim_kw, cache_interval=2, cache_mode="delta",
                   sequence=False), donates=True),
        Entry("cold_scan", SAMP, sampling._cold_scan, (p, x), (m,),
              dict(levels=4, return_sequence=False), donates=True),
        Entry("cold_scan_seq", SAMP, sampling._cold_scan_seq, (p, x), (m,),
              dict(levels=4, return_sequence=True)),
        Entry("cold_scan_cached", SAMP, sampling._cold_scan_cached,
              (p, x, ctx.cache(N)), (m,),
              dict(levels=4, return_sequence=False, cache_interval=2,
                   cache_mode="delta"), donates=True),
        Entry("ddim_scan_inpaint", SAMP, sampling._ddim_scan_inpaint,
              (p, x, x, ctx.mask(N), key), (m,),
              dict(ddim_kw, sequence=False), donates=True),
        Entry("ddim_scan_inpaint_seq", SAMP, sampling._ddim_scan_inpaint_seq,
              (p, x, x, ctx.mask(N), key), (m,),
              dict(ddim_kw, sequence=True)),
        Entry("ddim_scan_last_w8a16", "ddim_cold_tpu/ops/quant.py",
              sampling._ddim_scan_last, (ctx.qparams, ctx.x(N), key),
              (ctx.qmodel,), dict(ddim_kw), donates=True),
        Entry("dequant_matmul_xla", "ddim_cold_tpu/ops/quant.py",
              jax.jit(quant.dequant_matmul, static_argnames=("mode",)),
              (jax.ShapeDtypeStruct((8, 32), jnp.bfloat16),
               jax.ShapeDtypeStruct((32, 64), jnp.int8),
               jax.ShapeDtypeStruct((64,), jnp.float32)),
              (), dict(mode="xla")),
    ]

    TRAIN = "ddim_cold_tpu/train/step.py"
    H, W = m.img_size
    noisy = jax.ShapeDtypeStruct((N, H, W, m.in_chans), jnp.float32)
    t = jax.ShapeDtypeStruct((N,), jnp.int32)
    state = jax.eval_shape(
        lambda k, nz, tt: create_train_state(m, k, 1e-3, 100, (nz, None, tt)),
        key, noisy, t)
    loss_rec = jax.ShapeDtypeStruct((), jnp.float32)
    entries.append(Entry(
        "train_step", TRAIN, make_train_step(m),
        (state, (noisy, noisy, t), key, loss_rec), donates=True))
    return entries + _longcat_entries(key)


#: ``models/longcat.py`` at the width of its tests: a double layer whose
#: expert layer (8 + 4 router outputs, 4 of them identities, top-3, experts
#: 0-3 held) is a shortcut round two rescaled-latent attentions and two
#: dense MLPs
LONGCAT_TOY = dict(
    model_type="longcat_flash", hidden_size=64, ffn_hidden_size=96,
    expert_ffn_hidden_size=32, num_layers=1, num_attention_heads=2,
    attention_bias=False, rms_norm_eps=1e-5, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000000, attention_method="MLA", mla_scale_q_lora=True,
    mla_scale_kv_lora=True, n_routed_experts=4, n_experts_routed=8,
    zero_expert_num=4, zero_expert_type="identity", moe_topk=3,
    routed_scaling_factor=6)


def _longcat_entries(key) -> list[Entry]:
    """The newest ``HybridDenoiser`` stack's forward and gradient, off the
    TPU: the plain-JAX paths of the latent attention and the grouped expert
    product, the identity term and the shortcut among what is traced."""
    from ddim_cold_tpu.models.hybrid import HybridDenoiser

    PATH = "ddim_cold_tpu/models/longcat.py"
    model = HybridDenoiser(trunk=LONGCAT_TOY, img_size=(16, 16), patch_size=4)
    x = jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.int32)
    params = jax.eval_shape(model.init, key, x, t)["params"]
    forward = jax.jit(lambda p, x, t: model.apply({"params": p}, x, t))
    grad = jax.jit(jax.grad(
        lambda p, x, t: jnp.sum(model.apply({"params": p}, x, t) ** 2)))
    return [Entry("longcat_forward", PATH, forward, (params, x, t)),
            Entry("longcat_grad", PATH, grad, (params, x, t))]


def run_entry_checks(max_const_bytes: int = 1 << 20,
                     traces: dict | None = None) -> list[Finding]:
    """J001–J005 over every registered entry. When ``traces`` is passed
    (a dict), each entry's ``(entry, closed_jaxpr)`` is stashed into it —
    the kernels layer (P-rules) walks these instead of re-tracing."""
    ctx = Context()
    findings: list[Finding] = []
    for e in build_entries(ctx):
        closed = e.trace()
        if traces is not None:
            traces[e.name] = (e, closed)
        out_shapes = e.out_shapes()
        findings += jaxpr_checks.check_accumulation(closed, e.name, e.path)
        findings += jaxpr_checks.check_weak_types(out_shapes, e.name, e.path)
        findings += jaxpr_checks.check_donation(
            e.args_info(), out_shapes, e.name, e.path,
            expect_donation=e.donates)
        findings += jaxpr_checks.check_constants(closed, e.name, e.path,
                                                 max_bytes=max_const_bytes)
        findings += jaxpr_checks.check_host_callbacks(closed, e.name, e.path)
    return findings


# ---------------------------------------------------------------------------
# J006 — the serve-sweep signature check
# ---------------------------------------------------------------------------

def _serve_entry(ctx: Context, config, bucket: int) -> Entry:
    """The exact dispatch serve/engine.py's ``_build_program`` AOT-compiles
    for (config, bucket) — same functions, same statics, same aval shapes —
    mirrored here so its trace identity is checked statically. The task and
    preview branches mirror too: inpaint has its own constrained scan (with
    known/mask avals), ``preview_every > 0`` selects the sequence variant."""
    from ddim_cold_tpu.ops import sampling

    model = ctx.qmodel if config.quant else ctx.model
    if config.sp_degree > 1:
        # the engine traces sp configs against the sp clone over the
        # per-degree (data, seq) mesh; the mesh appears in the shard_map
        # jaxpr params, so sp programs hash distinctly from non-sp (and
        # per-geometry) even though the arg avals are identical
        model = ctx.sp_model(config)
    params = ctx.qparams if config.quant else ctx.params
    x = ctx.x(bucket)
    seq = config.preview_every > 0
    cache_kw = dict(cache_interval=config.cache_interval,
                    cache_mode=config.cache_mode,
                    cache_threshold=config.cache_threshold,
                    cache_tokens=config.cache_tokens or None)
    if config.task == "inpaint":
        H, W = ctx.model.img_size
        mask = jax.ShapeDtypeStruct((bucket, H, W, 1), jnp.float32)
        if config.cached:
            fn = (sampling._ddim_scan_inpaint_cached_seq if seq
                  else sampling._ddim_scan_inpaint_cached)
            return Entry("serve", "", fn,
                         (params, x, ctx.x(bucket), mask, ctx.key,
                          ctx.cache(bucket, config.cache_mode)), (model,),
                         dict(k=config.k, t_start=config.t_start, eta=0.0,
                              sequence=seq, **cache_kw))
        fn = (sampling._ddim_scan_inpaint_seq if seq
              else sampling._ddim_scan_inpaint)
        return Entry("serve", "", fn,
                     (params, x, ctx.x(bucket), mask, ctx.key), (model,),
                     dict(k=config.k, t_start=config.t_start, eta=0.0,
                          sequence=seq))
    if config.sampler == "cold":
        if config.cached:
            fn = (sampling._cold_scan_cached_seq if seq
                  else sampling._cold_scan_cached)
            return Entry("serve", "", fn,
                         (params, x, ctx.cache(bucket, config.cache_mode)),
                         (model,),
                         dict(levels=config.levels, return_sequence=seq,
                              **cache_kw))
        fn = sampling._cold_scan_seq if seq else sampling._cold_scan
        return Entry("serve", "", fn, (params, x), (model,),
                     dict(levels=config.levels, return_sequence=seq))
    if config.steps > 0:
        if config.cached:
            fn = (sampling._ddim_scan_fewstep_cached_seq if seq
                  else sampling._ddim_scan_fewstep_cached)
            return Entry("serve", "", fn,
                         (params, x, ctx.key,
                          ctx.cache(bucket, config.cache_mode)), (model,),
                         dict(steps=config.steps, t_start=config.t_start,
                              eta=0.0, sequence=seq, **cache_kw))
        fn = (sampling._ddim_scan_fewstep_seq if seq
              else sampling._ddim_scan_fewstep)
        return Entry("serve", "", fn, (params, x, ctx.key), (model,),
                     dict(steps=config.steps, t_start=config.t_start,
                          eta=0.0, sequence=seq))
    if config.cached:
        if config.telemetry:
            # mirrors Engine._ddim_cached_tel_spec: the telemetry scan has
            # no `sequence` static (last-only by contract)
            return Entry("serve", "", sampling._ddim_scan_cached_tel,
                         (params, x, ctx.key,
                          ctx.cache(bucket, config.cache_mode)), (model,),
                         dict(k=config.k, t_start=config.t_start, eta=0.0,
                              **cache_kw))
        fn = (sampling._ddim_scan_cached_seq if seq
              else sampling._ddim_scan_cached)
        return Entry("serve", "", fn,
                     (params, x, ctx.key,
                      ctx.cache(bucket, config.cache_mode)), (model,),
                     dict(k=config.k, t_start=config.t_start, eta=0.0,
                          sequence=seq, **cache_kw))
    fn = (sampling._ddim_scan_sequence if seq
          else sampling._ddim_scan_last)
    return Entry("serve", "", fn,
                 (params, x, ctx.key), (model,),
                 dict(k=config.k, t_start=config.t_start, eta=0.0))


def serve_signatures(ctx: Context, findings: list | None = None,
                     traces: dict | None = None) -> dict[str, str]:
    """``"<label>:b<bucket>" → trace hash`` for the whole warmed sweep.
    When ``findings`` is passed, each trace is also run through the J007
    static-trip-count check (no extra tracing — the J006 trace is reused).
    When ``traces`` is passed (a dict), each subject's ``(config,
    closed_jaxpr)`` is stashed into it — the collective-order pass (C001/
    C002) consumes this cache instead of re-tracing the sweep, which is
    what keeps the full graftcheck run inside the CPU budget."""
    out = {}
    for label, config, buckets in serve_sweep():
        for bucket in buckets:
            e = _serve_entry(ctx, config, bucket)
            closed = e.trace()
            subject = f"{label}:b{bucket}"
            out[subject] = jaxpr_checks.signature_hash(closed, e.dyn_args)
            if findings is not None:
                findings += jaxpr_checks.check_static_trip_count(
                    closed, subject, "ddim_cold_tpu/serve/engine.py")
            if traces is not None:
                traces[subject] = (config, closed)
    return out


# ---------------------------------------------------------------------------
# 200px kernel/memory entries — the geometry that crashed r04
# ---------------------------------------------------------------------------

#: the north-star model the kernels/memory layers prove statically
NS_MODEL = "oxford_flower_200_p4"
NS_TOKENS = 2501   # (200/4)² patches + cls — the N Mosaic rejected on r04
NS_ROWS = 16       # the 200px batch the reference ships
NS_K = 20          # the north-star DDIM step count

_FLASH_PATH = "ddim_cold_tpu/ops/flash_attention.py"
_QUANT_PATH = "ddim_cold_tpu/ops/quant.py"
_BLOCK_PATH = "ddim_cold_tpu/ops/block_kernels.py"


def kernel_entries() -> list[Entry]:
    """First-class 200px entries (N=2501; f32, bf16, w8a16): the full
    sampler scans at 200px — every in-tree
    pallas_call at the EXACT geometry that crashed r04 — plus standalone
    flash forward/grad traces per (dtype, block config) covering the
    backward kernels (``dq`` + ``dkv`` at explicit blocks, the one ``dqkv``
    launch where they are left to it) and every ``FLASH_BLOCK_SWEEP`` row, the
    dequant-pallas kernel at the 200px trunk GEMM shapes, and the float
    trunk's token-wise kernels (``ln_qkv``, ``block_tail``). The TINY serve
    sweep contains zero pallas_calls (it serves quant="xla" only), so
    these entries ARE the kernels layer's real coverage.

    Tracing stays abstract end to end (eval_shape params); the whole
    registry traces in a few seconds on CPU."""
    from ddim_cold_tpu.models.vit import MODEL_CONFIGS, DiffusionViT
    from ddim_cold_tpu.ops import quant, sampling
    from ddim_cold_tpu.ops.flash_attention import (
        FLASH_BLOCK_SWEEP, NS_FLASH_BLOCKS, flash_attention,
    )

    cfg = MODEL_CONFIGS[NS_MODEL]
    key = jax.random.PRNGKey(0)
    entries: list[Entry] = []

    # full sampler programs, flash trunk at the tuned north-star blocks —
    # these feed BOTH layers (P over their pallas_calls, M over the scan).
    # The fused variants dispatch the trunk megakernels (fused attention +
    # fused Mlp, ops/flash_attention.py + ops/quant.py) so P001–P003/
    # M001–M002 certify the fused programs too.
    base = DiffusionViT(dtype=jnp.bfloat16, use_flash=True,
                        flash_blocks=NS_FLASH_BLOCKS, **cfg)
    H, W = base.img_size
    x2 = jax.ShapeDtypeStruct((2, H, W, base.in_chans), jnp.float32)
    t2 = jax.ShapeDtypeStruct((2,), jnp.int32)
    xr = jax.ShapeDtypeStruct((NS_ROWS, H, W, base.in_chans), jnp.float32)
    mem = dict(tokens=NS_TOKENS, rows=NS_ROWS, memory=True)
    fparams = jax.eval_shape(base.init, key, x2, t2)["params"]
    qparams = jax.eval_shape(quant.quantize_params, fparams)
    for label, model in (("f32", base.clone(dtype=jnp.float32)),
                         ("bf16", base),
                         ("w8a16", base.clone(quant="pallas")),
                         ("w8a16_fused", base.clone(quant="pallas",
                                                    fused=True)),
                         ("w8a8_fused", base.clone(quant="w8a8",
                                                   fused=True))):
        params = qparams if model.quant else fparams
        entries.append(Entry(
            f"ns200_{label}", _FLASH_PATH, sampling._ddim_scan_last,
            (params, xr, key), (model,),
            dict(k=NS_K, t_start=None, eta=0.0), donates=True,
            meta=dict(mem)))

    # few-step distilled serving at the north star (ISSUE 17): the k=4
    # student program — 3-trip schedule
    # scan + the final jump-to-clean forward — so the P-rules certify its
    # pallas calls and the M-rules its peak-HBM at the 200px geometry
    entries.append(Entry(
        "ns200_fewstep4_bf16", _FLASH_PATH, sampling._ddim_scan_fewstep,
        (fparams, xr, key), (base,),
        dict(steps=4, t_start=None, eta=0.0, sequence=False), donates=True,
        meta=dict(mem)))

    # standalone flash kernels per (dtype, blocks): forward for every
    # sweep row, grad at the streamed default and the tuned config (the
    # backward dq/dkv kernels) and with the blocks left to the kernels (the
    # resident forward, the one dqkv launch). scale matches the model's
    # head_dim=64.
    qkv = jax.ShapeDtypeStruct((2, NS_TOKENS, cfg["num_heads"],
                                cfg["embed_dim"] // cfg["num_heads"]),
                               jnp.float32)
    scale = (cfg["embed_dim"] // cfg["num_heads"]) ** -0.5
    configs = []
    for bq, bkv in ((256, 512), NS_FLASH_BLOCKS, *FLASH_BLOCK_SWEEP,
                    (None, None)):
        if (bq, bkv) not in configs:
            configs.append((bq, bkv))
    for dt_label, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        q = jax.ShapeDtypeStruct(qkv.shape, dtype)
        for bq, bkv in configs:
            def fwd(qq, kk, vv, _bq=bq, _bkv=bkv):
                return flash_attention(qq, kk, vv, scale, _bq, _bkv)

            blocks = "auto" if bq is None else f"{bq}x{bkv}"
            entries.append(Entry(
                f"flash200_{dt_label}_{blocks}", _FLASH_PATH, fwd,
                (q, q, q), meta=dict(tokens=NS_TOKENS)))
            if (bq, bkv) in ((256, 512), NS_FLASH_BLOCKS, (None, None)):
                def loss(qq, kk, vv, _f=fwd):
                    return jnp.sum(_f(qq, kk, vv).astype(jnp.float32))

                entries.append(Entry(
                    f"flash200_grad_{dt_label}_{blocks}", _FLASH_PATH,
                    jax.grad(loss, argnums=(0, 1, 2)), (q, q, q),
                    meta=dict(tokens=NS_TOKENS)))

    # the dequant-pallas kernel at the 200px trunk GEMM shapes: qkv
    # (E → 3E) and proj/mlp (E → E) over M = rows·N activation rows
    E = cfg["embed_dim"]
    M = NS_ROWS * NS_TOKENS
    for label, n_out in (("qkv", 3 * E), ("proj", E)):
        entries.append(Entry(
            f"dequant200_{label}", _QUANT_PATH, quant._dequant_matmul_pallas,
            (jax.ShapeDtypeStruct((M, E), jnp.bfloat16),
             jax.ShapeDtypeStruct((E, n_out), jnp.int8),
             jax.ShapeDtypeStruct((n_out,), jnp.float32))))

    # standalone fused trunk kernels at the 200px geometry, blocks from the
    # committed autotune table (ops/tuning.py) — every (kernel, dtype, mode)
    # variant the fused sampler can dispatch gets its own P-rule subject
    from ddim_cold_tpu.ops import tuning
    from ddim_cold_tpu.ops.flash_attention import fused_trunk_attention

    heads = cfg["num_heads"]
    wq = jax.ShapeDtypeStruct((E, 3 * E), jnp.int8)
    sq = jax.ShapeDtypeStruct((3 * E,), jnp.float32)
    bq_ = jax.ShapeDtypeStruct((3 * E,), jnp.float32)
    wp = jax.ShapeDtypeStruct((E, E), jnp.int8)
    sp_ = jax.ShapeDtypeStruct((E,), jnp.float32)
    bp_ = jax.ShapeDtypeStruct((E,), jnp.float32)
    for dt_label, dtype, mode in (("f32", jnp.float32, "pallas"),
                                  ("bf16", jnp.bfloat16, "pallas"),
                                  ("w8a8", jnp.float32, "w8a8")):
        kernel_dt = jnp.int8 if mode == "w8a8" else dtype
        fbq, fbkv = tuning.attn_blocks(NS_TOKENS, E, heads, kernel_dt,
                                       device_kind=tuning.DEVICE_KIND)

        def fattn(xx, a, b, c, d, e, f, _bq=fbq, _bkv=fbkv, _mode=mode):
            return fused_trunk_attention(
                xx, a, b, c, d, e, f, num_heads=heads, scale=scale,
                block_q=_bq, block_kv=_bkv, mode=_mode)

        entries.append(Entry(
            f"fused200_attn_{dt_label}", _FLASH_PATH, fattn,
            (jax.ShapeDtypeStruct((2, NS_TOKENS, E), dtype),
             wq, sq, bq_, wp, sp_, bp_), meta=dict(tokens=NS_TOKENS)))

    # fused Mlp at the 200px trunk shapes (mlp_ratio=1.0 → hidden = E):
    # float, w8a16 and w8a8 variants over the full M = rows·N row count
    b1 = jax.ShapeDtypeStruct((E,), jnp.float32)
    b2 = jax.ShapeDtypeStruct((E,), jnp.float32)
    for dt_label, x_dt, w_dt, mode in (
            ("float_bf16", jnp.bfloat16, jnp.bfloat16, None),
            ("w8a16_bf16", jnp.bfloat16, jnp.int8, "pallas"),
            ("w8a8", jnp.float32, jnp.int8, "w8a8")):
        kernel_dt = jnp.int8 if mode == "w8a8" else x_dt
        bm = tuning.mlp_block_m(E, E, kernel_dt, quant=mode is not None,
                                device_kind=tuning.DEVICE_KIND)
        def fmlp(xx, w1_, b1_, w2_, b2_, *scales, _bm=bm, _mode=mode):
            kw = (dict(scale1=scales[0], scale2=scales[1]) if scales
                  else {})
            return quant.mlp_pallas(xx, w1_, b1_, w2_, b2_, mode=_mode,
                                    block_m=_bm, **kw)

        entries.append(Entry(
            f"mlp200_{dt_label}", _QUANT_PATH, fmlp,
            (jax.ShapeDtypeStruct((M, E), x_dt),
             jax.ShapeDtypeStruct((E, E), w_dt), b1,
             jax.ShapeDtypeStruct((E, E), w_dt), b2,
             *( (sp_, sp_) if mode else () ))))

    # the float trunk's token-wise kernels (ops/block_kernels.py) alone, at
    # the sampler's rows and the row block the shape gives them — the scan
    # entries above trace them too, two a block; the token axis stays ragged
    from ddim_cold_tpu.ops import block_kernels

    vec = lambda n: jax.ShapeDtypeStruct((n,), jnp.float32)  # noqa: E731
    mat = lambda k, n: jax.ShapeDtypeStruct((k, n), jnp.float32)  # noqa: E731
    for dt_label, dtype in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        act = jax.ShapeDtypeStruct((NS_ROWS, NS_TOKENS, E), dtype)
        rows = block_kernels.row_block(NS_TOKENS, E, E, dtype)
        entries.append(Entry(
            f"tokenwise200_ln_qkv_{dt_label}", _BLOCK_PATH,
            block_kernels.ln_qkv,
            (act, vec(E), vec(E), mat(E, 3 * E), vec(3 * E)),
            kwargs=dict(eps=1e-5, rows=rows), meta=dict(tokens=NS_TOKENS)))
        entries.append(Entry(
            f"tokenwise200_block_tail_{dt_label}", _BLOCK_PATH,
            block_kernels.block_tail,
            (act, act, mat(E, E), vec(E), vec(E), vec(E), mat(E, E), vec(E),
             mat(E, E), vec(E)),
            kwargs=dict(eps=1e-5, rows=rows), meta=dict(tokens=NS_TOKENS)))
    return entries


def kernel_traces() -> dict:
    """``name → (entry, closed_jaxpr)`` for the 200px registry — the
    shared input of the kernels/memory layers."""
    return {e.name: (e, e.trace()) for e in kernel_entries()}


def run_serve_signature_check(traces: dict | None = None) -> list[Finding]:
    """Trace the warmed sweep twice with independently built model/param
    worlds. Hash instability across worlds = a retrace would MISS the AOT
    executable (a serve-time compile); a hash shared by two distinct
    (config, bucket) pairs = the programs are indistinguishable at the
    abstract level, so the check itself lost resolution — both are J006.

    This cross-world stability is also the fleet replacement proof
    (serve/router.py): a replacement replica warms from the same
    (config, bucket) set in a freshly built world, which is exactly the
    world-B trace here — hash-equal programs mean the replacement serves
    from its own warmup without a single in-service compile.

    The world-A traces are also run through J007 (static trip count): no
    served program — in particular no adaptive-gated cached sampler — may
    contain a ``while`` primitive, so the drift gate provably cannot vary
    the loop structure at run time."""
    PATH = "ddim_cold_tpu/serve/engine.py"
    findings: list[Finding] = []
    sigs_a = serve_signatures(Context(), findings, traces)
    sigs_b = serve_signatures(Context())
    by_hash: dict[str, str] = {}
    for subject, h in sigs_a.items():
        if sigs_b[subject] != h:
            findings.append(Finding(
                "GRAFT-J006", PATH, f"unstable:{subject}", 0,
                f"serve pair {subject} traces to a different program hash "
                "from an independently built model — warmup's AOT "
                "executable would not be reused (serve-time recompile)"))
            continue
        if h in by_hash:
            findings.append(Finding(
                "GRAFT-J006", PATH, f"collision:{subject}", 0,
                f"serve pairs {by_hash[h]} and {subject} hash to the same "
                "abstract program — distinct configs must compile distinct "
                "programs or the signature check has lost resolution"))
        else:
            by_hash[h] = subject
    return findings
