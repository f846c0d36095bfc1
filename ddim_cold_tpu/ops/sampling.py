"""Samplers — the inference "scheduler" layer, as jitted ``lax.scan`` loops.

Replaces the reference's Python-loop samplers (methods on the torch model):

* ``ddim_sample``      ← ``sampler``             (reference ViT.py:220-237)
* ``ddim_sample(..., return_sequence=True)``
                       ← ``diffusion_sequence``  (reference ViT.py:239-256)
* ``cold_sample``      ← ``cold_sampler``        (reference ViT_draft2drawing.py:259-288)
* ``cold_sample(..., return_sequence=True)``
                       ← ``cold_diffusion_sequence`` (reference ViT_draft2drawing.py:290-309)
* ``sample_from``      ← the draft2drawing inner loop (reference
                          ViT_draft2drawing.py:394-408) — DDIM from an
                          arbitrary start level, the guided-sampling primitive
                          that also expresses slerp interpolation (C25)
* ``forward_noise``    ← ``√(1−ᾱ)·ε + √ᾱ·x`` encoding (ViT_draft2drawing.py:395-396)

Design: each reverse step is affine in (x, x̂0) — the per-step coefficients are
precomputed host-side (ops/schedule.py) and fed to a single ``lax.scan`` whose
body is one model forward + clamp + two fused multiply-adds. There is no
host↔device traffic until the final gather; k, N, T are static so XLA compiles
one program per (model, stride) pair. The reference's per-step ``print`` timing
is replaced by ``jax.profiler`` tracing (utils/profiling.py).
"""

from __future__ import annotations

import math
from functools import partial, wraps
from typing import Optional

import jax
import jax.numpy as jnp

from ddim_cold_tpu.obs import scopes, spans
from ddim_cold_tpu.obs.device import StepTelemetry
from ddim_cold_tpu.ops import schedule, step_cache
from ddim_cold_tpu.utils import profiling


def _on_mesh(sampler):
    """Run a public sampler with its ``mesh=`` argument as the ambient mesh
    (parallel/mesh.ambient), so the model's Pallas kernels launch per device
    inside the program it traces."""
    @wraps(sampler)
    def run(*args, mesh=None, **kwargs):
        from ddim_cold_tpu.parallel.mesh import ambient

        with ambient(mesh):
            return sampler(*args, mesh=mesh, **kwargs)

    return run


def _host_call(sampler: str, scan_steps: int, **attrs):
    """The host side of one public sampler call, as layer spans: one
    ``sampler/call`` (``n``, set by ``_start`` once the start batch exists,
    ``k`` or ``steps``, ``scan_steps``) whose children the caller opens —
    ``sampler/init`` (draw or copy of ``x_init``, its placement, the cache
    carry) and ``sampler/dispatch`` (the jitted scan's call until it
    returns; the device runs on after it)."""
    return spans.layer("sampler/call", sampler=sampler,
                       scan_steps=scan_steps, **attrs)


def _dispatch(scan, *args, **kwargs):
    """The jitted scan's call inside ``sampler/dispatch``, with the program
    noted for ``obs.scopes.scope_map`` (shapes and statics, no buffer; a
    flatten of the arguments and a lookup once the program is known)."""
    scopes.note("sampler/" + scan.__name__, scan, args, kwargs)
    return scan(*args, **kwargs)


def _start(call, model, rng, x_init, mesh, draw, *, copy: bool,
           noise: bool = True, cache_mode: Optional[str] = None):
    """The ``sampler/init`` of one call: the start batch — ``draw()`` for a
    fresh one, else the caller's (through a private copy when ``copy``: the
    last-only scans DONATE x_init, no HBM double-buffer, and a
    caller-provided start must survive the call; the mesh path already
    copies via device_put and the sequence scans do not donate) — placed on
    the mesh, the per-step noise key and, for ``cache_mode``, the cache
    carry. Returns ``(x_init, noise_rng, cache)`` and sets the call's
    ``n``."""
    with spans.layer("sampler/init"):
        if x_init is None:
            x_init = draw()
        elif copy:
            x_init = jnp.array(x_init, copy=True)
        x_init = _shard_init(x_init, mesh)
        noise_rng = None
        if noise:
            # distinct fold: with a fresh start, rng already produced x_init
            # — the per-step noise must not be correlated with it
            noise_rng = (jax.random.fold_in(rng, 0xD1F) if rng is not None
                         else jax.random.PRNGKey(0))
        cache = (_make_cache(model, x_init, mesh, cache_mode)
                 if cache_mode is not None else None)
    call.set(n=x_init.shape[0])
    return x_init, noise_rng, cache


def forward_noise(rng: jax.Array, img: jax.Array, t_start: int, total_steps: int = 2000):
    """Encode a clean image to noise level ``t_start``.

    ᾱ here is ``1 − √(t_start/T)`` — no +1, matching the draft2drawing app
    (reference ViT_draft2drawing.py:395), not the sampler's ``(t+1)/T``.
    """
    alpha = schedule.forward_noise_alpha(t_start, total_steps)
    eps = jax.random.normal(rng, img.shape, img.dtype)
    return math.sqrt(alpha) * img + math.sqrt(1.0 - alpha) * eps


def _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta: float):
    """One reverse-step update shared by both scan variants: the affine
    (cx, cx0) move plus, for stochastic DDIM (eta>0), fresh per-step noise
    keyed by folding t — one definition so the sequence and last-only paths
    can never sample from different stochastic processes."""
    x_next = c1 * x + c2 * x0
    if eta:
        z = jax.random.normal(jax.random.fold_in(noise_rng, t),
                              x.shape, x.dtype)
        x_next = x_next + cz * z
    return x_next


def _scan_inputs(coeffs):
    return (jnp.asarray(coeffs.t_seq), jnp.asarray(coeffs.cx),
            jnp.asarray(coeffs.cx0), jnp.asarray(coeffs.cz))


@partial(jax.jit, static_argnames=("model", "k", "t_start", "eta"))
def _ddim_scan_sequence(model, params, x_init, noise_rng, *, k: int,
                        t_start: Optional[int], eta: float = 0.0):
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    n = x_init.shape[0]

    def step(x, inputs):
        t, c1, c2, cz = inputs
        with profiling.scope("sampler/model"):
            x0 = model.apply({"params": params}, x,
                             jnp.full((n,), t, jnp.int32))
        x0 = jnp.clip(x0, -1.0, 1.0)
        return _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta), x0

    _, x0_out = jax.lax.scan(step, x_init, _scan_inputs(coeffs))
    # frames: the initial noisy image, then every x̂0 prediction — matching the
    # reference's recorded trajectory (ViT.py:244,254).
    frames = jnp.concatenate([x_init[None], x0_out], axis=0)
    return (frames + 1.0) / 2.0


@partial(jax.jit, static_argnames=("model", "k", "t_start", "eta"),
         donate_argnames=("x_init",))
def _ddim_scan_last(model, params, x_init, noise_rng, *, k: int,
                    t_start: Optional[int], eta: float = 0.0):
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, _ = carry
        t, c1, c2, cz = inputs
        with profiling.scope("sampler/model"):
            x0 = model.apply({"params": params}, x,
                             jnp.full((n,), t, jnp.int32))
        x0 = jnp.clip(x0, -1.0, 1.0)
        return (_ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta),
                x0), None

    (_, x0_last), _ = jax.lax.scan(
        step, (x_init, jnp.zeros_like(x_init)), _scan_inputs(coeffs))
    # the sample is the LAST x̂0 prediction, not the final noisy state
    # (reference ViT.py:236 returns denoised_img).
    return (x0_last + 1.0) / 2.0


def _fewstep_impl(model, params, x_init, noise_rng, *, steps: int,
                  t_start: Optional[int], eta: float, sequence: bool):
    """The few-step (distilled-student) scan family: ``steps`` model
    evaluations along the proportional ``fewstep_time_sequence``, with the
    FINAL evaluation hoisted OUT of the scan. The hoist is licensed by the
    schedule algebra (schedule.fewstep_coefficients): the last jump targets
    the clean image (ᾱ = 1), where the affine update degenerates to
    x' = x̂₀ exactly — so the program is scan(steps−1 updates) + one bare
    forward, and ``steps=1`` compiles to a scan-free single forward. That
    structure is also what keeps every k∈{1,2,4} program STRUCTURALLY
    distinct from the stride family's equal-trip-count scans under
    graftcheck's constant-blind J006 signature (a k-strided scan of equal
    length would hash identically once the baked coefficients are ignored).
    """
    coeffs = schedule.fewstep_coefficients(model.total_steps, steps, t_start,
                                           eta)
    n = x_init.shape[0]

    def forward(x, t):
        with profiling.scope("sampler/model"):
            x0 = model.apply({"params": params}, x,
                             jnp.full((n,), t, jnp.int32))
        return jnp.clip(x0, -1.0, 1.0)

    x, x0_out = x_init, None
    if steps > 1:
        def step(x, inputs):
            t, c1, c2, cz = inputs
            x0 = forward(x, t)
            return (_ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta),
                    x0 if sequence else None)

        x, x0_out = jax.lax.scan(
            step, x_init, tuple(a[:-1] for a in _scan_inputs(coeffs)))
    x0_last = forward(x, int(coeffs.t_seq[-1]))
    if sequence:
        frames = [x_init[None]] + ([x0_out] if x0_out is not None else []) \
            + [x0_last[None]]
        return (jnp.concatenate(frames, axis=0) + 1.0) / 2.0
    return (x0_last + 1.0) / 2.0


_FEWSTEP_STATICS = ("model", "steps", "t_start", "eta", "sequence")
#: last-only entry donates x_init (image output aliases it), mirroring the
#: stride family; the sequence entry never donates.
_ddim_scan_fewstep = jax.jit(_fewstep_impl, static_argnames=_FEWSTEP_STATICS,
                             donate_argnames=("x_init",))
_ddim_scan_fewstep_seq = jax.jit(_fewstep_impl,
                                 static_argnames=_FEWSTEP_STATICS)


def _fewstep_cached_impl(model, params, x_init, noise_rng, cache0, *,
                         steps: int, t_start: Optional[int], eta: float,
                         cache_interval: int, cache_mode: str,
                         cache_threshold=None, cache_tokens=None,
                         sequence: bool):
    """Few-step scan composed with the step cache (ops/step_cache.py): the
    first steps−1 evaluations route through ``apply_step`` inside the scan,
    and the hoisted final evaluation takes the schedule's LAST branch id
    outside it — the same refresh/reuse pattern a ``steps``-long cached
    stride scan would run, so the composition semantics (and the τ→0 /
    k_tok→all bitwise degeneracies) carry over unchanged. Returns
    ``(images, final_cache)`` for the engine's cache recycling."""
    coeffs = schedule.fewstep_coefficients(model.total_steps, steps, t_start,
                                           eta)
    spec = _cached_spec(model, steps, cache_interval, cache_mode,
                        cache_threshold, cache_tokens)
    n = x_init.shape[0]
    branches = jnp.asarray(spec.branches, jnp.int32)

    def evaluate(x, t, br, cache):
        with profiling.scope("sampler/cached_step"):
            x0_raw, cache = step_cache.apply_step(
                model, params, x, jnp.full((n,), t, jnp.int32), br, cache,
                spec)
        return jnp.clip(x0_raw, -1.0, 1.0), cache

    x, cache, x0_out = x_init, cache0, None
    if steps > 1:
        def step(carry, inputs):
            x, cache = carry
            (t, c1, c2, cz), br = inputs
            x0, cache = evaluate(x, t, br, cache)
            x_next = _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta)
            return (x_next, cache), (x0 if sequence else None)

        (x, cache), x0_out = jax.lax.scan(
            step, (x_init, cache0),
            (tuple(a[:-1] for a in _scan_inputs(coeffs)), branches[:-1]))
    x0_last, cache_out = evaluate(x, int(coeffs.t_seq[-1]), branches[-1],
                                  cache)
    if sequence:
        frames = [x_init[None]] + ([x0_out] if x0_out is not None else []) \
            + [x0_last[None]]
        return (jnp.concatenate(frames, axis=0) + 1.0) / 2.0, cache_out
    return (x0_last + 1.0) / 2.0, cache_out


_FEWSTEP_CACHED_STATICS = ("model", "steps", "t_start", "eta",
                           "cache_interval", "cache_mode", "cache_threshold",
                           "cache_tokens", "sequence")
#: donation mirrors the cached stride scan: x_init and the cache carry alias
#: outputs on the last-only entry; the sequence entry never donates.
_ddim_scan_fewstep_cached = jax.jit(
    _fewstep_cached_impl, static_argnames=_FEWSTEP_CACHED_STATICS,
    donate_argnames=("x_init", "cache0"))
_ddim_scan_fewstep_cached_seq = jax.jit(
    _fewstep_cached_impl, static_argnames=_FEWSTEP_CACHED_STATICS)


@_on_mesh
def ddim_sample_fewstep(
    model,
    params,
    rng: Optional[jax.Array] = None,
    *,
    steps: int,
    n: int = 128,
    x_init: Optional[jax.Array] = None,
    t_start: Optional[int] = None,
    return_sequence: bool = False,
    mesh=None,
    eta: float = 0.0,
    cache_interval: int = 1,
    cache_mode: str = "delta",
    cache_threshold: Optional[float] = None,
    cache_tokens: Optional[int] = None,
) -> jax.Array:
    """Few-step DDIM sampling: exactly ``steps`` model evaluations (the
    distilled-student serving path, k∈{1,2,4}); returns images in [0, 1].

    Where :func:`ddim_sample` fixes a STRIDE k (the step count falls out of
    T), this fixes the step COUNT along the proportional
    ``schedule.fewstep_time_sequence`` — one compiled program per ``steps``
    regardless of T, which is what ``SamplerConfig(steps=...)`` serves.
    Running a k=20-trained teacher through ``steps`` ≤ 4 is a (poor-quality)
    valid program — the intended params are a ``train/distill.py`` student,
    but nothing here checks provenance; ``eval/fid.py
    distilled_sampler_guard`` is the quality gate.

    ``rng``/``x_init``/``t_start``/``return_sequence``/``mesh``/``eta`` and
    the ``cache_*`` statics behave exactly as in :func:`ddim_sample`
    (guided private copy, data-axis SPMD, stochastic eta, step-cache
    composition).
    """
    if eta and rng is None:
        raise ValueError("eta > 0 draws per-step noise — pass rng")
    if x_init is None and rng is None:
        raise ValueError("ddim_sample_fewstep needs either rng or x_init")
    cached = step_cache.enabled(cache_interval)
    with _host_call("ddim_fewstep", steps, steps=steps) as call:
        H, W = model.img_size
        x_init, noise_rng, cache = _start(
            call, model, rng, x_init, mesh,
            lambda: jax.random.normal(rng, (n, H, W, model.in_chans),
                                      jnp.float32),
            copy=mesh is None and not return_sequence,
            cache_mode=cache_mode if cached else None)
        with spans.layer("sampler/dispatch"):
            if cached:
                fn = (_ddim_scan_fewstep_cached_seq if return_sequence
                      else _ddim_scan_fewstep_cached)
                out, _ = _dispatch(
                    fn, model, params, x_init, noise_rng, cache,
                    steps=steps, t_start=t_start, eta=eta,
                    cache_interval=cache_interval, cache_mode=cache_mode,
                    cache_threshold=cache_threshold,
                    cache_tokens=cache_tokens, sequence=return_sequence)
                return out
            fn = (_ddim_scan_fewstep_seq if return_sequence
                  else _ddim_scan_fewstep)
            return _dispatch(fn, model, params, x_init, noise_rng,
                             steps=steps, t_start=t_start, eta=eta,
                             sequence=return_sequence)


def _cached_spec(model, n_steps: int, cache_interval: int, cache_mode: str,
                 cache_threshold, cache_tokens) -> step_cache.CacheSpec:
    """One spec-construction site for every cached scan: supplies the
    model-derived token count for "token" mode and forwards the adaptive
    threshold / top-k statics so ops/step_cache.py's per-mode validation
    fires identically from samplers, engine, and graftcheck mirrors."""
    return step_cache.cache_spec(
        model.depth, n_steps, cache_interval, cache_mode,
        threshold=cache_threshold, token_k=cache_tokens,
        n_tokens=(model.num_patches + 1) if cache_mode == "token" else None)


def _ddim_cached_impl(model, params, x_init, noise_rng, cache0, *, k: int,
                      t_start: Optional[int], eta: float,
                      cache_interval: int, cache_mode: str,
                      cache_threshold=None, cache_tokens=None,
                      sequence: bool):
    """The feature-cached DDIM scan (ops/step_cache.py): same affine update
    as the plain scans, but the model evaluation routes through a
    ``lax.switch`` over the static refresh/reuse schedule and the block-delta
    cache rides the carry. One impl serves both the last-only and
    sequence-returning paths (``sequence`` is static) so the cached and exact
    samplers can never drift onto different update algebra.

    Returns ``(images, final_cache)``: the cache comes back out so the
    donated ``cache0`` buffers alias it (free at the XLA level — the carry is
    already materialized) and so a serving loop can recycle one cache
    allocation across dispatches (the schedule's step 0 always refreshes, so
    stale contents are never read; serve/engine.py does exactly this)."""
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    spec = _cached_spec(model, len(coeffs.t_seq), cache_interval, cache_mode,
                        cache_threshold, cache_tokens)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, x0_prev, cache = carry
        (t, c1, c2, cz), br = inputs
        with profiling.scope("sampler/cached_step"):
            x0_raw, cache = step_cache.apply_step(
                model, params, x, jnp.full((n,), t, jnp.int32), br, cache,
                spec)
        x0 = jnp.clip(x0_raw, -1.0, 1.0)
        x_next = _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta)
        return (x_next, x0, cache), (x0 if sequence else None)

    carry0 = (x_init, jnp.zeros_like(x_init), cache0)
    branches = jnp.asarray(spec.branches, jnp.int32)
    (_, x0_last, cache_out), x0_out = jax.lax.scan(
        step, carry0, (_scan_inputs(coeffs), branches))
    if sequence:
        frames = jnp.concatenate([x_init[None], x0_out], axis=0)
        return (frames + 1.0) / 2.0, cache_out
    return (x0_last + 1.0) / 2.0, cache_out


_CACHED_STATICS = ("model", "k", "t_start", "eta", "cache_interval",
                   "cache_mode", "cache_threshold", "cache_tokens",
                   "sequence")
#: last-only entry point — donates x_init and the cache carry (both alias
#: outputs: the image is x_init-shaped f32, the returned cache matches
#: cache0), so the sampler never double-buffers x or the deltas in HBM.
_ddim_scan_cached = jax.jit(_ddim_cached_impl, static_argnames=_CACHED_STATICS,
                            donate_argnames=("x_init", "cache0"))
#: sequence entry point — NO donation: the (steps+1, N, H, W, C) frames
#: output matches neither donated shape, so donation here would only raise
#: jax's unused-donation warning (the figure path keeps the plain behavior).
_ddim_scan_cached_seq = jax.jit(_ddim_cached_impl,
                                static_argnames=_CACHED_STATICS)


def _ddim_cached_tel_impl(model, params, x_init, noise_rng, cache0, *, k: int,
                          t_start: Optional[int], eta: float,
                          cache_interval: int, cache_mode: str,
                          cache_threshold=None, cache_tokens=None):
    """``_ddim_cached_impl`` with on-device step telemetry: the same cached
    scan, but each step also stacks the cache branch ACTUALLY taken (the
    adaptive gate's post-promotion index — ``ops/step_cache.apply_step_tel``)
    and the gate's drift value into a static-shaped ``(n_steps,)`` aux.
    Last-only (no ``sequence`` static — previews and telemetry are separate
    products; serve/batching.py rejects the combination), so the telemetry
    program keys on one fewer static than the plain cached scan. Returns
    ``(images, final_cache, (branch, drift))``; the host side decodes the
    aux via ``obs.device.summarize``."""
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    spec = _cached_spec(model, len(coeffs.t_seq), cache_interval, cache_mode,
                        cache_threshold, cache_tokens)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, x0_prev, cache = carry
        (t, c1, c2, cz), br = inputs
        with profiling.scope("sampler/cached_step"):
            x0_raw, cache, idx, drift = step_cache.apply_step_tel(
                model, params, x, jnp.full((n,), t, jnp.int32), br, cache,
                spec)
        x0 = jnp.clip(x0_raw, -1.0, 1.0)
        x_next = _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta)
        return (x_next, x0, cache), (idx, drift)

    carry0 = (x_init, jnp.zeros_like(x_init), cache0)
    branches = jnp.asarray(spec.branches, jnp.int32)
    (_, x0_last, cache_out), (br_seq, drift_seq) = jax.lax.scan(
        step, carry0, (_scan_inputs(coeffs), branches))
    return (x0_last + 1.0) / 2.0, cache_out, (br_seq, drift_seq)


_CACHED_TEL_STATICS = ("model", "k", "t_start", "eta", "cache_interval",
                       "cache_mode", "cache_threshold", "cache_tokens")
#: donation mirrors the last-only cached scan (x_init/cache alias outputs;
#: the tiny (n_steps,) aux allocates fresh — negligible).
_ddim_scan_cached_tel = jax.jit(_ddim_cached_tel_impl,
                                static_argnames=_CACHED_TEL_STATICS,
                                donate_argnames=("x_init", "cache0"))


def _ddim_inpaint_impl(model, params, x_init, known, mask, noise_rng, *,
                       k: int, t_start: Optional[int], eta: float,
                       sequence: bool):
    """The inpainting scan (ddim_cold_tpu/workloads): plain DDIM with a
    per-step constraint — after each x̂0 prediction, the KNOWN pixels are
    re-projected from the reference image (``x̂0 ← m·known + (1−m)·x̂0``)
    before the affine update, so the reverse process is pulled toward a
    sample whose masked region agrees with ``known`` exactly. ``mask`` is a
    static-shaped (N, H, W, 1) float batch input of {0, 1} (1 = known); the
    projection is per-row, so the engine's coalescing keeps the bitwise
    contract, and padding rows (mask 0) pass through untouched. The final
    output is the LAST projected x̂0, hence known pixels are preserved
    bit-exactly (mask idempotence — tests/test_workloads.py pins it).
    ``sequence=True`` returns the (steps+1, N, H, W, C) trajectory of
    projected x̂0 predictions (the preview path)."""
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, _ = carry
        t, c1, c2, cz = inputs
        with profiling.scope("sampler/model"):
            x0 = model.apply({"params": params}, x,
                             jnp.full((n,), t, jnp.int32))
        x0 = jnp.clip(x0, -1.0, 1.0)
        x0 = mask * known + (1.0 - mask) * x0
        return (_ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta),
                x0), (x0 if sequence else None)

    (_, x0_last), x0_out = jax.lax.scan(
        step, (x_init, jnp.zeros_like(x_init)), _scan_inputs(coeffs))
    if sequence:
        frames = jnp.concatenate([x_init[None], x0_out], axis=0)
        return (frames + 1.0) / 2.0
    return (x0_last + 1.0) / 2.0


_INPAINT_STATICS = ("model", "k", "t_start", "eta", "sequence")
#: last-only entry donates x_init (fresh noise, image output aliases it);
#: ``known``/``mask`` are caller-owned conditioning inputs and never donate.
_ddim_scan_inpaint = jax.jit(_ddim_inpaint_impl,
                             static_argnames=_INPAINT_STATICS,
                             donate_argnames=("x_init",))
#: sequence entry — no donation (frames alias nothing), mirroring the other
#: sequence scans.
_ddim_scan_inpaint_seq = jax.jit(_ddim_inpaint_impl,
                                 static_argnames=_INPAINT_STATICS)


def _ddim_inpaint_cached_impl(model, params, x_init, known, mask, noise_rng,
                              cache0, *, k: int, t_start: Optional[int],
                              eta: float, cache_interval: int,
                              cache_mode: str, cache_threshold=None,
                              cache_tokens=None, sequence: bool):
    """Feature-cached inpainting scan: ``_ddim_inpaint_impl``'s per-step
    known-pixel projection composed with ``_ddim_cached_impl``'s step-cache
    routing. The projection runs on the CLIPPED x̂0 — after the cache branch,
    before the affine update — exactly where the plain inpaint scan applies
    it, so ``cache_interval=1``-adjacent degenerate settings (adaptive
    threshold 0, token k = n_tokens) stay bitwise against the plain scan.
    Returns ``(images, final_cache)`` for the engine's per-bucket cache
    recycling, like the other cached scans."""
    coeffs = schedule.ddim_coefficients(model.total_steps, k, t_start, eta)
    spec = _cached_spec(model, len(coeffs.t_seq), cache_interval, cache_mode,
                        cache_threshold, cache_tokens)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, _, cache = carry
        (t, c1, c2, cz), br = inputs
        with profiling.scope("sampler/cached_step"):
            x0_raw, cache = step_cache.apply_step(
                model, params, x, jnp.full((n,), t, jnp.int32), br, cache,
                spec)
        x0 = jnp.clip(x0_raw, -1.0, 1.0)
        x0 = mask * known + (1.0 - mask) * x0
        x_next = _ddim_step_update(x, x0, t, c1, c2, cz, noise_rng, eta)
        return (x_next, x0, cache), (x0 if sequence else None)

    carry0 = (x_init, jnp.zeros_like(x_init), cache0)
    branches = jnp.asarray(spec.branches, jnp.int32)
    (_, x0_last, cache_out), x0_out = jax.lax.scan(
        step, carry0, (_scan_inputs(coeffs), branches))
    if sequence:
        frames = jnp.concatenate([x_init[None], x0_out], axis=0)
        return (frames + 1.0) / 2.0, cache_out
    return (x0_last + 1.0) / 2.0, cache_out


_INPAINT_CACHED_STATICS = ("model", "k", "t_start", "eta", "cache_interval",
                           "cache_mode", "cache_threshold", "cache_tokens",
                           "sequence")
#: donation mirrors the cached sampler: x_init (fresh noise) and the cache
#: carry alias outputs; known/mask are caller-owned conditioning, never
#: donated.
_ddim_scan_inpaint_cached = jax.jit(
    _ddim_inpaint_cached_impl, static_argnames=_INPAINT_CACHED_STATICS,
    donate_argnames=("x_init", "cache0"))
_ddim_scan_inpaint_cached_seq = jax.jit(
    _ddim_inpaint_cached_impl, static_argnames=_INPAINT_CACHED_STATICS)


def _make_cache(model, x_init: jax.Array, mesh,
                mode: str = "delta") -> step_cache.Cache:
    """Build the zero cache carry host-side and, under SPMD sampling, place
    it batch-sharded over the mesh's 'data' axis alongside the sample batch
    — explicit placement, so the scan's cache shards never gather.
    ``mode="adaptive"`` adds the drift-reference image leaf (x_init-shaped,
    f32); the other modes share the two-leaf (B, N+1, E) pair."""
    cache = step_cache.init_cache(x_init.shape[0], model.num_patches + 1,
                                  model.embed_dim, model.dtype, mode=mode,
                                  img_shape=x_init.shape[1:])
    return step_cache.shard_cache(cache, mesh)


def _shard_init(x_init: jax.Array, mesh) -> jax.Array:
    """Place the sample batch sharded over the mesh's 'data' axis: the whole
    scan then runs SPMD (params replicated, one psum-free forward per shard)
    — multi-chip sampling the reference's single-GPU sampler has no analogue
    for. The batch must divide over the data axis."""
    if mesh is None:
        return x_init
    from ddim_cold_tpu.parallel.mesh import batch_sharding

    return jax.device_put(x_init, batch_sharding(mesh))


@_on_mesh
def ddim_sample(
    model,
    params,
    rng: Optional[jax.Array] = None,
    *,
    k: int = 10,
    n: int = 128,
    x_init: Optional[jax.Array] = None,
    t_start: Optional[int] = None,
    return_sequence: bool = False,
    mesh=None,
    eta: float = 0.0,
    cache_interval: int = 1,
    cache_mode: str = "delta",
    cache_threshold: Optional[float] = None,
    cache_tokens: Optional[int] = None,
    telemetry: bool = False,
) -> jax.Array:
    """k-strided DDIM sampling; returns images in [0, 1], NHWC.

    Either pass ``rng`` (fresh N(0,1) start, reference ViT.py:224) or
    ``x_init`` (an already-encoded image — the guided path). Defaults mirror
    the reference API (k=10, N=128, ViT.py:221).

    ``return_sequence=True`` returns the (n_steps+1, N, H, W, C) trajectory of
    the initial noise plus every x̂0 prediction (the denoise-sequence figure).
    With a ``mesh``, the batch is sharded over its 'data' axis and the scan
    runs SPMD across the chips. A ``(data, seq)`` mesh additionally runs
    sequence-parallel attention when ``model`` was cloned onto it
    (``models.sp_clone`` — the serve engine's ``sp_mode``/``sp_degree``
    configs are exactly this pairing): the batch stays 'data'-sharded here
    while the patch tokens shard over 'seq' inside the attention shard_map,
    so ONE large request can use every chip instead of only scaling with
    batch. Put ``params`` on the same mesh (``parallel.shard_params``).

    ``eta`` interpolates toward stochastic (DDPM-like) sampling per the DDIM
    paper (schedule.ddim_coefficients; beyond-parity, default 0 = the
    reference's deterministic path, bit-exact). ``eta`` > 0 draws per-step
    noise from ``rng``, which is then required even with ``x_init``.

    ``cache_interval`` > 1 turns on training-free feature caching
    (ops/step_cache.py): every ``cache_interval``-th step runs the full model
    and refreshes a block-delta cache; the steps between skip the
    ``cache_mode``-selected trunk blocks ("delta" = the Δ-DiT front/rear
    phase split, "full" = the whole trunk) and apply the cached deltas
    instead. The schedule is static, so the scan stays one compiled program
    per (k, interval, mode). ``cache_interval=1`` (default) takes the plain
    scan — bit-for-bit the exact sampler. Requires ``scan_blocks=False``.

    Two further modes (ops/step_cache.py, this is the adaptive-caching
    surface):

    * ``cache_mode="adaptive"`` + ``cache_threshold=τ`` — error-gated delta
      reuse: the static schedule above becomes the worst-case bound, and a
      cheap on-device drift estimate (normalized ‖x − x_ref‖², max over the
      batch) overrides any reuse step back to a refresh whenever drift ≥ τ.
      Still one compiled program (data-dependent ``lax.switch`` index over
      the same static branch set), no host sync. τ=0.0 refreshes every step
      — bitwise the exact sampler. τ→∞ is bitwise the static "delta" mode.
    * ``cache_mode="token"`` + ``cache_tokens=k_tok`` — JiT spatial caching:
      non-refresh steps recompute only the ``k_tok`` most-changed tokens
      (CLS always live) through the trunk, scattering into the cached token
      stream. ``k_tok = num_patches + 1`` is bitwise the exact sampler.

    Both statics are part of the compiled-program key; they are rejected
    (by ops/step_cache.cache_spec) under any other ``cache_mode``.

    ``telemetry=True`` (requires the cached sampler, i.e.
    ``cache_interval`` > 1, and is last-only) additionally returns an
    ``obs.device.StepTelemetry`` aux — per scan step, the cache branch
    actually taken (post adaptive-gate promotion) and the gate's drift —
    as ``(images, telemetry)``. The aux is static-shaped and rides the
    same scan, so it costs no extra dispatches or compiles; images are
    bitwise identical with telemetry on or off.
    """
    if eta and rng is None:
        raise ValueError("eta > 0 draws per-step noise — pass rng")
    if x_init is None and rng is None:
        raise ValueError("ddim_sample needs either rng or x_init")
    cached = step_cache.enabled(cache_interval)
    if telemetry:
        if return_sequence:
            raise ValueError("telemetry=True is last-only — previews and "
                             "telemetry are separate products")
        if not cached:
            raise ValueError("telemetry=True needs the cached sampler "
                             "(cache_interval > 1)")
    scan_steps = len(schedule.ddim_time_sequence(model.total_steps, k,
                                                 t_start))
    with _host_call("ddim", scan_steps, k=k) as call:
        H, W = model.img_size
        x_init, noise_rng, cache = _start(
            call, model, rng, x_init, mesh,
            lambda: jax.random.normal(rng, (n, H, W, model.in_chans),
                                      jnp.float32),
            copy=mesh is None and not return_sequence,
            cache_mode=cache_mode if cached else None)
        with spans.layer("sampler/dispatch"):
            if telemetry:
                out, _, (br, drift) = _dispatch(
                    _ddim_scan_cached_tel,
                    model, params, x_init, noise_rng, cache,
                    k=k, t_start=t_start, eta=eta,
                    cache_interval=cache_interval, cache_mode=cache_mode,
                    cache_threshold=cache_threshold,
                    cache_tokens=cache_tokens)
                return out, StepTelemetry(branch=br, drift=drift)
            if cached:
                fn = (_ddim_scan_cached_seq if return_sequence
                      else _ddim_scan_cached)
                out, _ = _dispatch(
                    fn, model, params, x_init, noise_rng, cache,
                    k=k, t_start=t_start, eta=eta,
                    cache_interval=cache_interval, cache_mode=cache_mode,
                    cache_threshold=cache_threshold,
                    cache_tokens=cache_tokens, sequence=return_sequence)
                return out
            fn = _ddim_scan_sequence if return_sequence else _ddim_scan_last
            return _dispatch(fn, model, params, x_init, noise_rng,
                             k=k, t_start=t_start, eta=eta)


def sample_from(model, params, x_init: jax.Array, t_start: int, k: int = 10,
                eta: float = 0.0,
                rng: Optional[jax.Array] = None,
                return_sequence: bool = False,
                mesh=None,
                cache_interval: int = 1,
                cache_mode: str = "delta",
                cache_threshold: Optional[float] = None,
                cache_tokens: Optional[int] = None) -> jax.Array:
    """Guided sampling: DDIM-denoise an encoded image from level ``t_start``.

    Strictly a prefix-truncated ``ddim_sample`` (SURVEY.md C24). The
    draft2drawing app composes this with ``forward_noise``; slerp interpolation
    (C25) composes it with a spherical mix of two encodings. ``eta`` > 0
    switches to stochastic DDIM (see ``ddim_sample``) and requires ``rng``.
    ``return_sequence``/``mesh``/``cache_interval``/``cache_mode`` thread
    through to ``ddim_sample`` (trajectory output, data-axis SPMD, and the
    feature-cached sampler), so every guided composition — the editing
    workloads in particular — reaches the same variants the plain sampler has.
    """
    return ddim_sample(model, params, rng, x_init=x_init, t_start=t_start,
                       k=k, eta=eta, return_sequence=return_sequence,
                       mesh=mesh, cache_interval=cache_interval,
                       cache_mode=cache_mode, cache_threshold=cache_threshold,
                       cache_tokens=cache_tokens)


def slerp(a: jax.Array, b: jax.Array, frac: jax.Array) -> jax.Array:
    """Spherical interpolation between two (batches of) latents.

    The primitive of the reference's dormant interpolation app
    (ViT_draft2drawing.py:422-476): mix two forward-noised encodings on the
    great circle, then DDIM-decode with ``sample_from``. ``frac`` broadcasts
    against the leading axes, so a (F, 1, 1, 1, 1) fraction vector against
    (N, H, W, C) endpoints yields all F interpolants in one shot.
    """
    flat_a = a.reshape(a.shape[0], -1) if a.ndim > 1 else a[None]
    flat_b = b.reshape(b.shape[0], -1) if b.ndim > 1 else b[None]
    cos = jnp.sum(flat_a * flat_b, -1) / (
        jnp.linalg.norm(flat_a, axis=-1) * jnp.linalg.norm(flat_b, axis=-1)
    )
    theta_shape = (a.shape[:1] + (1,) * (a.ndim - 1)) if a.ndim > 1 else ()
    theta = jnp.arccos(jnp.clip(cos, -1.0, 1.0)).reshape(theta_shape)
    sin = jnp.sin(theta)
    # guard the denominator so the untaken branch carries no NaN (0/0) —
    # keeps jax_debug_nans and grads clean near parallel endpoints.
    safe_sin = jnp.where(sin < 1e-6, 1.0, sin)
    wa = jnp.sin((1.0 - frac) * theta) / safe_sin
    wb = jnp.sin(frac * theta) / safe_sin
    # degenerate (parallel) endpoints: fall back to lerp
    lin = (1.0 - frac) * a + frac * b
    return jnp.where(sin < 1e-6, lin, wa * a + wb * b)


def interp_states(rng: jax.Array, img_a: jax.Array, img_b: jax.Array,
                  n_interp: int, t_start: int,
                  total_steps: int = 2000) -> jax.Array:
    """The slerp-mixed encodings :func:`slerp_interpolate` decodes: both
    endpoints forward-noised to ``t_start`` with ONE key (independent noise
    per endpoint — the batch draw covers both, matching the reference's two
    separate draws ViT_draft2drawing.py:442-443), then ``n_interp``
    great-circle fractions between the two encodings. Factored out so the
    serving engine's interp workload (ddim_cold_tpu/workloads) builds
    bit-identical init states to the direct call — row i depends only on
    (key, endpoints, n_interp), never on its batchmates."""
    batch = jnp.stack([img_a, img_b])
    noisy = forward_noise(rng, batch, t_start, total_steps)
    frac = jnp.linspace(0.0, 1.0, n_interp).reshape(-1, 1, 1, 1, 1)
    return slerp(noisy[0][None], noisy[1][None], frac)[:, 0]


def slerp_interpolate(
    model,
    params,
    rng: jax.Array,
    img_a: jax.Array,
    img_b: jax.Array,
    *,
    n_interp: int = 8,
    t_start: int = 1800,
    k: int = 10,
    eta: float = 0.0,
    return_sequence: bool = False,
) -> jax.Array:
    """End-to-end latent interpolation (C25): encode both images to ``t_start``
    (one rng key, independent noise per endpoint — matching the reference's two
    separate draws, ViT_draft2drawing.py:442-443), slerp ``n_interp`` fractions
    between the encodings, and DDIM-decode each — returns (n_interp, H, W, C)
    in [0, 1]. ``eta`` > 0 decodes stochastically (same semantics as
    :func:`sample_from`; the decode key is folded from ``rng`` so the
    encoding noise and the decode noise stay independent)."""
    mixed = interp_states(rng, img_a, img_b, n_interp, t_start,
                          model.total_steps)
    return sample_from(model, params, mixed, t_start=t_start, k=k, eta=eta,
                       return_sequence=return_sequence,
                       rng=jax.random.fold_in(rng, 1))


def _cold_impl(model, params, x_init, *, levels: int, return_sequence: bool):
    t_seq = jnp.asarray(schedule.cold_time_sequence(levels))
    n = x_init.shape[0]

    def step(x, t):
        with profiling.scope("sampler/model"):
            x0 = model.apply({"params": params}, x,
                             jnp.full((n,), t, jnp.int32))
        x0 = jnp.clip(x0, -1.0, 1.0)
        # naive Cold-Diffusion Algorithm 1: x ← clamp(f(x, t)); the reference's
        # DDIM-style correction is present upstream only as commented-out code
        # (ViT_draft2drawing.py:275-285).
        return x0, x0 if return_sequence else None

    x_last, frames = jax.lax.scan(step, x_init, t_seq)
    if return_sequence:
        return (jnp.concatenate([x_init[None], frames], axis=0) + 1.0) / 2.0
    return (x_last + 1.0) / 2.0


_COLD_STATICS = ("model", "levels", "return_sequence")
#: last-only / sequence split mirrors the DDIM scans: only the last-only
#: entry donates x_init (its image output aliases the buffer; the sequence
#: frames cannot).
_cold_scan = jax.jit(_cold_impl, static_argnames=_COLD_STATICS,
                     donate_argnames=("x_init",))
_cold_scan_seq = jax.jit(_cold_impl, static_argnames=_COLD_STATICS)


def _cold_cached_impl(model, params, x_init, cache0, *, levels: int,
                      return_sequence: bool, cache_interval: int,
                      cache_mode: str, cache_threshold=None,
                      cache_tokens=None):
    """Feature-cached cold-diffusion scan — same naive Algorithm-1 update as
    ``_cold_scan``, model evaluation routed through the step cache. Returns
    ``(images, final_cache)`` like ``_ddim_cached_impl`` (donation aliasing +
    serve-loop cache recycling)."""
    t_seq = jnp.asarray(schedule.cold_time_sequence(levels))
    spec = _cached_spec(model, levels, cache_interval, cache_mode,
                        cache_threshold, cache_tokens)
    n = x_init.shape[0]

    def step(carry, inputs):
        x, cache = carry
        t, br = inputs
        with profiling.scope("sampler/cached_step"):
            x0_raw, cache = step_cache.apply_step(
                model, params, x, jnp.full((n,), t, jnp.int32), br, cache,
                spec)
        x0 = jnp.clip(x0_raw, -1.0, 1.0)
        return (x0, cache), (x0 if return_sequence else None)

    branches = jnp.asarray(spec.branches, jnp.int32)
    (x_last, cache_out), frames = jax.lax.scan(step, (x_init, cache0),
                                               (t_seq, branches))
    if return_sequence:
        return ((jnp.concatenate([x_init[None], frames], axis=0) + 1.0) / 2.0,
                cache_out)
    return (x_last + 1.0) / 2.0, cache_out


_COLD_CACHED_STATICS = ("model", "levels", "return_sequence",
                        "cache_interval", "cache_mode", "cache_threshold",
                        "cache_tokens")
_cold_scan_cached = jax.jit(_cold_cached_impl,
                            static_argnames=_COLD_CACHED_STATICS,
                            donate_argnames=("x_init", "cache0"))
_cold_scan_cached_seq = jax.jit(_cold_cached_impl,
                                static_argnames=_COLD_CACHED_STATICS)


@_on_mesh
def cold_sample(
    model,
    params,
    rng: Optional[jax.Array] = None,
    *,
    n: int = 49,
    levels: int = 6,
    x_init: Optional[jax.Array] = None,
    return_sequence: bool = False,
    mesh=None,
    cache_interval: int = 1,
    cache_mode: str = "delta",
    cache_threshold: Optional[float] = None,
    cache_tokens: Optional[int] = None,
) -> jax.Array:
    """Cold-diffusion sampling from per-sample constant-color "noise".

    The default init is a single N(0,1) RGB color per sample broadcast over
    the image (reference ViT_draft2drawing.py:264 — the fully-downsampled
    degenerate state); ``levels`` defaults to 6 = log2(64). Passing
    ``x_init`` instead starts the cold scan from a caller-provided degraded
    state at degradation level ``levels`` — the guided cold path (the
    super-resolution workload feeds an upsampled low-res image here, with
    ``levels`` = its downsampling level). With a ``mesh``, the batch runs
    SPMD sharded over its 'data' axis (see ``ddim_sample``).
    ``cache_interval`` > 1 enables the feature-cached scan (see
    ``ddim_sample``); 1 is bit-for-bit the plain sampler.
    """
    if x_init is None and rng is None:
        raise ValueError("cold_sample needs either rng or x_init")
    cached = step_cache.enabled(cache_interval)
    with _host_call("cold", levels, steps=levels) as call:
        H, W = model.img_size

        def color():  # one N(0,1) RGB colour a sample, over the image
            c = jax.random.normal(rng, (n, 1, 1, model.in_chans), jnp.float32)
            return jnp.broadcast_to(c, (n, H, W, model.in_chans))

        x_init, _, cache = _start(
            call, model, rng, x_init, mesh, color,
            copy=mesh is None and not return_sequence, noise=False,
            cache_mode=cache_mode if cached else None)
        with spans.layer("sampler/dispatch"):
            if cached:
                fn = (_cold_scan_cached_seq if return_sequence
                      else _cold_scan_cached)
                out, _ = _dispatch(
                    fn, model, params, x_init, cache,
                    levels=levels, return_sequence=return_sequence,
                    cache_interval=cache_interval, cache_mode=cache_mode,
                    cache_threshold=cache_threshold,
                    cache_tokens=cache_tokens)
                return out
            fn = _cold_scan_seq if return_sequence else _cold_scan
            return _dispatch(fn, model, params, x_init, levels=levels,
                             return_sequence=return_sequence)
