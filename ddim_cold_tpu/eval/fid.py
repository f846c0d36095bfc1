"""FID — Fréchet Inception Distance (streaming statistics + distance).

The north-star acceptance metric (BASELINE.json: "FID within 0.5 of the CUDA
reference"); the reference codebase itself has NO quantitative image metric
(samples are compared by eye, reference README.md:24), so this subsystem is a
required new build per SURVEY.md §7.

Pieces:
* ``ActivationStats`` — streaming (count, Σx, Σxxᵀ) accumulator; batches can
  arrive from any loader/sampler, memory is O(d²) regardless of sample count.
* ``frechet_distance`` — ‖μ₁−μ₂‖² + tr(Σ₁+Σ₂−2(Σ₁Σ₂)^½), with the matrix
  square root via symmetric eigendecomposition (no scipy dependency in the
  hot path; scipy.linalg.sqrtm is cross-checked in tests).
* ``compute_fid`` / ``fid_between`` — end-to-end: images in [0,1] → 299×299
  bilinear resize → [−1,1] → InceptionV3 pool3 features (jitted, batched) →
  statistics → distance.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.eval import inception


@dataclass
class ActivationStats:
    """Streaming mean/covariance of feature activations."""

    dim: int
    count: int = 0
    _sum: np.ndarray = field(default=None, repr=False)  # type: ignore[assignment]
    _outer: np.ndarray = field(default=None, repr=False)  # type: ignore[assignment]

    def __post_init__(self):
        if self._sum is None:
            self._sum = np.zeros(self.dim, np.float64)
        if self._outer is None:
            self._outer = np.zeros((self.dim, self.dim), np.float64)

    def update(self, feats: np.ndarray) -> None:
        feats = np.asarray(feats, np.float64)
        if feats.ndim != 2 or feats.shape[1] != self.dim:
            raise ValueError(f"expected (N, {self.dim}) features, got {feats.shape}")
        self.count += feats.shape[0]
        self._sum += feats.sum(axis=0)
        self._outer += feats.T @ feats

    def merge(self, other: "ActivationStats") -> "ActivationStats":
        """Combine two accumulators (e.g. per-host shards)."""
        if other.dim != self.dim:
            raise ValueError("dimension mismatch")
        out = ActivationStats(self.dim)
        out.count = self.count + other.count
        out._sum = self._sum + other._sum
        out._outer = self._outer + other._outer
        return out

    @property
    def mean(self) -> np.ndarray:
        if self.count < 1:
            raise ValueError("no samples accumulated")
        return self._sum / self.count

    @property
    def cov(self) -> np.ndarray:
        """Unbiased (N−1) covariance — matches np.cov / pytorch-fid."""
        if self.count < 2:
            raise ValueError("need ≥2 samples for covariance")
        mu = self.mean
        return (self._outer - self.count * np.outer(mu, mu)) / (self.count - 1)


def _sqrtm_psd(mat: np.ndarray, eps: float = 0.0) -> np.ndarray:
    """Symmetric-PSD matrix square root via eigh (negative eigenvalues from
    round-off are clamped to 0)."""
    w, v = np.linalg.eigh(mat)
    return (v * np.sqrt(np.clip(w, eps, None))) @ v.T


def trace_sqrt_product(sigma1: np.ndarray, sigma2: np.ndarray) -> float:
    """tr((Σ₁Σ₂)^½) computed stably: tr((Σ₁^½ Σ₂ Σ₁^½)^½) — the inner matrix
    is symmetric PSD, so everything stays in real symmetric eigensolves
    (scipy.sqrtm on the non-symmetric product can go complex)."""
    s1h = _sqrtm_psd(np.asarray(sigma1, np.float64))
    inner = s1h @ np.asarray(sigma2, np.float64) @ s1h
    inner = (inner + inner.T) / 2.0
    w = np.linalg.eigvalsh(inner)
    return float(np.sqrt(np.clip(w, 0.0, None)).sum())


def frechet_distance(mu1, sigma1, mu2, sigma2) -> float:
    """d²((μ₁,Σ₁), (μ₂,Σ₂)) = ‖μ₁−μ₂‖² + tr(Σ₁) + tr(Σ₂) − 2·tr((Σ₁Σ₂)^½)."""
    mu1, mu2 = np.asarray(mu1, np.float64), np.asarray(mu2, np.float64)
    sigma1, sigma2 = np.asarray(sigma1, np.float64), np.asarray(sigma2, np.float64)
    diff = float(((mu1 - mu2) ** 2).sum())
    return diff + float(np.trace(sigma1) + np.trace(sigma2)) \
        - 2.0 * trace_sqrt_product(sigma1, sigma2)


def fid_from_stats(a: ActivationStats, b: ActivationStats) -> float:
    return frechet_distance(a.mean, a.cov, b.mean, b.cov)


# ---------------------------------------------------------------------------
# feature extraction pipeline
# ---------------------------------------------------------------------------

def make_feature_fn(model=None, variables=None) -> tuple[Callable, int]:
    """Returns ``(feature_fn, dim)`` where ``feature_fn(images_01)`` maps a
    [0,1] NHWC batch (any resolution) to pool3 features.

    With no arguments, uses a random-init InceptionV3 — a valid metric space
    for smoke tests/regression tracking but NOT comparable to published FID
    numbers; pass variables converted from torch weights
    (inception.load_torch_inception) for those. Passing only ``variables``
    pairs them with a default ``InceptionV3Features()``; a model without
    variables is an error (random init would silently corrupt the metric).
    """
    if variables is None:
        if model is not None:
            raise ValueError(
                "inception model given without variables — refusing to pair "
                "real weights' architecture with random init")
        model, variables = inception.init_variables(jax.random.PRNGKey(0))
    elif model is None:
        model = inception.InceptionV3Features()

    @jax.jit
    def feature_fn(images_01):
        x = jnp.clip(images_01, 0.0, 1.0)
        x = jax.image.resize(
            x, (x.shape[0], inception.INCEPTION_SIZE, inception.INCEPTION_SIZE,
                x.shape[3]), method="bilinear")
        x = x * 2.0 - 1.0  # the FID-inception normalization (mean=std=0.5)
        return model.apply(variables, x)

    return feature_fn, inception.FEATURE_DIM


def stats_for_batches(batches: Iterable[np.ndarray], feature_fn: Callable,
                      dim: int = inception.FEATURE_DIM) -> ActivationStats:
    """Accumulate activation statistics over an iterable of [0,1] NHWC batches."""
    stats = ActivationStats(dim)
    for batch in batches:
        stats.update(np.asarray(feature_fn(jnp.asarray(batch))))
    return stats


def fid_between(real_batches: Iterable[np.ndarray],
                fake_batches: Iterable[np.ndarray],
                model=None, variables=None) -> float:
    """End-to-end FID between two streams of [0,1] image batches."""
    feature_fn, dim = make_feature_fn(model, variables)
    real = stats_for_batches(real_batches, feature_fn, dim)
    fake = stats_for_batches(fake_batches, feature_fn, dim)
    return fid_from_stats(real, fake)


def compute_fid(
    model,
    params,
    real_batches: Iterable[np.ndarray],
    *,
    rng: jax.Array,
    n_samples: int = 1024,
    sample_batch: int = 64,
    k: int = 20,
    inception_model=None,
    inception_variables=None,
    sampler: Optional[Callable] = None,
    cache_interval: int = 1,
    cache_mode: str = "delta",
    cache_threshold: Optional[float] = None,
    cache_tokens: Optional[int] = None,
) -> float:
    """FID of a diffusion model's samples against a real-image stream.

    ``model/params`` are the DiffusionViT; samples are drawn with
    ``ops.sampling.ddim_sample`` at stride ``k`` (the north-star metric path:
    200px, k=20) unless a custom ``sampler(rng, n) → [0,1] images`` is given.
    ``cache_interval``/``cache_mode`` pass through to the sampler's step
    cache (ops/step_cache.py); the default interval=1 is the exact sampler.
    """
    from ddim_cold_tpu.ops import sampling

    feature_fn, dim = make_feature_fn(inception_model, inception_variables)
    real = stats_for_batches(real_batches, feature_fn, dim)
    fake = ActivationStats(dim)
    remaining = n_samples
    while remaining > 0:
        # always sample a full batch (static shape → one sampler/inception
        # compile); surplus features of the final batch are dropped before
        # they reach the statistics.
        keep = min(sample_batch, remaining)
        rng, sub = jax.random.split(rng)
        imgs = (sampler(sub, sample_batch) if sampler is not None
                else sampling.ddim_sample(model, params, sub, k=k, n=sample_batch,
                                          cache_interval=cache_interval,
                                          cache_mode=cache_mode,
                                          cache_threshold=cache_threshold,
                                          cache_tokens=cache_tokens))
        fake.update(np.asarray(feature_fn(imgs))[:keep])
        remaining -= keep
    return fid_from_stats(real, fake)


def cached_sampler_guard(
    model,
    params,
    *,
    rng: jax.Array,
    n_samples: int = 256,
    sample_batch: int = 64,
    k: int = 20,
    cache_interval: int = 2,
    cache_mode: str = "full",
    cache_threshold: Optional[float] = None,
    cache_tokens: Optional[int] = None,
    task: str = "sample",
    mask=None,
    inception_model=None,
    inception_variables=None,
) -> dict:
    """Quality guard for the step-cached sampler (ops/step_cache.py): the
    Fréchet distance between the EXACT and CACHED samplers' output streams
    drawn from the SAME rng sequence, under one extractor.

    This is deliberately not "FID vs the real set twice": a paired
    exact-vs-cached distance isolates the cache's own distributional shift
    (it is exactly 0 when the cache is harmless and needs no real images or
    canonical extractor weights), where two FID-vs-real numbers would bury a
    small shift under the shared real-set term. With no
    ``inception_variables`` the extractor is the seeded random-init proxy
    (see :func:`make_feature_fn`) — fine here, because both streams go
    through the SAME extractor and only their distance is reported.

    ``cache_threshold``/``cache_tokens`` pass through to the adaptive/token
    modes (see ``ddim_sample``). ``task`` selects the guarded workload:
    ``"sample"`` (plain generation) or ``"inpaint"``, which pairs the exact
    and step-cached inpainting scans over the same known images (a fresh
    uniform [−1,1] batch per step, drawn from the shared rng stream) and
    ``mask`` (default: top half known) — guarding the editing path's cache
    composition, where the per-step mask re-projection keeps feeding the
    drift gate pixels the cache never predicted.

    Returns a dict with ``fid_exact_vs_cached``, ``max_abs_pixel_delta``
    (worst per-pixel divergence across every paired batch) and the sampler
    configuration.
    """
    from ddim_cold_tpu.ops import sampling

    if task not in ("sample", "inpaint"):
        raise ValueError(f"cached_sampler_guard task must be 'sample' or "
                         f"'inpaint', got {task!r}")
    feature_fn, dim = make_feature_fn(inception_model, inception_variables)
    exact, cached = ActivationStats(dim), ActivationStats(dim)
    H, W = model.img_size
    if task == "inpaint" and mask is None:
        mask = np.zeros((H, W), np.float32)
        mask[: H // 2] = 1.0
    max_delta = 0.0
    remaining = n_samples
    while remaining > 0:
        keep = min(sample_batch, remaining)
        rng, sub = jax.random.split(rng)
        if task == "inpaint":
            from ddim_cold_tpu import workloads

            known = jax.random.uniform(
                jax.random.fold_in(sub, 0xFACE),
                (sample_batch, H, W, model.in_chans),
                jnp.float32, -1.0, 1.0)
            imgs_e = workloads.inpaint(model, params, sub, known, mask, k=k)
            imgs_c = workloads.inpaint(model, params, sub, known, mask, k=k,
                                       cache_interval=cache_interval,
                                       cache_mode=cache_mode,
                                       cache_threshold=cache_threshold,
                                       cache_tokens=cache_tokens)
        else:
            imgs_e = sampling.ddim_sample(model, params, sub, k=k,
                                          n=sample_batch)
            imgs_c = sampling.ddim_sample(model, params, sub, k=k,
                                          n=sample_batch,
                                          cache_interval=cache_interval,
                                          cache_mode=cache_mode,
                                          cache_threshold=cache_threshold,
                                          cache_tokens=cache_tokens)
        max_delta = max(max_delta, float(jnp.max(jnp.abs(imgs_e - imgs_c))))
        exact.update(np.asarray(feature_fn(imgs_e))[:keep])
        cached.update(np.asarray(feature_fn(imgs_c))[:keep])
        remaining -= keep
    return {
        "fid_exact_vs_cached": round(float(fid_from_stats(exact, cached)), 4),
        "max_abs_pixel_delta": round(max_delta, 6),
        "n_samples": n_samples,
        "k": k,
        "task": task,
        "cache_interval": cache_interval,
        "cache_mode": cache_mode,
        "cache_threshold": cache_threshold,
        "cache_tokens": cache_tokens,
        "extractor": ("canonical" if inception_variables is not None else
                      "seeded random-init proxy (paired streams, same "
                      "extractor — distance is meaningful, absolute FID "
                      "scale is not)"),
    }


def quantized_sampler_guard(
    model,
    params,
    *,
    rng: jax.Array,
    n_samples: int = 256,
    sample_batch: int = 64,
    k: int = 20,
    quant: str = "xla",
    cache_interval: int = 1,
    cache_mode: str = "full",
    quantized_params=None,
    inception_model=None,
    inception_variables=None,
) -> dict:
    """Quality guard for the w8a16 trunk (ops/quant.py), the exact shape of
    :func:`cached_sampler_guard`: the Fréchet distance between the EXACT
    float and the QUANTIZED samplers' output streams from the SAME rng
    sequence under one extractor — 0 when quantization is harmless, and the
    acceptance bound ("shift ≤ 0.5") reads directly off it.

    ``model/params`` are the float pair; the quantized side runs
    ``model.clone(quant=quant)`` over ``quant.quantize_params(params)``
    (pass ``quantized_params`` to reuse a tree built elsewhere, e.g. the
    serving engine's). ``cache_interval`` > 1 additionally routes the
    quantized stream through the step cache, measuring the COMPOSED shift
    (quantization × block reuse) the PERF.md composition table reports.
    Alongside the distance, ``quant.calibrate``'s per-layer max-abs-error
    stats ride the report so a bad distance is attributable to a layer.
    """
    from ddim_cold_tpu.ops import quant as quant_mod
    from ddim_cold_tpu.ops import sampling

    qmodel = model.clone(quant=quant)
    qparams = (quantized_params if quantized_params is not None
               else quant_mod.quantize_params(params))
    feature_fn, dim = make_feature_fn(inception_model, inception_variables)
    exact, quantized = ActivationStats(dim), ActivationStats(dim)
    max_delta = 0.0
    remaining = n_samples
    while remaining > 0:
        keep = min(sample_batch, remaining)
        rng, sub = jax.random.split(rng)
        imgs_e = sampling.ddim_sample(model, params, sub, k=k, n=sample_batch)
        imgs_q = sampling.ddim_sample(qmodel, qparams, sub, k=k,
                                      n=sample_batch,
                                      cache_interval=cache_interval,
                                      cache_mode=cache_mode)
        max_delta = max(max_delta, float(jnp.max(jnp.abs(imgs_e - imgs_q))))
        exact.update(np.asarray(feature_fn(imgs_e))[:keep])
        quantized.update(np.asarray(feature_fn(imgs_q))[:keep])
        remaining -= keep
    cal = quant_mod.calibrate(params)
    worst = (max(cal.items(), key=lambda kv: kv[1]["max_abs_err"])
             if cal else (None, None))
    return {
        "fid_exact_vs_quant": round(float(fid_from_stats(exact, quantized)), 4),
        "max_abs_pixel_delta": round(max_delta, 6),
        "n_samples": n_samples,
        "k": k,
        "quant": quant,
        "quant_rev": quant_mod.QUANT_REV,
        "cache_interval": cache_interval,
        "cache_mode": cache_mode,
        "calibration_worst_layer": worst[0],
        "calibration_max_abs_err": (None if worst[1] is None
                                    else round(worst[1]["max_abs_err"], 8)),
        "extractor": ("canonical" if inception_variables is not None else
                      "seeded random-init proxy (paired streams, same "
                      "extractor — distance is meaningful, absolute FID "
                      "scale is not)"),
    }


def distilled_sampler_guard(
    model,
    teacher_params,
    student_params,
    *,
    rng: jax.Array,
    steps: int,
    n_samples: int = 256,
    sample_batch: int = 64,
    k: int = 20,
    cache_interval: int = 1,
    cache_mode: str = "full",
    inception_model=None,
    inception_variables=None,
) -> dict:
    """Quality guard for few-step distilled serving (train/distill.py +
    ``SamplerConfig(steps=k)``), the exact shape of
    :func:`quantized_sampler_guard`: the Fréchet distance between the
    TEACHER's k-step baseline stream and the STUDENT's ``steps``-evaluation
    stream from the SAME rng sequence under one extractor — so a latency win
    bought by cutting k can never silently buy a quality loss. Run it once
    per served student (steps ∈ {1, 2, 4}) to fill PERF.md's k-vs-quality
    table.

    Both streams draw the SAME init per batch (same sub-key, same n), so
    the distance isolates the schedule compression: teacher refines that
    init over ``k`` strided steps (``ddim_sample``), the student jumps it
    through its ``steps``-level schedule (``ddim_sample_fewstep``).
    ``cache_interval`` > 1 routes the STUDENT stream through the step cache,
    measuring the composed shift (distillation × block reuse). Unlike the
    quant guard there is no ``max_abs_pixel_delta`` acceptance reading —
    teacher and student outputs differ by design; the Fréchet shift IS the
    metric.
    """
    from ddim_cold_tpu.ops import sampling

    feature_fn, dim = make_feature_fn(inception_model, inception_variables)
    teacher, student = ActivationStats(dim), ActivationStats(dim)
    max_delta = 0.0
    remaining = n_samples
    while remaining > 0:
        keep = min(sample_batch, remaining)
        rng, sub = jax.random.split(rng)
        imgs_t = sampling.ddim_sample(model, teacher_params, sub, k=k,
                                      n=sample_batch)
        imgs_s = sampling.ddim_sample_fewstep(model, student_params, sub,
                                              steps=steps, n=sample_batch,
                                              cache_interval=cache_interval,
                                              cache_mode=cache_mode)
        max_delta = max(max_delta, float(jnp.max(jnp.abs(imgs_t - imgs_s))))
        teacher.update(np.asarray(feature_fn(imgs_t))[:keep])
        student.update(np.asarray(feature_fn(imgs_s))[:keep])
        remaining -= keep
    return {
        "fid_teacher_vs_student": round(float(fid_from_stats(teacher,
                                                             student)), 4),
        "max_abs_pixel_delta": round(max_delta, 6),
        "n_samples": n_samples,
        "k": k,
        "steps": steps,
        "cache_interval": cache_interval,
        "cache_mode": cache_mode,
        "extractor": ("canonical" if inception_variables is not None else
                      "seeded random-init proxy (paired streams, same "
                      "extractor — distance is meaningful, absolute FID "
                      "scale is not)"),
    }


def superres_consistency_guard(outputs, low_res) -> dict:
    """Editing-quality guard for served super-resolution (ROADMAP open
    item): the delivered output must still CONTAIN its input — nearest-
    downsampling the output (ops/degrade's floor-index convention, i.e.
    sampling the static anchor pixels) must reproduce the low-res input
    bit-exactly, in the engine's [0, 1] delivery space against the task's
    [−1, 1] input space (``(low_res + 1) / 2``).

    The raw cold scan does not guarantee this (its naive Algorithm-1 update
    predicts the anchors rather than carrying them), so callers run
    ``workloads.superres_project`` — the host-side data-consistency
    projection — on the delivered batch first; the guard then proves the
    whole convention stack end to end: the nearest-index math, the value
    mapping, and (served) that every row was projected against ITS OWN
    request's input — a row swap, a bucket-padding leak, or a resampled
    index table all break bit-exactness.

    Returns ``{"bit_exact", "max_abs_delta", "anchor_pixels"}`` —
    ``max_abs_delta`` is also a useful RAW-output quality metric (how far
    the un-projected sampler drifts from its input), which is why the guard
    takes arrays instead of running the sampler itself.
    """
    from ddim_cold_tpu.data.resize import nearest_indices

    out = np.asarray(outputs, np.float32)
    low = np.asarray(low_res, np.float32)
    if out.ndim == 3:
        out = out[None]
    if low.ndim == 3:
        low = low[None]
    iy = nearest_indices(low.shape[1], out.shape[1])
    ix = nearest_indices(low.shape[2], out.shape[2])
    down = out[:, iy[:, None], ix[None, :], :]
    target = (low + 1.0) / 2.0
    return {
        "bit_exact": bool(np.array_equal(down, target)),
        "max_abs_delta": round(float(np.max(np.abs(down - target))), 6),
        "anchor_pixels": int(down[0, ..., 0].size),
    }


# ---------------------------------------------------------------------------
# trend series (scripts/fid_trend.py): thinning, per-point deltas, provenance
# ---------------------------------------------------------------------------

#: a point-to-point FID change inside this relative band is read as noise
REL_FLOOR = 0.1
#: band = max(REL_FLOOR, BAND_K · median |successive relative delta|)
BAND_K = 3.0


def thin(seq, max_points: int) -> list:
    """Evenly thin to ≤ ``max_points``, always keeping first and last."""
    seq = list(seq)
    if max_points <= 0 or len(seq) <= max_points:
        return seq
    if max_points == 1:
        return [seq[0]]
    step = (len(seq) - 1) / (max_points - 1)
    idx = sorted({round(i * step) for i in range(max_points)})
    return [seq[i] for i in idx]


def _noise_band(prior_values) -> float:
    """Relative band for "is the newest delta noise": ``BAND_K`` × the median
    absolute successive relative delta over the prior series, floored at
    ``REL_FLOOR`` (a 1–2 point history has no measurable spread)."""
    deltas = [abs((b - a) / a) for a, b in zip(prior_values,
                                               prior_values[1:]) if a]
    if not deltas:
        return REL_FLOOR
    return max(REL_FLOOR, BAND_K * float(np.median(deltas)))


def annotate_deltas(rows, value_key: str, lower_is_better: bool = False) -> list:
    """Copy ``rows`` (dicts carrying ``value_key``) with per-point
    ``delta_rel`` / ``band`` / ``in_band`` annotations: a point is out of
    band when it is worse than its predecessor by more than the noise band
    of the points before it."""
    out = []
    vals: list = []
    for row in rows:
        row = dict(row)
        v = row.get(value_key)
        if isinstance(v, (int, float)) and vals:
            band = _noise_band(vals)
            prev = vals[-1]
            delta = (float(v) - prev) / abs(prev) if prev else 0.0
            worse = delta > band if lower_is_better else delta < -band
            row.update(delta_rel=round(delta, 4), band=round(band, 4),
                       in_band=not worse)
        if isinstance(v, (int, float)):
            vals.append(float(v))
        out.append(row)
    return out


def run_metadata(chip=None) -> dict:
    """The provenance stamp a trend artifact carries (``run_meta``): git
    sha, device kind, jax/jaxlib versions, round, and an EXTERNALLY-supplied
    timestamp.

    The timestamp comes from ``DDIM_COLD_RUN_TS`` (seconds since epoch) or
    ``SOURCE_DATE_EPOCH``, never from the wall clock here — an unstamped
    environment yields ``None`` rather than a value that would make re-runs
    nondeterministic."""
    import os
    import subprocess
    from importlib.metadata import PackageNotFoundError, version

    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=here, capture_output=True, text=True,
                             timeout=10).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):  # no git / not a checkout
        sha = None

    def _version(dist):
        try:
            return version(dist)
        except PackageNotFoundError:
            return None

    ts = None
    raw_ts = (os.environ.get("DDIM_COLD_RUN_TS")
              or os.environ.get("SOURCE_DATE_EPOCH") or "").strip()
    if raw_ts:
        try:
            ts = float(raw_ts)
        except ValueError:
            ts = raw_ts  # ISO strings still order lexicographically
    rnd = os.environ.get("DDIM_COLD_ROUND", "").strip()
    return {
        "git_sha": sha,
        "device_kind": chip,
        "jax": _version("jax"),
        "jaxlib": _version("jaxlib"),
        "timestamp": ts,
        "round": int(rnd) if rnd.isdigit() else None,
    }
