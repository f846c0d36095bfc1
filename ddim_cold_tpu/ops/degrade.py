"""Device-side cold degradation operator D(x, t) — the jittable twin of
data/resize.py's host pipeline.

Index math is identical to the host path (torch interpolate-nearest
convention: src = floor(dst · in/out)), so host-prepared training targets and
on-device degradations agree bit-for-bit — the golden-test in
tests/test_degrade.py pins this.

Down-then-up nearest resize composes into a single gather per axis:
``idx[i] = down_idx[up_idx[i]]``; each level is a static gather and a traced
per-sample ``t`` selects between levels via ``lax.switch`` under ``vmap``
(compiler-friendly — no dynamic shapes).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ddim_cold_tpu.data.resize import nearest_indices


def _level_indices(size: int, level: int) -> np.ndarray:
    """Composed gather indices for one degradation level (2^level)."""
    target = max(int(np.floor(size / (2**level))), 1)
    down = nearest_indices(target, size)  # small ← big
    up = nearest_indices(size, target)  # big ← small
    return down[up]


@partial(jax.jit, static_argnames=("size", "max_step"))
def cold_degrade(imgs: jax.Array, t: jax.Array, *, size: int, max_step: int = 6) -> jax.Array:
    """D(x, t) for a batch: (B, H, W, C) float, per-sample int t ∈ [0, max_step].

    t=0 is the identity (the reference's D(x, 2^0) — two identity resizes,
    diffusion_loader.py:94-95 with t−1=0).
    """
    tables = jnp.asarray(
        np.stack([_level_indices(size, lv) for lv in range(max_step + 1)])
    )  # (levels+1, size)

    def one(img, ti):
        idx = tables[ti]
        return img[idx][:, idx]

    return jax.vmap(one)(imgs, t.astype(jnp.int32))


def upsample_nearest(imgs: jax.Array, size: int) -> jax.Array:
    """Nearest-upsample (B, h, w, C) → (B, size, size, C), torch convention.

    The "up" half of the cold degradation on its own: for a low-res image
    ``lo = nearest-downsample(x, level)``, ``upsample_nearest(lo, size)`` IS
    ``cold_degrade(x, level)`` — the degraded full-size state the cold scan
    starts from. The super-resolution workload (ddim_cold_tpu/workloads)
    uses exactly this to lift a user's low-res input into the sampler's
    state space; the index math matches the host path bit-for-bit, so a
    constant-color 1×1 input reproduces ``cold_sample``'s broadcast init
    exactly (the equivalence test in tests/test_workloads.py).
    """
    imgs = jnp.asarray(imgs, jnp.float32)
    if imgs.ndim == 3:
        imgs = imgs[None]
    iy = jnp.asarray(nearest_indices(size, imgs.shape[1]))
    ix = jnp.asarray(nearest_indices(size, imgs.shape[2]))
    return imgs[:, iy][:, :, ix]


def normalize_base(base: jax.Array) -> jax.Array:
    """Raw base image → float32 in [−1, 1] with the host pipeline's exact op
    order (÷255 then ·2−1, datasets._load_base) so a uint8-shipped batch is
    bit-identical to the host-normalized float path. Float input passes
    through (already normalized host-side)."""
    if base.dtype == jnp.uint8:
        return base.astype(jnp.float32) / 255.0 * 2.0 - 1.0
    return base


def _batch_constrain(mesh, batch_axis):
    """Sharding hint pinning arrays batch-sharded, all other dims replicated.

    The degrade gathers are per-sample ops: partitioned over batch they need
    zero communication, but left to the partitioner's cost model under a
    dp×tp×sp mesh it can pick a W-sharded layout for the gather and then hit
    "Involuntary full rematerialization" resharding into the attention layout
    (the replicate-the-tensor fallback). Identity when no
    mesh is given or the axis isn't in it (single-chip callers)."""
    if mesh is None or batch_axis not in getattr(mesh, "axis_names", ()):
        return lambda a: a
    from jax.sharding import NamedSharding, PartitionSpec

    def con(a):
        spec = PartitionSpec(batch_axis, *([None] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    return con


def make_cold_prepare(size: int, max_step: int, chain: bool, *,
                      mesh=None, batch_axis: str = "data"):
    """In-jit batch corruption for the device-side cold data path.

    The host ships only ``(base, t)`` — one clean image per sample instead of
    the two degraded float copies (2× less host→device traffic) — and this
    hook (train/step.py
    ``prepare``) rebuilds the exact host contract ``(D(x,t), D(x,t−1)|x₀, t)``
    on device. The degradation is a pure gather (cold_degrade), so the result
    is bit-identical to the host/C++ pipeline. ``normalize_base`` additionally
    accepts uint8 bases (a further 4× for identity-resize datasets) for
    callers that ship raw bytes.

    ``mesh``/``batch_axis`` keep the gathers batch-sharded under SPMD (see
    ``_batch_constrain``); pass the training mesh whenever the step is jitted
    over one.
    """
    con = _batch_constrain(mesh, batch_axis)

    def prepare(batch, rng):
        del rng  # cold corruption is deterministic given (base, t)
        base, t = batch
        x = con(normalize_base(base))
        t = con(t)
        noisy = con(cold_degrade(x, t, size=size, max_step=max_step))
        target = (con(cold_degrade(x, t - 1, size=size, max_step=max_step))
                  if chain else x)
        return noisy, target, t

    return prepare


def make_gaussian_prepare(total_steps: int, *, mesh=None,
                          batch_axis: str = "data"):
    """In-jit Gaussian forward-noising for the device-side data path (C13).

    The host ships ``(x₀, t)`` with t from the same Philox stream as the host
    pipeline (identical noising *schedule*); ε is drawn ON DEVICE from the
    step rng under ᾱ(t) = 1 − √((t+1)/T) (reference diffusion_loader.py:52-54,
    the ViT.py:231 schedule). The noise bit-stream therefore differs from the
    host path — statistically identical, not bit-identical, which is why the
    trainer keeps the val loader on the host path (deterministic val loss).
    """

    con = _batch_constrain(mesh, batch_axis)

    def prepare(batch, rng):
        base, t = batch
        x = con(normalize_base(base))
        t = con(t)
        alpha = 1.0 - jnp.sqrt((t.astype(jnp.float32) + 1.0) / total_steps)
        alpha = alpha[:, None, None, None]
        noise = jax.random.normal(rng, x.shape, jnp.float32)
        noisy = jnp.sqrt(alpha) * x + jnp.sqrt(1.0 - alpha) * noise
        return noisy, x, t

    return prepare
