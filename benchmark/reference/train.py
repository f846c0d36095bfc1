"""The upstream training step (multi_gpu_trainer.py:89-134) and the cold
degradation that feeds it (diffusion_loader.py:60-97), plain float32.

loss = mean smooth-L1(model(D(x, t), t), D(x, t-1));  grads clipped to global
norm 1;  AdamW(b1 .9, b2 .999, eps 1e-8, weight decay .05 on every leaf) with
the learning rate on a cosine to 0 over ``decay_steps``, stepped per batch.
Dropout is off: its masks come from the program's own random stream, which a
reference that takes nothing from the program cannot reproduce (the cells
that use this run the program with dropout 0 and say so).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import vit

B1, B2, EPS, WEIGHT_DECAY, CLIP = 0.9, 0.999, 1e-8, 0.05, 1.0


def nearest_indices(out_size: int, in_size: int) -> np.ndarray:
    """torch ``interpolate(mode="nearest")``: source = floor(dst * in / out)."""
    idx = np.floor(np.arange(out_size) * (in_size / out_size)).astype(np.int64)
    return np.minimum(idx, in_size - 1)


def cold_degrade(img: np.ndarray, scale: int) -> np.ndarray:
    """D(x, s): nearest down to floor(size / s), nearest back up. (H, W, C)."""
    size = img.shape[0]
    small = max(int(math.floor(size / scale)), 1)
    down = nearest_indices(small, size)
    up = nearest_indices(size, small)
    return img[down][:, down][up][:, up]


def cold_batch(base_u8: np.ndarray, t: np.ndarray):
    """(noisy, target) float32 in [-1, 1] from uint8 bases and levels t >= 1:
    the chain targets of the upstream trainer, D(x, 2^t) -> D(x, 2^(t-1))."""
    x = base_u8.astype(np.float32) / 255.0 * 2.0 - 1.0  # ToTensor, Normalize(.5, .5)
    noisy = np.stack([cold_degrade(x[i], 2 ** int(t[i])) for i in range(len(t))])
    target = np.stack([cold_degrade(x[i], 2 ** (int(t[i]) - 1))
                       for i in range(len(t))])
    return noisy, target


def smooth_l1_sum(pred, target):
    d = jnp.abs(pred - target)
    return jnp.sum(jnp.where(d < 1.0, 0.5 * d * d, d - 0.5))


@partial(jax.jit, static_argnames=("arch", "ops"))
def _loss_grad_block(params, noisy, target, t, *, arch, ops):
    def f(p):
        pred = vit.forward(p, noisy, t, ops=ops, remat=True, **dict(arch))
        return smooth_l1_sum(pred, target)
    return jax.value_and_grad(f)(params)


def loss_and_grads(params, noisy, target, t, *, arch: dict, ops=vit.EXACT,
                   rows: int = 0):
    """Mean loss over the batch and its gradients, in blocks of ``rows`` rows
    (0: the whole batch at once) so that a long-sequence batch fits."""
    n = noisy.shape[0]
    rows = rows or n
    arch = tuple(sorted(arch.items()))
    total, grads = 0.0, None
    for lo in range(0, n, rows):
        sl = slice(lo, lo + rows)
        val, g = _loss_grad_block(params, jnp.asarray(noisy[sl]),
                                  jnp.asarray(target[sl]),
                                  jnp.asarray(t[sl], jnp.int32),
                                  arch=arch, ops=ops)
        total = total + val
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    denom = float(np.prod(noisy.shape))
    return total / denom, jax.tree.map(lambda g: g / denom, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree.leaves(tree)))


def clip(grads):
    norm = global_norm(grads)
    return jax.tree.map(
        lambda g: jnp.where(norm < CLIP, g, g / norm * CLIP), grads)


def cosine_lr(lr: float, count: int, decay_steps: int) -> float:
    return lr * 0.5 * (1.0 + math.cos(math.pi * min(count, decay_steps)
                                      / decay_steps))


def adamw_step(params, mu, nu, grads, count: int, lr: float, decay_steps: int):
    """One optimizer step on already-clipped grads; ``count`` starts at 0."""
    mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
    c1, c2 = 1 - B1 ** (count + 1), 1 - B2 ** (count + 1)
    step_lr = cosine_lr(lr, count, decay_steps)
    params = jax.tree.map(
        lambda p, m, v: p - step_lr * ((m / c1) / (jnp.sqrt(v / c2) + EPS)
                                       + WEIGHT_DECAY * p),
        params, mu, nu)
    return params, mu, nu


def follow(params, batches, *, arch: dict, lr: float, decay_steps: int,
           ops=vit.EXACT, rows: int = 0):
    """Drive the reference through ``batches`` of (noisy, target, t).
    Returns the per-step losses, the first clipped gradient, and the
    parameters after the last step."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    mu, nu = zeros, zeros
    losses, first = [], None
    for count, (noisy, target, t) in enumerate(batches):
        loss, grads = loss_and_grads(params, noisy, target, t, arch=arch,
                                     ops=ops, rows=rows)
        grads = clip(grads)
        if first is None:
            first = grads
        params, mu, nu = adamw_step(params, mu, nu, grads, count, lr,
                                    decay_steps)
        losses.append(float(loss))
    return losses, first, params
