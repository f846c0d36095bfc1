"""The k-strided DDIM sampler of the upstream model (ViT.py:220-237), as a
Python loop over one jitted step.

    for t in range(T-1, 0, -k):
        x0 = clamp(model(x, t), -1, 1)
        a_t  = 1 - sqrt((t+1)/T) + 1e-5
        a_tk = 1 - sqrt((t+1-k)/T)
        noise = (x - sqrt(a_t) x0) / sqrt(1 - a_t)
        x = sqrt(a_tk) (x/sqrt(a_t) + (sqrt((1-a_tk)/a_tk) - sqrt((1-a_t)/a_t)) noise)
    return (x0 + 1) / 2

The schedule numbers are Python floats (float64) handed to the step as
float32 scalars, as upstream's ``math.sqrt`` values are. Departure: where
``t+1-k`` is negative upstream would raise; it is clamped to 0 here (never
reached for the strides the benchmark uses).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import vit


def time_sequence(total_steps: int, k: int) -> list[int]:
    return list(range(total_steps - 1, 0, -k))


@partial(jax.jit, static_argnames=("arch", "ops"))
def _step(params, x, t, a_t, a_tk, *, arch, ops):
    n = x.shape[0]
    x0 = vit.forward(params, x, jnp.full((n,), t, jnp.int32), ops=ops,
                     **dict(arch))
    x0 = jnp.clip(x0, -1.0, 1.0)
    noise = (x - jnp.sqrt(a_t) * x0) / jnp.sqrt(1.0 - a_t)
    d = jnp.sqrt((1.0 - a_tk) / a_tk) - jnp.sqrt((1.0 - a_t) / a_t)
    return jnp.sqrt(a_tk) * (x / jnp.sqrt(a_t) + d * noise), x0


def sample(params, x_init, *, k: int, total_steps: int, arch: dict,
           ops=vit.EXACT, steps: int | None = None):
    """Images in [0, 1] after ``steps`` (default: all) reverse steps."""
    x = jnp.asarray(x_init, jnp.float32)
    x0 = x
    arch = tuple(sorted(arch.items()))
    for t in time_sequence(total_steps, k)[:steps]:
        a_t = 1.0 - math.sqrt((t + 1.0) / total_steps) + 1e-5
        a_tk = 1.0 - math.sqrt(max(t + 1.0 - k, 0.0) / total_steps)
        x, x0 = _step(params, x, t, jnp.float32(a_t), jnp.float32(a_tk),
                      arch=arch, ops=ops)
    return (x0 + 1.0) / 2.0
