"""The control of ``correct``: the reference's contractions computed one
step below the configuration's stated precision.

The configurations state bfloat16 compute; the step below is an 8-bit float.
``fp8_ops`` quantizes both operands of every contraction to float8_e4m3fn
with one scale per tensor (amax mapped to the format's largest value), which
is the 8-bit path a later change would be tempted by; accumulation stays
float32. ``bf16_ops`` is the stated precision itself and is what sound runs
are expected to sit near.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import vit

E4M3_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _ops(cast):
    return (lambda x, w: vit.exact_mm(cast(x), cast(w)),
            lambda eq, a, b: vit.exact_contract(eq, cast(a), cast(b)))


FP8 = _ops(_fp8)
BF16 = _ops(_bf16)
BY_NAME = {"float32": vit.EXACT, "bfloat16": BF16, "float8_e4m3": FP8}
#: the nearest precision below the one a configuration states
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3"}
