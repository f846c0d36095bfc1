"""``(x_t, t) -> x0_hat`` of the x0-predicting ViT, float32, no kernels.

NHWC images in [-1, 1]; the patch projection is the Conv2d(kernel=stride=p) of
the upstream model written as reshape + matmul (the same linear map). Every
contraction goes through ``mm``/``contract`` so that the control of the
benchmark's ``correct`` can put a lower precision in their place
(``reference/lowprec.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def exact_mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def exact_contract(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


EXACT = (exact_mm, exact_contract)


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / jnp.sqrt(2.0)))


def patchify(x, p):
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def unpatchify(tok, p, h, w, c):
    b = tok.shape[0]
    x = tok.reshape(b, h // p, w // p, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


def block(p, x, heads, ops):
    mm, contract = ops
    b, n, d = x.shape
    hd = d // heads
    y = layer_norm(x, p["norm1"])
    qkv = mm(y, p["attn"]["qkv"]["kernel"]) + p["attn"]["qkv"]["bias"]
    qkv = qkv.reshape(b, n, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = contract("bnhd,bmhd->bhnm", q, k) * hd ** -0.5
    attn = jax.nn.softmax(logits, axis=-1)
    out = contract("bhnm,bmhd->bnhd", attn, v).reshape(b, n, d)
    x = x + mm(out, p["attn"]["proj"]["kernel"]) + p["attn"]["proj"]["bias"]
    y = layer_norm(x, p["norm2"])
    y = gelu(mm(y, p["mlp"]["fc1"]["kernel"]) + p["mlp"]["fc1"]["bias"])
    return x + mm(y, p["mlp"]["fc2"]["kernel"]) + p["mlp"]["fc2"]["bias"]


def forward(params, x, t, *, patch_size, depth, num_heads, ops=EXACT,
            remat=False):
    """x0_hat in [-inf, inf] (the sampler clamps), NHWC float32."""
    mm, _ = ops
    x = x.astype(jnp.float32)
    b, h, w, c = x.shape
    tok = mm(patchify(x, patch_size), params["patch_embed"]["proj"]["kernel"])
    tok = tok + params["patch_embed"]["proj"]["bias"]
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, tok.shape[-1]))
    tok = jnp.concatenate([cls, tok], axis=1)
    tok = tok + params["pos_embed"]
    tok = tok + params["time_embed"]["embedding"][t][:, None, :]
    blk = (lambda p, z: block(p, z, num_heads, ops))
    if remat:
        blk = jax.checkpoint(blk)
    for i in range(depth):
        tok = blk(params[f"blocks_{i}"], tok)
    tok = layer_norm(tok, params["norm"])
    out = mm(tok, params["head"]["kernel"]) + params["head"]["bias"]
    return unpatchify(out[:, 1:], patch_size, h, w, c)
