"""``(x_t, t) -> x0_hat`` of the hybrid state-space denoiser, and its DDIM
loop: float32, matmul precision ``highest``, no kernels. Imports nothing of
the program.

The trunk is AI21-Jamba2-3B's decoder stack (``model_type: jamba``,
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json) between
this system's own input stage (patch projection, class token, learned
position table, time table: ``reference/vit.py``) and pixel head. With
x in R^{L x hidden_size}, eps = ``rms_norm_eps``, no bias unless said:

* layer i: ``x += mixer_i(rms_norm(x))``; ``x += W_down(silu(W_gate y) *
  W_up y)``, ``y = rms_norm(x)``; after the last layer the final rms_norm.
  ``mixer_i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset``, else Mamba.
* Mamba-1 (Gu & Dao 2023; d = mamba_expand * hidden_size): ``[u, z] = x
  W_in``; ``u_t <- silu(b_c + sum_j w_j * u_{t-k+1+j})``; ``[dt, B, C] = u
  W_x``, each rms_normed; ``Delta = softplus(dt W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``h_t = exp(Delta_t x A) * h_{t-1} + (Delta_t * u_t) x B_t``,
  ``h_0 = 0``; ``y_t = h_t C_t + D * u_t``; out = ``(y * silu(z)) W_out``.
* attention: ``num_attention_heads`` query heads on ``num_key_value_heads``
  shared K/V heads, no position term, scale head_dim^-1/2, causal mask.

Departures from the source, each also in the configuration file: the token
embedding and tied output head (``vocab_size`` rows) are not held: nothing
here draws or scores token ids; the inner rms_norms on dt, B and C are the
``jamba`` modelling code's (``dt_layernorm``, ``b_layernorm``,
``c_layernorm``), not keys of ``config.json``; the layer order ``i %
attn_layer_period == attn_layer_offset`` is that code's rule; the position
term is this system's learned table (Jamba has none).

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control; the scan is a plain
``lax.scan`` over tokens. The parameter tree is the program's (bfloat16 at
the published size) and is upcast one layer at a time, one jitted function
per layer kind, so that the reference fits beside the program's weights.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import vit
from .ddim import time_sequence


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def selective_scan(u, delta, a, b, c):
    """``y_t = h_t C_t`` of the recurrence above. ``u, delta: (n, L, d)``;
    ``a: (d, s)``; ``b, c: (n, L, s)``."""

    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = (jnp.exp(d_t[..., None] * a) * h
             + (d_t * u_t)[..., None] * b_t[:, None, :])
        return h, (h * c_t[:, None, :]).sum(-1)

    first = lambda x: jnp.swapaxes(x, 0, 1)
    h0 = jnp.zeros((u.shape[0],) + a.shape, jnp.float32)
    _, ys = jax.lax.scan(step, h0, (first(u), first(delta), first(b), first(c)))
    return first(ys)


def mamba(p, x, cfg, ops):
    mm, _ = ops
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    s, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps, n_tok = cfg["rms_norm_eps"], x.shape[1]
    uz = mm(x, p["in_proj"]["kernel"])
    if cfg["mamba_proj_bias"]:
        uz = uz + p["in_proj"]["bias"]
    u, z = uz[..., :d], uz[..., d:]
    past = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    u = sum(p["conv1d_kernel"][j] * past[:, j:j + n_tok] for j in range(k))
    if cfg["mamba_conv_bias"]:
        u = u + p["conv1d_bias"]
    u = silu(u)
    dbc = mm(u, p["x_proj"]["kernel"])
    dt = rms_norm(dbc[..., :r], p["dt_layernorm"], eps)
    b = rms_norm(dbc[..., r:r + s], p["b_layernorm"], eps)
    c = rms_norm(dbc[..., r + s:], p["c_layernorm"], eps)
    delta = jax.nn.softplus(mm(dt, p["dt_proj"]["kernel"]) + p["dt_proj"]["bias"])
    y = selective_scan(u, delta, -jnp.exp(p["A_log"]), b, c) + p["D"] * u
    out = mm(y * silu(z), p["out_proj"]["kernel"])
    return out + p["out_proj"]["bias"] if cfg["mamba_proj_bias"] else out


def attention(p, x, cfg, ops):
    mm, contract = ops
    n, n_tok, width = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = width // heads
    # query head h = g * (heads / kv) + r reads K/V head g
    q = mm(x, p["q_proj"]["kernel"]).reshape(n, n_tok, kv, heads // kv, hd)
    k = mm(x, p["k_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    v = mm(x, p["v_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    logits = contract("bngrd,bmgd->bgrnm", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((n_tok, n_tok), bool))
    attn = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = contract("bgrnm,bmgd->bngrd", attn, v).reshape(n, n_tok, width)
    return mm(out, p["o_proj"]["kernel"])


def mlp(p, x, ops):
    mm, _ = ops
    hidden = silu(mm(x, p["gate_proj"]["kernel"])) * mm(x, p["up_proj"]["kernel"])
    return mm(hidden, p["down_proj"]["kernel"])


def is_attention_layer(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


@partial(jax.jit, static_argnames=("cfg", "attn", "ops"))
def _layer(p, x, *, cfg, attn, ops):
    cfg = dict(cfg)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    y = rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"])
    x = x + (attention(p["self_attn"], y, cfg, ops) if attn
             else mamba(p["mamba"], y, cfg, ops))
    y = rms_norm(x, p["pre_ff_layernorm"], cfg["rms_norm_eps"])
    return x + mlp(p["feed_forward"], y, ops)


@partial(jax.jit, static_argnames=("patch_size", "ops"))
def _embed(p, x, t, *, patch_size, ops):
    mm, _ = ops
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    tok = mm(vit.patchify(x.astype(jnp.float32), patch_size),
             p["patch_embed"]["proj"]["kernel"]) + p["patch_embed"]["proj"]["bias"]
    cls = jnp.broadcast_to(p["cls_token"], (x.shape[0], 1, tok.shape[-1]))
    tok = jnp.concatenate([cls, tok], axis=1) + p["pos_embed"]
    return tok + p["time_embed"]["embedding"][t][:, None, :]


@partial(jax.jit, static_argnames=("patch_size", "shape", "eps", "ops"))
def _head(p, tok, *, patch_size, shape, eps, ops):
    mm, _ = ops
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    tok = rms_norm(tok, p["final_layernorm"], eps)
    out = mm(tok, p["head"]["kernel"]) + p["head"]["bias"]
    return vit.unpatchify(out[:, 1:], patch_size, *shape)


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, type(None)))))


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys."""
    cfg = _hashable(trunk)
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok = _embed(outer, x, t, patch_size=patch_size, ops=ops)
    for i in range(trunk["num_hidden_layers"]):
        tok = _layer(params[f"layers_{i}"], tok, cfg=cfg,
                     attn=is_attention_layer(trunk, i), ops=ops)
    return _head(outer, tok, patch_size=patch_size, shape=x.shape[1:],
                 eps=trunk["rms_norm_eps"], ops=ops)


@jax.jit
def _update(x, x0, a_t, a_tk):
    """The reverse step of ``reference/ddim.py`` (upstream ViT.py:220-237)."""
    x0 = jnp.clip(x0, -1.0, 1.0)
    noise = (x - jnp.sqrt(a_t) * x0) / jnp.sqrt(1.0 - a_t)
    d = jnp.sqrt((1.0 - a_tk) / a_tk) - jnp.sqrt((1.0 - a_t) / a_t)
    return jnp.sqrt(a_tk) * (x / jnp.sqrt(a_t) + d * noise), x0


def sample(params, x_init, *, k: int, total_steps: int, trunk: dict,
           patch_size: int, ops=vit.EXACT, steps: int | None = None):
    """Images in [0, 1] after ``steps`` (default: all) reverse steps; the
    schedule as ``reference/ddim.py`` has it."""
    x = jnp.asarray(x_init, jnp.float32)
    x0 = x
    for t in time_sequence(total_steps, k)[:steps]:
        a_t = 1.0 - math.sqrt((t + 1.0) / total_steps) + 1e-5
        a_tk = 1.0 - math.sqrt(max(t + 1.0 - k, 0.0) / total_steps)
        x0 = forward(params, x, jnp.full((x.shape[0],), t, jnp.int32),
                     trunk=trunk, patch_size=patch_size, ops=ops)
        x, x0 = _update(x, x0, jnp.float32(a_t), jnp.float32(a_tk))
    return (x0 + 1.0) / 2.0
