"""``(x_t, t) -> x0_hat`` of the Mamba-2 / attention / latent-expert denoiser,
and its DDIM loop: float32, matmul precision ``highest``, no kernels. Imports
nothing of the program.

The trunk is a slice of NVIDIA-Nemotron-3-Super-120B-A12B's decoder stack
(``model_type: nemotron_h``,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
between this system's own input stage (patch projection, class token, learned
position table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel
head. Layer i of the slice is published layer ``layers_from + i`` and its kind
is letter i of the slice's ``hybrid_override_pattern``. With x in R^{L x
hidden_size}, every norm an rms_norm with a gain, eps =
``layer_norm_epsilon``, no bias except the convolution's, positions 0 (class
token), 1, ... in raster order:

* layer: ``x += mixer(rms_norm(x))``; after the last layer the final
  rms_norm.
* ``M`` (H = ``mamba_num_heads``, P = ``mamba_head_dim``, N =
  ``ssm_state_size``, G = ``n_groups``, d = H P): ``[z, xBC, dt] = y W_in``
  (d, d + 2GN, H columns, in that order); ``xBC_t <- silu(b_c + sum_j w_j *
  xBC_{t-k+1+j})`` (depthwise, ``conv_kernel`` taps, causal); ``[x, B, C] =
  xBC`` (d, GN, GN); ``Delta = softplus(dt + dt_bias)`` a head; ``a_h =
  -exp(A_log_h)``; for head h in group g = h // (H/G), ``S_0 = 0``:
  ``S_t = exp(Delta_t,h a_h) S_{t-1} + Delta_t,h x_t,h (x) B_t,g``,
  ``y_t,h = S_t C_t,g + D_h x_t,h``: TOKEN BY TOKEN, a ``lax.scan`` over all
  the tokens with the ``(H, P, N)`` state in float32 — no chunks, no blocks;
  then ``y <- rms_norm_G(y * silu(z))``, the gate first, the variance over
  each of the G groups of d/G channels, one gain of d; out ``= y W_out``.
* ``*``: ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads`` K/V heads (query head h reads K/V head h //
  (heads/kv)), score ``q_t . k_s head_dim^-1/2`` for s <= t, softmax, NO
  rotary and no other position term; one block of ``QUERY_BLOCK`` queries at
  a time against all the keys under an explicit boolean mask.
* ``E``: ``r = sigmoid(y W_r)`` over all ``n_experts_routed`` outputs; chosen
  = the ``num_experts_per_tok`` largest of ``r + e_score_correction_bias``
  (the bias chooses, never weighs; ties to the lower index; no group limit);
  ``w_e = routed_scaling_factor r_e / sum_chosen r`` (``norm_topk_prob``);
  ``l = y W_lin`` (``fc1_latent_proj``); ``E_e(l) = W_down,e relu(W_up,e
  l)^2``; out ``= shared(y) + (sum_{e chosen, e held} w_e E_e(l)) W_lout``
  (``fc2_latent_proj``), ``shared(y) = W_d relu(W_u y)^2`` on the full width.

Departures from the source, each also in the configuration file:

* **the share**: ``n_routed_experts`` experts from ``experts_held_from`` on
  are held (128 from 0: one of 4 chips that share each layer by its experts);
  the router keeps its published width. What the experts held elsewhere would
  add is left out, and that partial result goes on to the next layer.
* ``num_hidden_layers`` 11 of 88: published layers 27-37, one whole period
  ``MEMEMEMEM*E``. The MTP module and the vocabulary are not held.
* ``assumed``, because the modelling code decides it and ``config.json`` has
  no key: no rotary term in attention; the column orders ``[z, xBC, dt]`` and
  ``[x, B, C]``; Delta unclamped; the gate before the grouped norm; the
  latent's place (one projection in and one out a layer, router and shared
  expert on the full width); the router's sigmoid score and selection bias;
  this system's image, patch, time table and learned position table.

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control; the scan is a plain
``lax.scan`` over tokens. The parameter tree is the program's (bfloat16 at
the published size); a layer is upcast by itself, one jitted function a
kind, the experts ``EXPERT_BLOCK`` at a time, each applied only to the rows
whose chosen set contains it (row lists taken on the host, padded to whole
multiples of ``ROW_PAD`` rows of weight 0 so that few shapes compile).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import vit
from .ddim import time_sequence
from .hybrid import _embed, _head, _update, rms_norm, silu

#: experts upcast and applied together
EXPERT_BLOCK = 16
#: an expert's row list is padded to a multiple of this: at ~704 rows an
#: expert nearly every block of 16 then has the one shape (1,024)
ROW_PAD = 512
#: queries scored against all the keys at a time
QUERY_BLOCK = 128
BANKS = ("up_proj", "down_proj")


def _f32(p):
    return jax.tree.map(lambda w: w.astype(jnp.float32), p)


def recurrence(x, delta, a, b, c):
    """``y_t = S_t C_t`` of the recurrence above, token by token. ``x: (n, L,
    H, P)``; ``delta: (n, L, H)``; ``a: (H,)``; ``b, c: (n, L, G, N)``."""
    heads, groups = x.shape[2], b.shape[2]

    def step(S, xs):
        x_t, d_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, heads // groups, axis=1) for v in (b_t, c_t))
        S = (jnp.exp(d_t * a)[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, (S * c_t[:, :, None, :]).sum(-1)

    first = lambda v: jnp.swapaxes(v, 0, 1)
    S0 = jnp.zeros((x.shape[0], heads, x.shape[3], b.shape[3]), jnp.float32)
    _, ys = jax.lax.scan(step, S0, (first(x), first(delta), first(b), first(c)))
    return first(ys)


def mamba2(p, y, cfg, ops):
    mm, _ = ops
    H, P, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"])
    d, k = H * P, cfg["conv_kernel"]
    n, n_tok, _ = y.shape
    zxbcdt = mm(y, p["in_proj"]["kernel"])
    z, xbc, dt = zxbcdt[..., :d], zxbcdt[..., d:2 * d + 2 * G * N], zxbcdt[..., 2 * d + 2 * G * N:]
    past = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(p["conv1d_kernel"][j] * past[:, j:j + n_tok] for j in range(k))
    if cfg["use_conv_bias"]:
        xbc = xbc + p["conv1d_bias"]
    xbc = silu(xbc)
    x = xbc[..., :d].reshape(n, n_tok, H, P)
    b = xbc[..., d:d + G * N].reshape(n, n_tok, G, N)
    c = xbc[..., d + G * N:].reshape(n, n_tok, G, N)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    out = recurrence(x, delta, -jnp.exp(p["A_log"]), b, c)
    out = (out + p["D"][:, None] * x).reshape(n, n_tok, d) * silu(z)
    runs = out.reshape(n, n_tok, G, d // G)
    runs = runs * jax.lax.rsqrt((runs * runs).mean(-1, keepdims=True)
                                + cfg["layer_norm_epsilon"])
    return mm(runs.reshape(n, n_tok, d) * p["norm"]["scale"],
              p["out_proj"]["kernel"])


def attention(p, y, cfg, ops):
    mm, contract = ops
    n, n_tok, _ = y.shape
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    # query head h = g * (heads / kv) + r reads K/V head g
    q = mm(y, p["q_proj"]["kernel"]).reshape(n, n_tok, kv, heads // kv, hd)
    k = mm(y, p["k_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    v = mm(y, p["v_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    blocks = -(-n_tok // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - n_tok)) + ((0, 0),) * 3)
    q = jnp.moveaxis(q.reshape(n, blocks, QUERY_BLOCK, kv, heads // kv, hd), 1, 0)
    col = jnp.arange(n_tok)

    def block(args):
        q_b, start = args
        # rows of padding past the last token see what the last token sees
        row = jnp.minimum(start + jnp.arange(QUERY_BLOCK), n_tok - 1)[:, None]
        logits = contract("bngrd,bmgd->bgrnm", q_b, k) * hd ** -0.5
        attn = jax.nn.softmax(jnp.where(col <= row, logits, -jnp.inf), axis=-1)
        return contract("bgrnm,bmgd->bngrd", attn, v)

    out = jax.lax.map(block, (q, jnp.arange(blocks) * QUERY_BLOCK))
    out = jnp.moveaxis(out, 0, 1).reshape(n, blocks * QUERY_BLOCK, heads * hd)
    return mm(out[:, :n_tok], p["o_proj"]["kernel"])


def relu2_mlp(p, x, ops):
    mm, _ = ops
    hidden = jnp.square(jnp.maximum(mm(x, p["up_proj"]["kernel"]), 0.0))
    return mm(hidden, p["down_proj"]["kernel"])


def route(p, y, cfg, ops):
    """(expert ids, weights), each ``(rows, num_experts_per_tok)``."""
    mm, _ = ops
    r = jax.nn.sigmoid(mm(y, p["router"]))
    _, top_e = jax.lax.top_k(r + p["e_score_correction_bias"],
                             cfg["num_experts_per_tok"])
    top_r = jnp.take_along_axis(r, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_r = top_r / top_r.sum(-1, keepdims=True)
    return top_e, cfg["routed_scaling_factor"] * top_r


@partial(jax.jit, static_argnames=("count", "ops"))
def _expert_block(banks, lat, rows, weights, start, *, count, ops):
    """``count`` experts from ``start`` on, each applied to its own rows of
    the latent ``lat (rows, latent)``. ``banks``: the layer's two stacked
    arrays, whole, in storage precision; ``rows``, ``weights``: ``(count,
    padded rows)``. Returns the weighted results added up by row."""
    _, contract = ops
    take = lambda bank: jax.lax.dynamic_slice_in_dim(
        bank, start, count).astype(jnp.float32)
    x = lat[rows]  # (count, padded, latent)
    hidden = jnp.square(jnp.maximum(
        contract("epd,edf->epf", x, take(banks["up_proj"])), 0.0))
    out = contract("epf,efd->epd", hidden, take(banks["down_proj"]))
    out = out * weights[..., None]
    return jnp.zeros_like(lat).at[rows.reshape(-1)].add(
        out.reshape(-1, lat.shape[-1]))


def experts(banks, lat, top_e, weight, cfg, ops):
    """``sum_{e chosen, e held} w_e E_e(l)`` over the rows of ``lat``: a loop
    over the held experts, ``EXPERT_BLOCK`` at a time, each applied to the
    rows routed to it (lists taken on the host)."""
    first, held = cfg.get("experts_held_from", 0), cfg["n_routed_experts"]
    top_e, weight = np.asarray(top_e), np.asarray(weight)
    k = top_e.shape[1]
    order = np.argsort(top_e.reshape(-1), kind="stable")
    bounds = np.searchsorted(top_e.reshape(-1)[order],
                             np.arange(first, first + held + 1))
    total = jnp.zeros_like(lat)
    for b0 in range(0, held, EXPERT_BLOCK):
        count = min(EXPERT_BLOCK, held - b0)
        spans = [order[bounds[e]:bounds[e + 1]] for e in range(b0, b0 + count)]
        width = max(ROW_PAD, -(-max(map(len, spans)) // ROW_PAD) * ROW_PAD)
        rows = np.zeros((count, width), np.int32)
        weights = np.zeros((count, width), np.float32)
        for j, span in enumerate(spans):
            rows[j, :len(span)] = span // k
            weights[j, :len(span)] = weight.reshape(-1)[span]
        total = total + _expert_block(banks, lat, rows, weights, b0,
                                      count=count, ops=ops)
    return total


@partial(jax.jit, static_argnames=("cfg", "kind", "ops"))
def _mix(p, x, *, cfg, kind, ops):
    """``x + mixer(rms_norm(x))`` of an ``M`` or ``*`` layer; ``p`` its
    tree."""
    cfg, p = json.loads(cfg), _f32(p)
    y = rms_norm(x, p["norm"], cfg["layer_norm_epsilon"])
    return x + (mamba2 if kind == "M" else attention)(p["mixer"], y, cfg, ops)


@partial(jax.jit, static_argnames=("cfg", "ops"))
def _experts_front(norm, p, x, *, cfg, ops):
    """``(shared(y), the latent l, expert ids, weights)`` of an ``E`` layer;
    ``p`` its mixer's tree without the expert banks."""
    mm, _ = ops
    cfg, p = json.loads(cfg), _f32(p)
    y = rms_norm(x, _f32(norm), cfg["layer_norm_epsilon"])
    y2 = y.reshape(-1, y.shape[-1])
    top_e, weight = route(p, y2, cfg, ops)
    return (relu2_mlp(p["shared_expert"], y, ops),
            mm(y2, p["fc1_latent_proj"]["kernel"]), top_e, weight)


@partial(jax.jit, static_argnames=("ops",))
def _experts_back(w_out, x, shared, routed, *, ops):
    mm, _ = ops
    return x + shared + mm(routed, w_out.astype(jnp.float32)).reshape(x.shape)


def latent_experts(p, y, cfg, ops=vit.EXACT):
    """The mixer of an ``E`` layer on ``y (rows, hidden)``; ``p`` its tree."""
    mm, _ = ops
    small = _f32({k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small, y, cfg, ops)
    lat = mm(y, small["fc1_latent_proj"]["kernel"])
    routed = experts({k: p[k] for k in BANKS}, lat, top_e, weight, cfg, ops)
    return (relu2_mlp(small["shared_expert"], y, ops)
            + mm(routed, small["fc2_latent_proj"]["kernel"]))


def layer(p, x, cfg, i, ops=vit.EXACT):
    """Layer i of the slice on ``x (n, L, hidden)``; ``p`` its tree."""
    static = json.dumps(cfg, sort_keys=True)
    kind = cfg["hybrid_override_pattern"][i]
    if kind in "M*":
        return _mix(p, x, cfg=static, kind=kind, ops=ops)
    if kind != "E":
        raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}")
    mixer = p["mixer"]
    rest = {k: v for k, v in mixer.items() if k not in BANKS}
    shared, lat, top_e, weight = _experts_front(p["norm"], rest, x, cfg=static,
                                                ops=ops)
    routed = experts({k: mixer[k] for k in BANKS}, lat, top_e, weight, cfg, ops)
    return _experts_back(mixer["fc2_latent_proj"]["kernel"], x, shared, routed,
                         ops=ops)


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``n_routed_experts`` the experts held, plus
    ``n_experts_routed``, ``experts_held_from`` and ``layers_from``."""
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok = _embed(outer, x, t, patch_size=patch_size, ops=ops)
    for i in range(trunk["num_hidden_layers"]):
        tok = layer(params[f"layers_{i}"], tok, trunk, i, ops)
    return _head(outer, tok, patch_size=patch_size, shape=x.shape[1:],
                 eps=trunk["layer_norm_epsilon"], ops=ops)


def sample(params, x_init, *, k: int, total_steps: int, trunk: dict,
           patch_size: int, ops=vit.EXACT, steps: int | None = None):
    """Images in [0, 1] after ``steps`` (default: all) reverse steps; the
    schedule and the update as ``reference/ddim.py`` has them."""
    x = jnp.asarray(x_init, jnp.float32)
    x0 = x
    for t in time_sequence(total_steps, k)[:steps]:
        a_t = 1.0 - math.sqrt((t + 1.0) / total_steps) + 1e-5
        a_tk = 1.0 - math.sqrt(max(t + 1.0 - k, 0.0) / total_steps)
        x0 = forward(params, x, jnp.full((x.shape[0],), t, jnp.int32),
                     trunk=trunk, patch_size=patch_size, ops=ops)
        x, x0 = _update(x, x0, jnp.float32(a_t), jnp.float32(a_tk))
    return (x0 + 1.0) / 2.0
