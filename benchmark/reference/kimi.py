"""``(x_t, t) -> x0_hat`` of the delta-attention / position-free
latent-attention / sigmoid-routed denoiser, and its DDIM loop: float32,
matmul precision ``highest``, no kernels. Imports nothing of the program.

The trunk is a slice of Kimi-Linear-48B-A3B-Instruct's decoder stack
(``model_type: kimi_linear``,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
between this system's own input stage (patch projection, class token, learned
position table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel
head. Layer i of the slice is published layer ``layers_from + i``; the two
lists of ``linear_attn_config`` count layers from 1. With x in R^{L x
hidden_size}, every norm an rms_norm with a gain, eps = ``rms_norm_eps``, no
bias anywhere, positions 0 (class token), 1, ... in raster order, no position
term inside any layer:

* layer: ``x += mixer(N_a(x))``; ``x += ffn(N_b(x))`` (``input_layernorm``,
  ``post_attention_layernorm``); after the last layer the final rms_norm.
  The mixer is ``kda`` where ``layers_from + i + 1`` is in ``kda_layers``,
  ``mla`` where it is in ``full_attn_layers``.
* ``kda`` (H = ``linear_attn_config.num_heads`` heads of d = its ``head_dim``
  key and value channels): ``q = l2(silu(conv(y W_q)))``, ``k = l2(silu(conv(y
  W_k)))``, ``v = silu(conv(y W_v))``: three projections to H d columns, three
  depthwise causal convolutions of ``short_conv_kernel_size`` taps without
  bias (``u_t <- sum_j w_j * u_{t-taps+1+j}``), ``l2(x) = x / sqrt(sum x^2 +
  1e-6)`` over a head's d channels; ``g = -exp(A_log_h) softplus((y W_fa)
  W_fb + dt_bias)`` a head and key channel; ``beta = sigmoid(y W_beta)`` a
  head; then for each head, ``S_0 = 0``, S in R^{d x d} (key channel x value
  channel): ``S' = diag(exp(g_t)) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S_t^T q_t d^-1/2``: TOKEN BY TOKEN, a ``lax.scan``
  over all the tokens with the ``(H, d, d)`` state in float32: no chunks, no
  blocks, no solve; out ``= (rms_norm_head(o) * sigmoid((y W_ga) W_gb))
  W_o``, the norm over each head's d channels with one gain of d for all the
  heads, the gate after it.
* ``mla`` (H = ``num_attention_heads``): ``[q_nope_h, q_r_h] = y W_q`` for each
  head, no query latent; ``[c_kv, k_r] = y W_kva``; ``c_kv = rms_norm(c_kv)``;
  ``[k_nope_h, v_h] = c_kv W_kvb``; nothing is rotated. Score of query t, key
  s <= t, head h: ``(q_nope_h . k_nope_h + q_r_h . k_r) (nope + rot)^-1/2``;
  softmax over s <= t; out ``= concat_h(sum_s p_s v_h,s) W_o``. Computed per
  head on the assembled ``k_h = [k_nope_h, k_r]``, one block of
  ``QUERY_BLOCK`` queries at a time against the keys up to the last query of
  the block's run (``KEY_EXTENTS`` runs) under an explicit boolean mask.
* ``ffn``, published layer ``< first_k_dense_replace``: ``W_down(silu(W_gate
  y) * W_up y)`` at ``intermediate_size``. Else: ``r = sigmoid(y W_r)`` over
  all ``num_experts_routed`` outputs; chosen = the ``num_experts_per_token``
  largest of ``r + e_score_correction_bias`` (the bias chooses, never weighs;
  ties to the lower index; one group: no group limit); ``w_e =
  routed_scaling_factor r_e / sum_chosen r`` (``moe_renormalize``); out ``=
  shared(y) + sum_{e chosen, e held} w_e E_e(y)``, experts and the shared one
  that MLP at ``moe_intermediate_size``.

Departures from the source, each also in the configuration file:

* **column order**: the tree is the program's, whose ``q_proj`` (of an
  ``mla`` layer) holds all the heads' nope columns and then all their second
  parts, and whose ``kv_b_proj`` all the ``k_nope`` columns and then all the
  ``v`` columns (published: a head's parts side by side). Read here by that
  rule: ``reference/pangu.py``'s ``heads_of``.
* **the share**: ``num_experts`` experts from ``experts_held_from`` on are
  held (128 from 0: one of 2 chips that share each layer by its experts); the
  router keeps its published width. What the experts held elsewhere would add
  is left out, and that partial result goes on to the next layer.
* ``num_hidden_layers`` 5 of 27: published layers 0-4, the leading dense layer
  and one whole period kda, kda, mla, kda. The vocabulary is not held.
* ``assumed``: everything the modelling code decides and ``config.json`` has
  no key for; the configuration file lists each.

Every contraction between activations and weights goes through the ``ops``
pair of ``reference/vit.py`` so that ``lowprec`` can stand in for the control;
the scan is a plain ``lax.scan`` over tokens, its sums elementwise in
float32. The parameter tree is the program's (bfloat16 at the published
size); a layer's mixer and its MLP front are upcast apart, one jitted function
each, the experts a block at a time as ``reference/laguna.py`` has them.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import vit
from .ddim import time_sequence
from .glm import _f32, _query_blocks, QUERY_BLOCK
from .hybrid import _embed, _head, _update, mlp, rms_norm, silu
from .laguna import BANKS, experts
from .pangu import KEY_EXTENTS, heads_of

L2_EPS = 1e-6


def layer_kind(cfg: dict, i: int) -> str:
    """``kda`` | ``mla`` of layer i of the slice."""
    number = cfg.get("layers_from", 0) + i + 1
    lists = cfg["linear_attn_config"]
    if number in lists["kda_layers"]:
        return "kda"
    if number in lists["full_attn_layers"]:
        return "mla"
    raise ValueError(f"layer {number} is in neither list of linear_attn_config")


def recurrence(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` of the delta rule above, token by token. ``q, k, v,
    g: (n, L, H, d)``; ``beta: (n, L, H)``."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S                        # (n, H, d, d)
        miss = v_t - (S * k_t[..., None]).sum(-2)
        S = S + (b_t[..., None] * k_t)[..., None] * miss[..., None, :]
        return S, (S * q_t[..., None]).sum(-2)

    first = lambda a: jnp.swapaxes(a, 0, 1)
    n, _, H, d = q.shape
    _, out = jax.lax.scan(step, jnp.zeros((n, H, d, d), jnp.float32),
                          tuple(first(a) for a in (q, k, v, g, beta)))
    return first(out)


def conv_silu(w, u):
    """``silu(sum_j w_j * u_{t-taps+1+j})``; ``w: (taps, channels)``."""
    taps, n_tok = w.shape[0], u.shape[1]
    past = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return silu(sum(w[j] * past[:, j:j + n_tok] for j in range(taps)))


def kda(p, y, cfg, ops):
    mm, _ = ops
    lin = cfg["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    n, n_tok, _ = y.shape
    heads = lambda a: a.reshape(n, n_tok, H, d)
    q, k, v = (heads(conv_silu(p[f"{part}_conv1d"]["conv1d_kernel"],
                               mm(y, p[f"{part}_proj"]["kernel"])))
               for part in "qkv")
    l2 = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        mm(mm(y, p["f_a_proj"]["kernel"]), p["f_b_proj"]["kernel"])
        + p["dt_bias"]))
    beta = jax.nn.sigmoid(mm(y, p["b_proj"]["kernel"]))
    out = recurrence(l2(q) * d ** -0.5, l2(k), v, g, beta)
    out = out * jax.lax.rsqrt((out * out).mean(-1, keepdims=True)
                              + cfg["rms_norm_eps"]) * p["o_norm"]["scale"]
    gate = mm(mm(y, p["g_a_proj"]["kernel"]), p["g_b_proj"]["kernel"])
    return mm(out.reshape(n, n_tok, H * d) * jax.nn.sigmoid(gate),
              p["o_proj"]["kernel"])


def mla(p, y, cfg, ops):
    mm, contract = ops
    n, n_tok, _ = y.shape
    H, nope, rot = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"])
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    q = jnp.concatenate(heads_of(mm(y, p["q_proj"]["kernel"]), H, nope), -1)
    kv_a = mm(y, p["kv_a_proj_with_mqa"]["kernel"])
    k_nope, v = heads_of(
        mm(rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], cfg["rms_norm_eps"]),
           p["kv_b_proj"]["kernel"]), H, nope)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        kv_a[:, :, None, rank:], (n, n_tok, H, rot))], axis=-1)
    q_blocks, starts = _query_blocks(q, n_tok)
    runs = []
    per = -(-len(starts) // KEY_EXTENTS)
    for lo in range(0, len(starts), per):
        hi = min(lo + per, len(starts))
        seen = min(hi * QUERY_BLOCK, n_tok)
        k_seen, v_seen, col = k[:, :seen], v[:, :seen], jnp.arange(seen)

        def block(args, k_seen=k_seen, v_seen=v_seen, col=col):
            q_b, start = args  # (n, QUERY_BLOCK, H, nope + rot)
            logits = (contract("bnhd,bmhd->bhnm", q_b, k_seen)
                      * (nope + rot) ** -0.5)
            # rows of padding past the last token see every key of the run
            sees = col <= (start + jnp.arange(QUERY_BLOCK))[:, None]
            attn = jax.nn.softmax(jnp.where(sees, logits, -jnp.inf), -1)
            return contract("bhnm,bmhd->bnhd", attn, v_seen)

        runs.append(jax.lax.map(block, (q_blocks[lo:hi], starts[lo:hi])))
    out = jnp.moveaxis(jnp.concatenate(runs, 0), 0, 1)
    return mm(out.reshape(n, -1, H * vd)[:, :n_tok], p["o_proj"]["kernel"])


def route(p, y, cfg, ops):
    """(expert ids, weights), each ``(rows, num_experts_per_token)``."""
    mm, _ = ops
    r = jax.nn.sigmoid(mm(y, p["router"]))
    _, top_e = jax.lax.top_k(r + p["e_score_correction_bias"],
                             cfg["num_experts_per_token"])
    top_r = jnp.take_along_axis(r, top_e, axis=-1)
    if cfg["moe_renormalize"]:
        top_r = top_r / top_r.sum(-1, keepdims=True)
    return top_e, cfg["routed_scaling_factor"] * top_r


@partial(jax.jit, static_argnames=("cfg", "kind", "ops"))
def _mix(p, x, *, cfg, kind, ops):
    """``x + mixer(N_a(x))``. ``p``: the layer's tree without its MLP."""
    cfg, p = json.loads(cfg), _f32(p)
    y = rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"])
    return x + (kda if kind == "kda" else mla)(p["self_attn"], y, cfg, ops)


@partial(jax.jit, static_argnames=("cfg", "dense", "ops"))
def _ffn_front(norm, p, x, *, cfg, dense, ops):
    """Dense layer: the layer's output. Sparse layer: ``(x + shared(y), y,
    expert ids, weights)``; ``p`` its MLP's tree without the expert banks."""
    cfg, p = json.loads(cfg), _f32(p)
    y = rms_norm(x, _f32(norm), cfg["rms_norm_eps"])
    if dense:
        return x + mlp(p, y, ops)
    y2 = y.reshape(-1, y.shape[-1])
    top_e, weight = route(p, y2, cfg, ops)
    return x + mlp(p["shared_expert"], y, ops), y2, top_e, weight


def sparse_mlp(p, y, cfg, ops=vit.EXACT):
    """``ffn`` of a sparse layer on ``y (rows, hidden)``; ``p`` its tree."""
    small = _f32({k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small, y, cfg, ops)
    return (mlp(small["shared_expert"], y, ops)
            + experts({k: p[k] for k in BANKS}, y, top_e, weight, cfg, ops))


def layer(p, x, cfg, i, ops=vit.EXACT):
    """Layer i of the slice on ``x (n, L, hidden)``; ``p`` its tree."""
    static = json.dumps(cfg, sort_keys=True)
    x = _mix({k: v for k, v in p.items() if k != "mlp"}, x, cfg=static,
             kind=layer_kind(cfg, i), ops=ops)
    norm = p["post_attention_layernorm"]
    if cfg.get("layers_from", 0) + i < cfg["first_k_dense_replace"]:
        return _ffn_front(norm, p["mlp"], x, cfg=static, dense=True, ops=ops)
    rest = {k: v for k, v in p["mlp"].items() if k not in BANKS}
    x, y, top_e, weight = _ffn_front(norm, rest, x, cfg=static, dense=False,
                                     ops=ops)
    return x + experts({k: p["mlp"][k] for k in BANKS}, y, top_e, weight, cfg,
                       ops).reshape(x.shape)


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``num_experts`` the experts held, plus
    ``num_experts_routed``, ``experts_held_from`` and ``layers_from``."""
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok = _embed(outer, x, t, patch_size=patch_size, ops=ops)
    for i in range(trunk["num_hidden_layers"]):
        tok = layer(params[f"layers_{i}"], tok, trunk, i, ops)
    return _head(outer, tok, patch_size=patch_size, shape=x.shape[1:],
                 eps=trunk["rms_norm_eps"], ops=ops)


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
