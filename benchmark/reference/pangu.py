"""``(x_t, t) -> x0_hat`` of the dense-causal latent-attention,
sandwich-normed, sigmoid-routed denoiser, and its DDIM loop: float32, matmul
precision ``highest``, no kernels. Imports nothing of the program.

The trunk is a slice of openPangu-Ultra-MoE-718B's decoder stack
(``model_type: pangu_ultra_moe``,
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json)
between this system's own input stage (patch projection, class token, learned
position table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel
head. Layer i of the slice is published layer ``layers_from + i``. With x in
R^{L x hidden_size}, N_a..N_d rms_norms with their own gains, eps =
``rms_norm_eps``, no bias anywhere, positions 0 (class token), 1, ... in
raster order:

* layer (``sandwich_norm``): ``x += N_b(attn(N_a(x)))``; ``x +=
  N_d(ffn(N_c(x)))`` (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``); after the last layer the
  final rms_norm.
* ``attn`` (H = ``num_attention_heads``): ``c_q = rms_norm(y W_qa)``;
  ``[q_nope_h, q_r_h] = c_q W_qb`` for each head (``qk_nope_head_dim`` +
  ``qk_rope_head_dim``). ``[c_kv, k_r] = y W_kva``; ``c_kv = rms_norm(c_kv)``;
  ``[k_nope_h, v_h] = c_kv W_kvb``. Rotary (theta = ``rope_theta``, ``inv_j =
  theta^(-2j / qk_rope_head_dim)``, dim j paired with dim j + rot/2) on every
  ``q_r_h`` and on the one ``k_r``, shared by all the heads. Score of query
  t, key s <= t, head h: ``(q_nope_h . k_nope_h + q_r_h . k_r) * (nope +
  rot)^-1/2``; softmax over s <= t; ``o_h = sum_s p_s v_h,s``; out ``=
  concat_h(o_h) W_o``. Computed per head on the assembled ``k_h =
  [k_nope_h, k_r]``, one block of queries at a time under an explicit
  boolean mask, against the keys up to the last query of the block's run.
* ``ffn``, published layer ``< first_k_dense_replace``: ``W_down(silu(W_gate
  y) * W_up y)`` at ``intermediate_size``. Else: ``s = sigmoid(y W_r)`` over
  all ``n_experts_routed`` outputs; chosen = the ``num_experts_per_tok``
  largest (ties to the lower index; no selection bias, no group limit);
  ``w_e = routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``);
  out ``= shared(y) + sum_{e chosen, e held} w_e E_e(y)``, experts and the
  shared one that MLP at ``moe_intermediate_size``.

Departures from the source, each also in the configuration file:

* **column order**: the tree is the program's, whose ``q_b_proj`` holds all
  the heads' nope columns and then all their rotated columns, and whose
  ``kv_b_proj`` all the ``k_nope`` columns and then all the ``v`` columns
  (published: a head's parts side by side). Read here by that rule:
  :func:`heads_of`.
* **the share**: ``n_routed_experts`` experts from ``experts_held_from`` on
  are held (8 from 0: one of 32 chips that share each layer by its experts);
  the router keeps its published width. What the experts held elsewhere would
  add is left out, and that partial result goes on to the next layer.
* ``num_hidden_layers`` 5 of 61: published layers 2-6, the last leading dense
  layer and four expert layers. The MTP module and the vocabulary are not
  held.
* ``assumed``, because the modelling code decides it and ``config.json`` has
  no key: the router's sigmoid score without a bias; the two inner rms_norms
  (``q_a_layernorm``, ``kv_a_layernorm``); ``rotate_half`` pairing; that the
  second norm of a sandwich sits on the sub-layer's result, before the add;
  this system's image, patch, time table and learned position table.

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control. The parameter tree is the
program's (bfloat16 at the published size); a layer's attention and its MLP
front are upcast apart, one jitted function each, the experts a block at a
time as ``reference/laguna.py`` has them.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import vit
from .ddim import time_sequence
from .glm import _f32, _held, _query_blocks, QUERY_BLOCK, rotary
from .hybrid import _embed, _head, _update, mlp, rms_norm
from .laguna import BANKS, experts

#: runs of query blocks, each scored against the keys up to its last query:
#: 9/16 of the pairs at 8 where one run scores every pair (0.26 against 0.47 s
#: a layer at 9,217 tokens x 128 heads on the chip, same result to 1e-6)
KEY_EXTENTS = 8


def heads_of(x, heads: int, first: int):
    """The two parts of an up-projection's result ``x (n, L, H·(first +
    second))`` whose columns are all the heads' first parts, then all their
    second parts: ``((n, L, H, first), (n, L, H, second))``."""
    n, n_tok, _ = x.shape
    return (x[..., :heads * first].reshape(n, n_tok, heads, first),
            x[..., heads * first:].reshape(n, n_tok, heads, -1))


def attention(p, x, cfg, ops):
    """``attn`` of the layer's normed input ``x``: every head's key assembled
    as ``k_h = [k_nope_h, k_r]``, queries in blocks of ``QUERY_BLOCK`` rows,
    the blocks in ``KEY_EXTENTS`` runs, each run against the keys up to its
    last query (no query of the run sees a later one) under the explicit
    mask s <= t."""
    mm, contract = ops
    n, n_tok, _ = x.shape
    H, nope, rot = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"])
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    c_q = rms_norm(mm(x, p["q_a_proj"]["kernel"]), p["q_a_layernorm"], eps)
    q_nope, q_r = heads_of(mm(c_q, p["q_b_proj"]["kernel"]), H, nope)
    q = jnp.concatenate([q_nope, rotary(q_r, theta, 0, rot, False)], axis=-1)
    kv_a = mm(x, p["kv_a_proj_with_mqa"]["kernel"])
    k_r = rotary(kv_a[:, :, None, rank:], theta, 0, rot, False)
    k_nope, v = heads_of(
        mm(rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], eps),
           p["kv_b_proj"]["kernel"]), H, nope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (n, n_tok, H, rot))], axis=-1)
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
    """(expert ids, weights), each ``(rows, num_experts_per_tok)``."""
    mm, _ = ops
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(mm(y, p["router"])),
                                 cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_e, cfg["routed_scaling_factor"] * top_s


@partial(jax.jit, static_argnames=("cfg", "ops"))
def _attend(p, x, *, cfg, ops):
    """``x + N_b(attn(N_a(x)))``. ``p``: the layer's tree without its MLP."""
    cfg, p = json.loads(cfg), _f32(p)
    eps = cfg["rms_norm_eps"]
    out = attention(p["self_attn"], rms_norm(x, p["input_layernorm"], eps),
                    cfg, ops)
    return x + rms_norm(out, p["post_attention_layernorm"], eps)


@partial(jax.jit, static_argnames=("cfg", "dense", "ops"))
def _ffn_front(norms, p, x, *, cfg, dense, ops):
    """Dense layer: the layer's output. Sparse layer: ``(shared(y), y, expert
    ids, weights)``; ``p`` its MLP's tree without the expert banks; ``norms``
    the layer's ``pre_mlp_layernorm`` and ``post_mlp_layernorm``."""
    cfg, p, (before, after) = json.loads(cfg), _f32(p), _f32(norms)
    y = rms_norm(x, before, cfg["rms_norm_eps"])
    if dense:
        return x + rms_norm(mlp(p, y, ops), after, cfg["rms_norm_eps"])
    y2 = y.reshape(-1, y.shape[-1])
    top_e, weight = route(p, y2, cfg, ops)
    return mlp(p["shared_expert"], y, ops), y2, top_e, weight


@partial(jax.jit, static_argnames=("eps",))
def _add_normed(x, out, norm, *, eps):
    return x + rms_norm(out, _f32(norm), eps)


def sparse_mlp(p, y, cfg, ops=vit.EXACT):
    """``ffn`` of a sparse layer on ``y (rows, hidden)``; ``p`` its tree."""
    small = _f32({k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small, y, cfg, ops)
    return (mlp(small["shared_expert"], y, ops)
            + experts({k: p[k] for k in BANKS}, y, top_e, weight, _held(cfg),
                      ops))


def layer(p, x, cfg, i, ops=vit.EXACT):
    """Layer i of the slice on ``x (n, L, hidden)``; ``p`` its tree."""
    static = json.dumps(cfg, sort_keys=True)
    x = _attend({k: v for k, v in p.items() if k != "mlp"}, x, cfg=static,
                ops=ops)
    norms = p["pre_mlp_layernorm"], p["post_mlp_layernorm"]
    if cfg.get("layers_from", 0) + i < cfg["first_k_dense_replace"]:
        return _ffn_front(norms, p["mlp"], x, cfg=static, dense=True, ops=ops)
    rest = {k: v for k, v in p["mlp"].items() if k not in BANKS}
    shared, y, top_e, weight = _ffn_front(norms, rest, x, cfg=static,
                                          dense=False, ops=ops)
    routed = experts({k: p["mlp"][k] for k in BANKS}, y, top_e, weight,
                     _held(cfg), ops)
    return _add_normed(x, shared + routed.reshape(x.shape), norms[1],
                       eps=cfg["rms_norm_eps"])


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``n_routed_experts`` the experts held, plus
    ``n_experts_routed``, ``experts_held_from`` and ``layers_from``."""
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
