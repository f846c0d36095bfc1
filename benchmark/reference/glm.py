"""``(x_t, t) -> x0_hat`` of the latent-attention, sparse-selection,
sigmoid-routed denoiser, and its DDIM loop: float32, matmul precision
``highest``, no kernels. Imports nothing of the program.

The trunk is a slice of GLM-5.2's decoder stack (``model_type: glm_moe_dsa``,
https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json) between this
system's own input stage (patch projection, class token, learned position
table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel head.
Layer i of the slice is published layer ``layers_from + i`` and reads that
entry of ``indexer_types`` and ``mlp_layer_types``. With x in R^{L x
hidden_size}, eps = ``rms_norm_eps``, y = rms_norm(x), no bias but the
indexer's layer norm, positions 0 (class token), 1, ... in raster order:

* layer: ``x += attn(rms_norm(x))``; ``x += ffn(rms_norm(x))``; after the
  last layer the final rms_norm.
* ``attn`` (H = ``num_attention_heads``): ``c_q = rms_norm(y W_qa)``; ``q_h =
  c_q W_qb`` -> H heads of ``[q_nope (qk_nope_head_dim), q_rope
  (qk_rope_head_dim)]``. ``[c_kv, k_r] = y W_kva``; ``c_kv = rms_norm(c_kv)``;
  ``[k_nope_h, v_h] = c_kv W_kvb`` for each head. Rotary (theta =
  ``rope_parameters.rope_theta``, ``inv_j = theta^(-2j / qk_rope_head_dim)``;
  ``rope_interleave``: dims 2j, 2j + 1 pair) on every ``q_rope_h`` and on the
  one ``k_r``, shared by all the heads. Score of query t, key s, head h:
  ``(q_nope_h . k_nope_h + q_rope_h . k_r) * qk_head_dim^-1/2``; softmax over
  **s in S_t only**; ``o_h = sum_s p_s v_h,s``; out ``= concat_h(o_h) W_o``.
  Computed per head, one block of queries at a time against all the keys
  under an explicit boolean mask.
* the indexer (``indexer_types[layer] == "full"``; J = ``index_n_heads``, D =
  ``index_head_dim``): ``q^I = c_q W^I_q``; ``k^I = layer_norm(y W^I_k)`` (one
  head; eps 1e-6, scale and bias); rotary on the first ``qk_rope_head_dim``
  dims of both (``indexer_rope_interleave``); ``w = y W^I_w * J^-1/2 *
  D^-1/2``; ``I_ts = sum_j w_tj relu(q^I_tj . k^I_s)``. ``tau_t`` = the
  ``index_topk``-th largest of ``{I_ts : s <= t}`` with multiplicity (a row's
  sorted visible scores; -inf where t sees fewer); ``S_t = {s <= t : I_ts >=
  tau_t}``. ``"shared"``: the S of the nearest ``full`` layer before it.
* ``ffn``, ``mlp_layer_types[layer] == "dense"``: ``W_down(silu(W_gate y) *
  W_up y)`` at ``intermediate_size``. ``"sparse"``: ``s = sigmoid(y W_r)``
  over all ``n_experts_routed`` outputs; chosen = the ``num_experts_per_tok``
  largest of ``s + b`` (``e_score_correction_bias``; ties to the lower index;
  ``n_group`` = ``topk_group`` = 1: no group limit); ``w_e =
  routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``); out ``=
  shared(y) + sum_{e chosen, e held} w_e E_e(y)``, experts and the shared one
  that MLP at ``moe_intermediate_size``.

Departures from the source, each also in the configuration file:

* **ties**: every key whose score EQUALS tau_t is kept, so a row may keep
  more than ``index_topk`` keys; a ``topk`` would keep exactly that many and
  break the tie by an order of its own.
* **the share**: ``n_routed_experts`` experts from ``experts_held_from`` on
  are held (16 from 0: one of 16 chips that share each layer by its experts);
  the router keeps its published width. What the experts held elsewhere would
  add is left out, and that partial result goes on to the next layer.
* ``num_hidden_layers`` 5 of 78: published layers 2-6, the last leading dense
  layer and one period (shared, shared, shared, full) of the indexer pattern.
* the MTP module and the vocabulary are not held; the Hadamard rotation the
  public inference code applies to the indexer's q and k before its FP8 cast
  is orthogonal on both sides of a dot product and changes no score.
* ``assumed``, because the modelling code decides it and ``config.json`` has
  no key: the two inner rms_norms (``q_a_layernorm``, ``kv_a_layernorm``);
  the indexer's layer norm, its eps and the ``J^-1/2 D^-1/2`` scale; that the
  indexer rotates its FIRST ``qk_rope_head_dim`` dims and attention its LAST;
  the router's weights from the unbiased scores; this system's image, patch,
  time table and learned position table.

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
import numpy as np

from . import vit
from .ddim import time_sequence
from .hybrid import _embed, _head, _update, mlp, rms_norm
from .laguna import BANKS, experts, rotate

#: queries scored against all the keys at a time
QUERY_BLOCK = 128
INDEX_NORM_EPS = 1e-6


def rotary(x, theta: float, first: int, rot: int, interleave: bool):
    """``x (n, L, heads, head_dim)``: dims ``first .. first + rot`` of every
    head rotated by token position."""
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    part = x[..., first:first + rot]
    if interleave:
        angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
                 * jnp.asarray(inv, jnp.float32))[:, None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = part[..., 0::2], part[..., 1::2]
        part = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(part.shape)
    else:
        part = rotate(part, inv, 1.0)
    return jnp.concatenate([x[..., :first], part, x[..., first + rot:]], -1)


def _query_blocks(x, n_tok):
    """``x (n, L, ...)`` -> ``(blocks, n, QUERY_BLOCK, ...)``, zero rows past
    the last token, and each block's first row."""
    blocks = -(-n_tok // QUERY_BLOCK)
    pad = [(0, 0), (0, blocks * QUERY_BLOCK - n_tok)] + [(0, 0)] * (x.ndim - 2)
    x = jnp.pad(x, pad).reshape(x.shape[0], blocks, QUERY_BLOCK, *x.shape[2:])
    return jnp.moveaxis(x, 1, 0), jnp.arange(blocks) * QUERY_BLOCK


def selection(p, y, c_q, cfg, ops):
    """S as a bool ``(n, L, L)``: row t, the keys query t attends to."""
    mm, contract = ops
    n, n_tok, _ = y.shape
    J, D, rot = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    pairs = cfg.get("indexer_rope_interleave", False)
    q = rotary(mm(c_q, p["wq_b"]["kernel"]).reshape(n, n_tok, J, D),
               theta, 0, rot, pairs)
    k = mm(y, p["wk"]["kernel"])
    mean = k.mean(-1, keepdims=True)
    var = ((k - mean) ** 2).mean(-1, keepdims=True)
    k = ((k - mean) / jnp.sqrt(var + INDEX_NORM_EPS) * p["k_norm"]["scale"]
         + p["k_norm"]["bias"])
    k = rotary(k[:, :, None, :], theta, 0, rot, pairs)[:, :, 0]
    w = mm(y, p["weights_proj"]["kernel"]) * (J ** -0.5 * D ** -0.5)
    top, col = cfg["index_topk"], jnp.arange(n_tok)

    def block(args):
        q_b, w_b, start = args  # (n, QUERY_BLOCK, J, D), (n, QUERY_BLOCK, J)
        row = (start + jnp.arange(QUERY_BLOCK))[:, None]
        sees = col <= row
        dots = jnp.maximum(contract("bnjd,bmd->bjnm", q_b, k), 0.0)
        scores = (dots * jnp.moveaxis(w_b, 2, 1)[..., None]).sum(1)
        seen = jnp.where(sees, scores, -jnp.inf)
        if top >= n_tok:
            return jnp.broadcast_to(sees, seen.shape)
        tau = jnp.sort(seen, axis=-1)[..., n_tok - top, None]
        return (seen >= tau) & sees

    q_blocks, starts = _query_blocks(q, n_tok)
    w_blocks, _ = _query_blocks(w, n_tok)
    keep = jax.lax.map(block, (q_blocks, w_blocks, starts))
    return jnp.moveaxis(keep, 0, 1).reshape(n, -1, n_tok)[:, :n_tok]


def attention(p, x, keep, cfg, full: bool, ops):
    """(``attn`` of the layer's normed input ``x``, the S it attended over:
    its own where the layer is ``full``, else ``keep`` as handed)."""
    mm, contract = ops
    n, n_tok, _ = x.shape
    H, nope, rot = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"])
    hd, vd, rank = cfg["qk_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    pairs = cfg.get("rope_interleave", False)
    c_q = rms_norm(mm(x, p["q_a_proj"]["kernel"]), p["q_a_layernorm"], eps)
    q = rotary(mm(c_q, p["q_b_proj"]["kernel"]).reshape(n, n_tok, H, hd),
               theta, nope, rot, pairs)
    kv_a = mm(x, p["kv_a_proj_with_mqa"]["kernel"])
    k_r = rotary(kv_a[:, :, None, rank:], theta, 0, rot, pairs)
    kv = mm(rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], eps),
            p["kv_b_proj"]["kernel"]).reshape(n, n_tok, H, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (n, n_tok, H, rot))], axis=-1)
    v = kv[..., nope:]
    if full:
        keep = selection(p["indexer"], x, c_q, cfg, ops)

    def block(args):
        q_b, keep_b = args  # (n, QUERY_BLOCK, H, hd), (n, QUERY_BLOCK, L)
        logits = contract("bnhd,bmhd->bhnm", q_b, k) * hd ** -0.5
        # rows of padding past the last token attend to everything
        keep_b = keep_b | ~keep_b.any(-1, keepdims=True)
        attn = jax.nn.softmax(jnp.where(keep_b[:, None], logits, -jnp.inf), -1)
        return contract("bhnm,bmhd->bnhd", attn, v)

    q_blocks, _ = _query_blocks(q, n_tok)
    keep_blocks, _ = _query_blocks(keep, n_tok)
    out = jax.lax.map(block, (q_blocks, keep_blocks))
    out = jnp.moveaxis(out, 0, 1).reshape(n, -1, H * vd)[:, :n_tok]
    return mm(out, p["o_proj"]["kernel"]), keep


def route(p, y, cfg, ops):
    """(expert ids, weights), each ``(rows, num_experts_per_tok)``."""
    mm, _ = ops
    s = jax.nn.sigmoid(mm(y, p["router"]))
    _, top_e = jax.lax.top_k(s + p["e_score_correction_bias"],
                             cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    return top_e, cfg["routed_scaling_factor"] * top_s


def _f32(p):
    return jax.tree.map(lambda w: w.astype(jnp.float32), p)


@partial(jax.jit, static_argnames=("cfg", "full", "ops"))
def _attend(p, x, keep, *, cfg, full, ops):
    """``x + attn(rms_norm(x))`` and the selection. ``p``: the layer's tree
    without its MLP."""
    cfg, p = json.loads(cfg), _f32(p)
    out, keep = attention(
        p["self_attn"], rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"]),
        keep, cfg, full, ops)
    return x + out, keep


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


def _held(cfg) -> dict:
    """The share under the names ``reference/laguna.py``'s ``experts`` reads."""
    return {"experts_held_from": cfg.get("experts_held_from", 0),
            "num_experts": cfg["n_routed_experts"]}


def sparse_mlp(p, y, cfg, ops=vit.EXACT):
    """``ffn`` of a sparse layer on ``y (rows, hidden)``; ``p`` its tree."""
    small = _f32({k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small, y, cfg, ops)
    return (mlp(small["shared_expert"], y, ops)
            + experts({k: p[k] for k in BANKS}, y, top_e, weight, _held(cfg),
                      ops))


def layer(p, x, keep, cfg, i, ops=vit.EXACT):
    """Layer i of the slice on ``x (n, L, hidden)``; ``p`` its tree; ``keep``
    the selection handed on by the layer before (None for the first).
    Returns ``(x, keep)``."""
    static = json.dumps(cfg, sort_keys=True)
    at = cfg.get("layers_from", 0) + i
    full = cfg["indexer_types"][at] == "full"
    if keep is None:
        if not full:
            raise ValueError(f"layer {at} shares a selection and has none")
        keep = jnp.zeros((), bool)  # not read
    x, keep = _attend({k: v for k, v in p.items() if k != "mlp"}, x, keep,
                      cfg=static, full=full, ops=ops)
    norm = p["post_attention_layernorm"]
    if cfg["mlp_layer_types"][at] == "dense":
        return _ffn_front(norm, p["mlp"], x, cfg=static, dense=True,
                          ops=ops), keep
    rest = {k: v for k, v in p["mlp"].items() if k not in BANKS}
    x, y, top_e, weight = _ffn_front(norm, rest, x, cfg=static, dense=False,
                                     ops=ops)
    banks = {k: p["mlp"][k] for k in BANKS}
    return x + experts(banks, y, top_e, weight, _held(cfg),
                       ops).reshape(x.shape), keep


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``n_routed_experts`` the experts held, plus
    ``n_experts_routed``, ``experts_held_from`` and ``layers_from``."""
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok, keep = _embed(outer, x, t, patch_size=patch_size, ops=ops), None
    for i in range(trunk["num_hidden_layers"]):
        tok, keep = layer(params[f"layers_{i}"], tok, keep, trunk, i, ops)
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
