"""``(x_t, t) -> x0_hat`` of the double-layer, shortcut-expert, zero-compute-
expert denoiser with rescaled latents, and its DDIM loop: float32, matmul
precision ``highest``, no kernels. Imports nothing of the program.

The trunk is a slice of LongCat-Flash-Omni's decoder stack
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json;
the equations from the row's ``config`` and the family's published modelling
code) between this system's own input stage (patch projection, class token,
learned position table, time table: ``reference/hybrid.py``'s ``_embed``) and
pixel head. With x in R^{L x hidden_size}, N_* rms_norms with their own
gains, eps = ``rms_norm_eps``, no bias anywhere, positions 0 (class token),
1, ... in raster order, every attention causal:

* one layer (``input_layernorm_{0,1}``, ``post_attention_layernorm_{0,1}``,
  ``self_attn_{0,1}``, ``mlps_{0,1}``, ``mlp`` in the tree)::

      h1 = x  + attn_0(N_in0(x))
      y  = N_post0(h1)
      s  = E(y)                      # the shortcut: computed from y, added last
      h2 = h1 + M_0(y)
      h3 = h2 + attn_1(N_in1(h2))
      h4 = h3 + M_1(N_post1(h3))
      out = h4 + s

  ``M_i(y) = W_down(silu(W_gate y) * W_up y)`` at ``ffn_hidden_size``; after
  the last layer the final rms_norm.
* ``attn`` (H = ``num_attention_heads``): ``c_q = rms_norm(y W_qa)``; ``[q_nope_h,
  q_r_h] = (c_q W_qb) * a_q``, ``a_q = sqrt(hidden_size / q_lora_rank)``
  (``mla_scale_q_lora``); ``[c_kv, k_r] = y W_kva``; ``c_kv = rms_norm(c_kv) *
  a_kv``, ``a_kv = sqrt(hidden_size / kv_lora_rank)`` (``mla_scale_kv_lora``);
  ``[k_nope_h, v_h] = c_kv W_kvb``; ``k_r`` is not rescaled. BOTH multiplied
  where the published code multiplies: q behind ``q_b_proj``, the latent
  behind its norm. Rotary (theta = ``rope_theta``, ``inv_j = theta^(-2j /
  qk_rope_head_dim)``, dims 2j and 2j + 1 paired) on every ``q_r_h`` and on the
  one ``k_r``, shared by all the heads. Score of query t, key s <= t, head h:
  ``(q_nope_h . k_nope_h + q_r_h . k_r) * (nope + rot)^-1/2``; softmax over s
  <= t; ``o_h = sum_s p_s v_h,s``; out ``= concat_h(o_h) W_o``. Computed per
  head on the assembled ``k_h = [k_nope_h, k_r]``, one block of queries at a
  time under an explicit boolean mask, as ``reference/pangu.py`` does.
* ``E(y)``: ``r = softmax(y W_r)`` over ALL ``n_experts_routed +
  zero_expert_num`` outputs; S = the ``moe_topk`` largest of ``r + b`` (ties to
  the lower index), b the ``e_score_correction_bias``; ``w_e =
  routed_scaling_factor * r_e``, r WITHOUT the bias and NOT renormalised;
  ``E(y) = sum_{e in S, e < n_experts_routed, e held} w_e E_e(y) + (sum_{e in
  S, e >= n_experts_routed} w_e) * y``; ``E_e`` that MLP at
  ``expert_ffn_hidden_size``; no shared expert.

Departures from the source, each also in the configuration file:

* **column order**: the tree is the program's, whose ``q_b_proj`` holds all
  the heads' nope columns and then all their rotated columns, and whose
  ``kv_b_proj`` all the ``k_nope`` columns and then all the ``v`` columns
  (published: a head's parts side by side): ``reference/pangu.heads_of``.
* **the share**: ``n_routed_experts`` experts from ``experts_held_from`` on
  are held (16 from 0: one of 32 chips that share each layer by its experts);
  the router keeps its published width. What the experts held elsewhere would
  add is left out, and that partial ``s`` goes on. The identity term is kept
  whole: every chip computes it alike for its own tokens.
* ``num_layers`` 4 of 28: published layers 0-3. The vocabulary is not held.
* ``assumed``, because the modelling code decides it and the catalog's
  ``config`` has no key: the softmax score, the bias and no renormalisation;
  ``silu``; the two inner rms_norms; the ``interleave`` pairing; where the two
  rescalings sit; the shortcut's order; this system's image, patch, time
  table and learned position table.

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control. The parameter tree is the
program's (bfloat16 at the published size) and stays on the device beside
this reference, so ONE SUB-LAYER is up-cast at a time, each in a jitted
function of its own: an attention (0.36 GB in float32), a dense MLP (0.9 GB),
the router, and the experts a block at a time as ``reference/laguna.py`` has
them (16 experts: 2.4 GB) — never a layer (5.0 GB).
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import vit
from .ddim import time_sequence
from .glm import _f32, _query_blocks, QUERY_BLOCK, rotary
from .hybrid import _embed, _head, _update, mlp, rms_norm
from .laguna import BANKS, experts
from .pangu import KEY_EXTENTS, heads_of


def multipliers(cfg) -> tuple:
    """``(a_q, a_kv)``: sqrt(hidden_size / rank) where the configuration says
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora``, else 1."""
    return tuple(
        math.sqrt(cfg["hidden_size"] / cfg[rank]) if cfg.get(flag) else 1.0
        for flag, rank in (("mla_scale_q_lora", "q_lora_rank"),
                           ("mla_scale_kv_lora", "kv_lora_rank")))


def attention(p, x, cfg, ops):
    """``attn`` of the sub-layer's normed input ``x``: every head's key
    assembled as ``k_h = [k_nope_h, k_r]``, queries in blocks of
    ``QUERY_BLOCK`` rows, the blocks in ``KEY_EXTENTS`` runs, each run against
    the keys up to its last query under the explicit mask s <= t."""
    mm, contract = ops
    n, n_tok, _ = x.shape
    H, nope, rot = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                    cfg["qk_rope_head_dim"])
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    a_q, a_kv = multipliers(cfg)
    c_q = rms_norm(mm(x, p["q_a_proj"]["kernel"]), p["q_a_layernorm"], eps)
    q_nope, q_r = heads_of(mm(c_q, p["q_b_proj"]["kernel"]) * a_q, H, nope)
    q = jnp.concatenate([q_nope, rotary(q_r, theta, 0, rot, True)], axis=-1)
    kv_a = mm(x, p["kv_a_proj_with_mqa"]["kernel"])
    k_r = rotary(kv_a[:, :, None, rank:], theta, 0, rot, True)
    c_kv = rms_norm(kv_a[..., :rank], p["kv_a_layernorm"], eps) * a_kv
    k_nope, v = heads_of(mm(c_kv, p["kv_b_proj"]["kernel"]), H, nope)
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
    """``(expert ids, weights)``, each ``(rows, moe_topk)``, over all the
    router's outputs: ids from ``n_experts_routed`` on are identities. ``p``:
    ``router`` and ``e_score_correction_bias``."""
    mm, _ = ops
    r = jax.nn.softmax(mm(y, p["router"]), axis=-1)
    _, top_e = jax.lax.top_k(r + p["e_score_correction_bias"], cfg["moe_topk"])
    return top_e, cfg["routed_scaling_factor"] * jnp.take_along_axis(
        r, top_e, axis=-1)


def passed(top_e, weight, cfg):
    """``sum_{e in S, e >= n_experts_routed} w_e`` of each row: what its
    identity picks weigh."""
    return jnp.where(top_e >= cfg["n_experts_routed"], weight, 0.0).sum(
        -1, keepdims=True)


@partial(jax.jit, static_argnames=("cfg", "ops"))
def _attend(norm, p, x, *, cfg, ops):
    """``x + attn(N(x))``; ``p`` one attention's tree, ``norm`` its pre-norm."""
    cfg, p, norm = json.loads(cfg), _f32(p), _f32(norm)
    return x + attention(p, rms_norm(x, norm, cfg["rms_norm_eps"]), cfg, ops)


@partial(jax.jit, static_argnames=("cfg", "ops"))
def _route(norm, p, x, *, cfg, ops):
    """``(y, expert ids, weights)`` of ``y = N(x)`` in rows; ``p`` the expert
    layer's tree without its banks."""
    cfg, p, norm = json.loads(cfg), _f32(p), _f32(norm)
    y = rms_norm(x, norm, cfg["rms_norm_eps"])
    y = y.reshape(-1, y.shape[-1])
    return (y,) + route(p, y, cfg, ops)


@partial(jax.jit, static_argnames=("eps", "ops"))
def _dense(norm, p, x, *, eps, ops):
    """``x + M(N(x))``; ``p`` one dense MLP's tree, ``norm`` its pre-norm."""
    p, norm = _f32(p), _f32(norm)
    return x + mlp(p, rms_norm(x, norm, eps), ops)


def _held(cfg) -> dict:
    """The share under the names ``reference/laguna.py``'s ``experts`` reads."""
    return {"experts_held_from": cfg.get("experts_held_from", 0),
            "num_experts": cfg["n_routed_experts"]}


def sparse_mlp(p, y, cfg, ops=vit.EXACT):
    """``E`` on ``y (rows, hidden)``; ``p`` the expert layer's tree."""
    small = _f32({k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small, y, cfg, ops)
    return (experts({k: p[k] for k in BANKS}, y, top_e, weight, _held(cfg), ops)
            + passed(top_e, weight, cfg) * y)


def layer(p, x, cfg, ops=vit.EXACT):
    """One layer of the slice on ``x (n, L, hidden)``; ``p`` its tree."""
    static = json.dumps(cfg, sort_keys=True)
    eps = cfg["rms_norm_eps"]
    h1 = _attend(p["input_layernorm_0"], p["self_attn_0"], x, cfg=static,
                 ops=ops)
    rest = {k: v for k, v in p["mlp"].items() if k not in BANKS}
    y, top_e, weight = _route(p["post_attention_layernorm_0"], rest, h1,
                              cfg=static, ops=ops)
    s = (experts({k: p["mlp"][k] for k in BANKS}, y, top_e, weight, _held(cfg),
                 ops) + passed(top_e, weight, cfg) * y).reshape(x.shape)
    h2 = _dense(p["post_attention_layernorm_0"], p["mlps_0"], h1, eps=eps,
                ops=ops)
    h3 = _attend(p["input_layernorm_1"], p["self_attn_1"], h2, cfg=static,
                 ops=ops)
    h4 = _dense(p["post_attention_layernorm_1"], p["mlps_1"], h3, eps=eps,
                ops=ops)
    return h4 + s


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``n_routed_experts`` the experts held, plus
    ``n_experts_routed``, ``experts_held_from`` and ``layers_from``."""
    outer = {k: v for k, v in params.items() if not k.startswith("layers_")}
    tok = _embed(outer, x, t, patch_size=patch_size, ops=ops)
    for i in range(trunk["num_layers"]):
        tok = layer(params[f"layers_{i}"], tok, trunk, ops)
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
