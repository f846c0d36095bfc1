"""``(x_t, t) -> x0_hat`` of the denoiser whose router reads the layer's input
before attention, and its DDIM loop: float32, matmul precision ``highest``, no
kernels. Imports nothing of the program.

The trunk is the leading layers of SmallThinker-21BA3B-Instruct's decoder
stack
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
between this system's own input stage (patch projection, class token, learned
position table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel
head. Layer i reads entry i of the published lists ``rope_layout`` and
``sliding_window_layout``. With x in R^{L x hidden_size} the layer's input,
eps = ``rms_norm_eps``, no bias anywhere, positions 0 (class token), 1, ... in
raster order:

* ``l = x W_r``: the router's logits, of x ITSELF, before the norm and before
  attention (``route``).
* ``x' = x + attn_i(rms_norm_in(x))``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``; ``rope_layout[i] == 1``:
  q and k turned by the default rotary (``inv_j = rope_theta^(-2j/head_dim)``,
  every dim, dim j paired with dim j + head_dim/2: ``reference/laguna.py``'s
  ``rotate``); ``0``: q and k as projected, no position term. Query head h
  reads K/V head ``h // (heads / kv)``; scores ``q_h k^T * head_dim^-1/2``;
  mask j <= t (``sliding_window_layout[i] == 0``) or t -
  ``sliding_window_size`` < j <= t (``1``); softmax; out ``= concat_h(o_h)
  W_o``. Computed one block of ``QUERY_BLOCK`` queries at a time, the blocks
  in ``KEY_EXTENTS`` runs, each run against the keys from the first its first
  query may see to its last query, under the explicit boolean mask.
* ``z = rms_norm_post(x')``; ``p = softmax(l)`` over all
  ``moe_num_primary_experts_routed`` outputs; ``S`` = the
  ``moe_num_active_primary_experts`` largest (ties to the lower index);
  ``w_e = p_e / sum_{e' in S} p_e'`` (``norm_topk_prob``); out ``= x' +
  sum_{e in S, e held} w_e W_down,e(relu(W_gate,e z) * W_up,e z)``. The
  softmax over all the outputs renormalised over S equals the softmax over
  S's logits, so either order of the source's two steps gives these weights.

Departures from the source, each also in the configuration file:

* **the share**: ``moe_num_primary_experts`` experts from
  ``experts_held_from`` on are held (in the benchmark's configuration all 64
  of 64: nothing is left out); the router keeps its published width
  ``moe_num_primary_experts_routed``. What experts held elsewhere would add
  is left out, and that partial result goes on to the next layer.
* ``num_hidden_layers`` 8 of 52: two whole periods (full, window, window,
  window). The token embedding and output head are not held.
* ``assumed``: where the router reads, the ReLU gate, no shared or secondary
  expert, no q/k norm, the pairing of the rotated dims; the configuration
  file gives the ground of each.

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control. The parameter tree is the
program's (bfloat16 at the published size). A layer's router, attention and
norms are upcast together, one jitted function a layer kind; its experts in a
plain loop over the experts held, ``EXPERT_BLOCK`` upcast at a time (a
layer's 64 are 1.5 GB in float32), each applied to the rows whose top-k
contains it: the row lists are taken on the host, expert by expert, and
padded to whole multiples of ``ROW_PAD`` rows, weight 0, so that few shapes
compile. No sort, no grouped product.
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
from .glm import QUERY_BLOCK, _f32, _query_blocks
from .hybrid import _embed, _head, _update, rms_norm
from .laguna import BANKS, rotary, rotate
from .pangu import KEY_EXTENTS

#: the two published lists that layer i reads entry i of
PER_LAYER = ("rope_layout", "sliding_window_layout")
#: experts upcast and applied together
EXPERT_BLOCK = 16
#: an expert's row list is padded to a multiple of this
ROW_PAD = 256


def attention(p, y, cfg, rotated, windowed, ops):
    """``attn_i`` of the layer's normed input ``y``: ``rotated``,
    ``windowed`` entry i of the two published lists."""
    mm, contract = ops
    n, n_tok, _ = y.shape
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    q = mm(y, p["q_proj"]["kernel"]).reshape(n, n_tok, heads, hd)
    k = mm(y, p["k_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    v = mm(y, p["v_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    if rotated:
        rope = rotary({"rope_theta": cfg["rope_theta"]}, hd)
        q, k = rotate(q, *rope), rotate(k, *rope)
    window = cfg["sliding_window_size"] if windowed else n_tok
    # query head h = g * (heads / kv) + r reads K/V head g
    q_blocks, starts = _query_blocks(
        q.reshape(n, n_tok, kv, heads // kv, hd), n_tok)
    runs = []
    per = -(-len(starts) // KEY_EXTENTS)
    for lo in range(0, len(starts), per):
        hi = min(lo + per, len(starts))
        first = max(lo * QUERY_BLOCK - window + 1, 0)
        last = min(hi * QUERY_BLOCK, n_tok)
        k_run, v_run = k[:, first:last], v[:, first:last]
        col = jnp.arange(first, last)

        def block(args, k_run=k_run, v_run=v_run, col=col):
            q_b, start = args  # (n, QUERY_BLOCK, kv, heads / kv, hd)
            # rows of padding past the last token see what it sees
            row = jnp.minimum(start + jnp.arange(QUERY_BLOCK),
                              n_tok - 1)[:, None]
            sees = (col <= row) & (col > row - window)
            logits = contract("bngrd,bmgd->bgrnm", q_b, k_run) * hd ** -0.5
            attn = jax.nn.softmax(jnp.where(sees, logits, -jnp.inf), axis=-1)
            return contract("bgrnm,bmgd->bngrd", attn, v_run)

        runs.append(jax.lax.map(block, (q_blocks[lo:hi], starts[lo:hi])))
    out = jnp.moveaxis(jnp.concatenate(runs, 0), 0, 1)
    return mm(out.reshape(n, -1, heads * hd)[:, :n_tok], p["o_proj"]["kernel"])


def route(router, x, cfg, ops):
    """(expert ids, weights), each ``(rows, moe_num_active_primary_experts)``,
    from ``x (rows, hidden)``: what the router is GIVEN to read."""
    mm, _ = ops
    p = jax.nn.softmax(mm(x, router), axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_e, top_p


@partial(jax.jit, static_argnames=("cfg", "rotated", "windowed", "ops"))
def _front(p, x, *, cfg, rotated, windowed, ops):
    """Everything of a layer but its experts, in the order of the equations:
    the routing from the layer's input, then attention. ``p``: the layer's
    tree without the expert banks; ``cfg``: the sizes a layer reads, without
    the two per-layer lists, whose entries for this layer are ``rotated`` and
    ``windowed`` (one compiled function a layer KIND). Returns ``(x', z as
    rows, expert ids, weights)``."""
    cfg, p = json.loads(cfg), _f32(p)
    eps = cfg["rms_norm_eps"]
    top_e, weight = route(p["mlp"]["router"], x.reshape(-1, x.shape[-1]), cfg,
                          ops)
    x = x + attention(p["self_attn"], rms_norm(x, p["input_layernorm"], eps),
                      cfg, rotated, windowed, ops)
    z = rms_norm(x, p["post_attention_layernorm"], eps)
    return x, z.reshape(-1, z.shape[-1]), top_e, weight


@partial(jax.jit, static_argnames=("count", "ops"))
def _expert_block(banks, z, rows, weights, start, *, count, ops):
    """``count`` experts from ``start`` on, each applied to its own rows.
    ``banks``: the layer's three stacked arrays, whole, in storage precision;
    ``rows``, ``weights``: ``(count, padded rows)``. Returns the weighted
    results added up by row, ``(all rows, hidden)``."""
    _, contract = ops
    take = lambda bank: jax.lax.dynamic_slice_in_dim(
        bank, start, count).astype(jnp.float32)
    x = z[rows]  # (count, padded, hidden)
    hidden = (jnp.maximum(contract("epd,edf->epf", x, take(banks["gate_proj"])),
                          0.0)
              * contract("epd,edf->epf", x, take(banks["up_proj"])))
    out = contract("epf,efd->epd", hidden, take(banks["down_proj"]))
    out = out * weights[..., None]
    return jnp.zeros_like(z).at[rows.reshape(-1)].add(
        out.reshape(-1, z.shape[-1]))


def experts(banks, z, top_e, weight, cfg, ops):
    """``sum_{e in S, e held} w_e E_e(z)`` over the rows of ``z``: a loop over
    the experts held, each applied to the rows whose top-k names it (lists
    taken on the host, expert by expert), ``EXPERT_BLOCK`` a call."""
    first, held = cfg.get("experts_held_from", 0), cfg["moe_num_primary_experts"]
    top_e, weight = np.asarray(top_e), np.asarray(weight)
    total = jnp.zeros_like(z)
    for b0 in range(0, held, EXPERT_BLOCK):
        count = min(EXPERT_BLOCK, held - b0)
        named = [top_e == first + b0 + j for j in range(count)]  # (rows, k)
        lists = [np.flatnonzero(hit.any(-1)) for hit in named]
        width = max(ROW_PAD, -(-max(map(len, lists)) // ROW_PAD) * ROW_PAD)
        rows = np.zeros((count, width), np.int32)
        weights = np.zeros((count, width), np.float32)
        for j, (hit, its) in enumerate(zip(named, lists)):
            rows[j, :len(its)] = its
            weights[j, :len(its)] = (weight * hit).sum(-1)[its]
        total = total + _expert_block(banks, z, rows, weights, b0,
                                      count=count, ops=ops)
    return total


def layer(p, x, cfg, i, ops=vit.EXACT):
    """Layer i of the trunk on ``x (n, L, hidden)``; ``p`` its tree."""
    banks = {k: p["mlp"][k] for k in BANKS}
    rest = dict(p, mlp={k: v for k, v in p["mlp"].items() if k not in BANKS})
    sizes = {k: v for k, v in cfg.items() if k not in PER_LAYER}
    x, z, top_e, weight = _front(
        rest, x, cfg=json.dumps(sizes, sort_keys=True),
        rotated=bool(cfg["rope_layout"][i]),
        windowed=bool(cfg["sliding_window_layout"][i]), ops=ops)
    return x + experts(banks, z, top_e, weight, cfg, ops).reshape(x.shape)


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``moe_num_primary_experts`` the experts held, plus
    ``moe_num_primary_experts_routed`` and ``experts_held_from``."""
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
