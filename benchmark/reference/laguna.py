"""``(x_t, t) -> x0_hat`` of the sparse-expert denoiser, and its DDIM loop:
float32, matmul precision ``highest``, no kernels. Imports nothing of the
program.

The trunk is the leading layers of Laguna-S-2.1's decoder stack
(``model_type: laguna``,
https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json) between
this system's own input stage (patch projection, class token, learned position
table, time table: ``reference/hybrid.py``'s ``_embed``) and pixel head. Layer
i reads entry i of the published lists ``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``gating_types``. With x in R^{L x
hidden_size}, eps = ``rms_norm_eps``, no bias (``attention_bias`` false):

* layer i: ``x += attn_i(rms_norm(x))``; ``x += ffn_i(rms_norm(x))``; after
  the last layer the final rms_norm.
* ``attn_i``, H = 48 (``full_attention``) or 72 (``sliding_attention``) query
  heads on ``num_key_value_heads`` K/V heads of ``head_dim``: ``q = y W_q``,
  ``k = y W_k``, ``v = y W_v``, ``g = sigmoid(y W_g)`` (L x H). Rotary
  positions p = 0 (class token), 1, ... in raster order on q and k
  (``rotary``): window layers ``rope_type: default``, theta = 10,000, all
  dims; full layers YaRN over the first ``partial_rotary_factor`` of the dims,
  the rest unrotated: ``inv_j = theta^(-2j/rot)``; ``d(beta) = rot * ln(
  original_max_position_embeddings / (beta * 2 pi)) / (2 ln theta)``; ``low =
  max(floor d(beta_fast), 0)``, ``high = min(ceil d(beta_slow), rot - 1)``;
  ``ramp_j = clip((j - low) / (high - low), 0, 1)``; ``inv_j <- inv_j / factor
  * ramp_j + inv_j * (1 - ramp_j)``; cos and sin times ``attention_factor``
  (the arithmetic of ``transformers``' ``_compute_yarn_parameters``, written
  out here). Query head h reads K/V head ``h // (H / num_key_value_heads)``.
  Scores ``q_h k^T * head_dim^-1/2``; mask j <= t (full) or t -
  ``sliding_window`` < j <= t (window); softmax; ``o_h = g_h * sum_j p_hj
  v_j``; out ``= concat_h(o_h) W_o``. Computed one block of queries at a time
  against all the keys with an explicit boolean mask.
* ``ffn_i``, ``mlp_layer_types[i] == "dense"`` (layer 0, ``mlp_only_layers``):
  ``W_down(silu(W_gate y) * W_up y)`` at ``intermediate_size``. ``"sparse"``:
  ``r = softmax(y W_r)`` over all ``num_experts_routed`` router outputs
  (``moe_router_logit_softcapping`` 0: none); ``S`` = the
  ``num_experts_per_tok`` largest (ties to the lower index); ``w_e =
  moe_routed_scaling_factor * r_e / sum_{e' in S} r_e'`` (``norm_topk_prob``),
  on the experts' outputs (``moe_apply_router_weight_on_input`` false); out
  ``= shared(y) + sum_{e in S, e held} w_e E_e(y)``, ``shared`` and every
  ``E_e`` that MLP at ``shared_expert_intermediate_size`` /
  ``moe_intermediate_size``.

Departures from the source, each also in the configuration file:

* **the share**: ``num_experts`` experts from ``experts_held_from`` on are
  held (128 from 0: one of 2 chips that share each layer by its experts); the
  router keeps its published width ``num_experts_routed`` (256). What the
  experts held elsewhere would add is left out, and that partial result goes
  on to the next layer. Nothing stands in for the other chip.
* ``num_hidden_layers`` 5 of 48: the leading dense layer and one period
  (window, window, window, full) of the pattern.
* the token embedding and output head (``vocab_size`` rows) are not held:
  nothing here draws or scores token ids.
* ``assumed``, because ``config.json`` has no key for it: the SiLU-gated MLP
  (the ``qwen*_moe`` family whose key names ``decoder_sparse_step``,
  ``mlp_only_layers``, ``norm_topk_prob``, ``shared_expert_intermediate_size``
  the file uses); router scores by softmax before the top-k (same family); no
  gate on the shared expert; no q/k norm; the per-head gate's form and input
  (headwise sigmoid of a linear map of the layer's normed input, on the
  context before ``W_o``: arXiv:2505.06708); ``rotate_half`` pairing of the
  rotated dims (dim j with dim j + rot/2); this system's image, patch, time
  table and learned position table, added once at the input beside the rotary
  term inside attention.

Every contraction goes through the ``ops`` pair of ``reference/vit.py`` so
that ``lowprec`` can stand in for the control. The parameter tree is the
program's (bfloat16 at the published size). A layer's attention, norms, dense
MLP, router and shared expert are upcast together, one jitted function per
layer kind; its experts a block at a time (``EXPERT_BLOCK``; a layer's 128 are
4.8 GB in float32), each applied only to the rows whose top-k contains it:
the row lists are taken on the host and padded to whole multiples of
``ROW_PAD`` rows, weight 0, so that few shapes compile.
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
from .hybrid import _embed, _head, _update, mlp, rms_norm, silu

#: experts upcast and applied together
EXPERT_BLOCK = 16
#: an expert's row list is padded to a multiple of this
ROW_PAD = 256
#: queries scored against all the keys at a time
QUERY_BLOCK = 256


def rotary(rope: dict, head_dim: int) -> tuple:
    """(inverse frequencies ``(rot / 2,)`` float64, factor on cos and sin)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    factor = float(rope["factor"])
    reach = rope["original_max_position_embeddings"]
    dim_of = lambda beta: (rot * math.log(reach / (beta * 2 * math.pi))
                           / (2 * math.log(theta)))
    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv / factor * ramp + inv * (1.0 - ramp), float(scale)


def rotate(x, inv_freq, scale):
    """``x (n, L, heads, head_dim)``: dim j < rot/2 pairs with dim j + rot/2."""
    half = len(inv_freq)
    angle = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32))[:, None, :]
    cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def attention(p, x, cfg, i, ops):
    mm, contract = ops
    n, n_tok, _ = x.shape
    kind = cfg["layer_types"][i]
    heads, kv = cfg["num_attention_heads_per_layer"][i], cfg["num_key_value_heads"]
    hd, rep = cfg["head_dim"], heads // kv
    rope = rotary(cfg["rope_parameters"][kind], hd)
    q = rotate(mm(x, p["q_proj"]["kernel"]).reshape(n, n_tok, heads, hd), *rope)
    k = rotate(mm(x, p["k_proj"]["kernel"]).reshape(n, n_tok, kv, hd), *rope)
    v = mm(x, p["v_proj"]["kernel"]).reshape(n, n_tok, kv, hd)
    gate = jax.nn.sigmoid(mm(x, p["g_proj"]["kernel"]))  # (n, L, heads)
    window = cfg["sliding_window"] if kind == "sliding_attention" else n_tok
    # query head h = g * rep + r reads K/V head g
    blocks = -(-n_tok // QUERY_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, blocks * QUERY_BLOCK - n_tok), (0, 0), (0, 0)))
    q = q.reshape(n, blocks, QUERY_BLOCK, kv, rep, hd)
    col = jnp.arange(n_tok)

    def block(args):
        q_b, start = args  # (n, QUERY_BLOCK, kv, rep, hd)
        # rows of padding past the last token see what it sees
        row = jnp.minimum(start + jnp.arange(QUERY_BLOCK), n_tok - 1)[:, None]
        sees = (col <= row) & (col > row - window)
        logits = contract("bngrd,bmgd->bgrnm", q_b, k) * hd ** -0.5
        attn = jax.nn.softmax(jnp.where(sees, logits, -jnp.inf), axis=-1)
        return contract("bgrnm,bmgd->bngrd", attn, v)

    out = jax.lax.map(block, (jnp.moveaxis(q, 1, 0),
                              jnp.arange(blocks) * QUERY_BLOCK))
    out = jnp.moveaxis(out, 0, 1).reshape(n, blocks * QUERY_BLOCK, heads, hd)
    out = out[:, :n_tok] * gate[..., None]
    return mm(out.reshape(n, n_tok, heads * hd), p["o_proj"]["kernel"])


def route(router, y, cfg, ops):
    """(expert ids, weights), each ``(rows, num_experts_per_tok)``."""
    mm, _ = ops
    r = jax.nn.softmax(mm(y, router), axis=-1)
    top_r, top_e = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_r = top_r / top_r.sum(-1, keepdims=True)
    return top_e, cfg["moe_routed_scaling_factor"] * top_r


def _static(cfg) -> str:
    """The trunk's mapping, lists and nested groups included, as something a
    jitted function can take as a static argument."""
    return json.dumps(cfg, sort_keys=True)


@partial(jax.jit, static_argnames=("cfg", "i", "ops"))
def _front(p, x, *, cfg, i, ops):
    """Everything of layer i but its experts. ``p``: the layer's tree without
    the expert banks. Dense layer: the layer's output. Sparse layer: ``(x
    after attention + shared(y), y, expert ids, weights)``."""
    cfg = json.loads(cfg)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), p)
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["self_attn"], rms_norm(x, p["input_layernorm"], eps),
                      cfg, i, ops)
    y = rms_norm(x, p["post_attention_layernorm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + mlp(p["mlp"], y, ops)
    y2 = y.reshape(-1, y.shape[-1])
    top_e, weight = route(p["mlp"]["router"], y2, cfg, ops)
    shared = mlp(p["mlp"]["shared_expert"], y, ops)
    return x + shared, y2, top_e, weight


@partial(jax.jit, static_argnames=("count", "ops"))
def _expert_block(banks, y, rows, weights, start, *, count, ops):
    """``count`` experts from ``start`` on, each applied to its own rows.
    ``banks``: the layer's three stacked arrays, whole, in storage precision;
    ``rows``, ``weights``: ``(count, padded rows)``. Returns the weighted
    results added up by row, ``(all rows, hidden)``."""
    _, contract = ops
    take = lambda bank: jax.lax.dynamic_slice_in_dim(
        bank, start, count).astype(jnp.float32)
    x = y[rows]  # (count, padded, hidden)
    hidden = (silu(contract("epd,edf->epf", x, take(banks["gate_proj"])))
              * contract("epd,edf->epf", x, take(banks["up_proj"])))
    out = contract("epf,efd->epd", hidden, take(banks["down_proj"]))
    out = out * weights[..., None]
    return jnp.zeros_like(y).at[rows.reshape(-1)].add(
        out.reshape(-1, y.shape[-1]))


def experts(banks, y, top_e, weight, cfg, ops):
    """``sum_{e in S, e held} w_e E_e(y)`` over the rows of ``y``: a loop over
    the held experts, ``EXPERT_BLOCK`` at a time, each applied to the rows
    routed to it (lists taken on the host)."""
    first, held = cfg.get("experts_held_from", 0), cfg["num_experts"]
    top_e, weight = np.asarray(top_e), np.asarray(weight)
    k = top_e.shape[1]
    order = np.argsort(top_e.reshape(-1), kind="stable")
    bounds = np.searchsorted(top_e.reshape(-1)[order],
                             np.arange(first, first + held + 1))
    total = jnp.zeros_like(y)
    for b0 in range(0, held, EXPERT_BLOCK):
        count = min(EXPERT_BLOCK, held - b0)
        spans = [order[bounds[e]:bounds[e + 1]] for e in range(b0, b0 + count)]
        width = max(ROW_PAD, -(-max(map(len, spans)) // ROW_PAD) * ROW_PAD)
        rows = np.zeros((count, width), np.int32)
        weights = np.zeros((count, width), np.float32)
        for j, span in enumerate(spans):
            rows[j, :len(span)] = span // k
            weights[j, :len(span)] = weight.reshape(-1)[span]
        total = total + _expert_block(banks, y, rows, weights, b0,
                                      count=count, ops=ops)
    return total


BANKS = ("gate_proj", "up_proj", "down_proj")


def sparse_mlp(p, y, cfg, ops=vit.EXACT):
    """``ffn_i`` of a sparse layer on ``y (rows, hidden)``; ``p`` its tree
    (``router``, ``shared_expert``, the three expert banks)."""
    small = jax.tree.map(lambda w: w.astype(jnp.float32),
                         {k: v for k, v in p.items() if k not in BANKS})
    top_e, weight = route(small["router"], y, cfg, ops)
    return (mlp(small["shared_expert"], y, ops)
            + experts({k: p[k] for k in BANKS}, y, top_e, weight, cfg, ops))


def layer(p, x, cfg, i, ops=vit.EXACT):
    """Layer i of the trunk on ``x (n, L, hidden)``; ``p`` its tree."""
    static = _static(cfg)
    if cfg["mlp_layer_types"][i] == "dense":
        return _front(p, x, cfg=static, i=i, ops=ops)
    banks = {k: p["mlp"][k] for k in BANKS}
    rest = dict(p, mlp={k: v for k, v in p["mlp"].items() if k not in BANKS})
    x, y, top_e, weight = _front(rest, x, cfg=static, i=i, ops=ops)
    return x + experts(banks, y, top_e, weight, cfg, ops).reshape(x.shape)


def forward(params, x, t, *, trunk: dict, patch_size: int, ops=vit.EXACT):
    """x0_hat (the sampler clamps), NHWC float32. ``trunk``: the published
    config's keys, ``num_experts`` the experts held, plus
    ``num_experts_routed`` and ``experts_held_from``."""
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
