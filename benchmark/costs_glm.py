"""Operations and bytes a latent-attention, sparse-selection
(``model_type: glm_moe_dsa``) configuration needs, from shapes alone: what
``costs.py`` is for the ViT. A file of its own because a ``model_config`` PR
may edit no benchmark file (PERF.md section 7 names the fold).

Matmul operations only (2 per multiply-add). Attention scores and values are
counted for the SELECTED pairs, whatever a kernel multiplies: one that
computes every causal chunk and masks reads at most selected / causal of its
own efficiency (39.5 % at 9,217 tokens), and a later one that skips reads
better on the same yardstick. The index scores are counted for the causal
pairs (the indexer has to score every visible key to choose among them), and
the experts for the rows routed to the experts held here.
"""

from __future__ import annotations

from benchmark.costs import tokens

_ACT = {"bfloat16": 2, "float32": 4}


def causal_pairs(n_tokens: int) -> int:
    """Pairs (t, s) with s <= t."""
    return n_tokens * (n_tokens + 1) // 2


def selected_pairs(n_tokens: int, top: int) -> int:
    """Sum over queries t of the keys t attends to: min(t + 1, top) (exact
    ties at the threshold, which keep more, are not credited)."""
    w = min(top, n_tokens)
    return w * (w + 1) // 2 + (n_tokens - w) * w


def layer_kinds(config: dict) -> list:
    """(indexer kind, MLP kind) of each layer of the slice."""
    first = config.get("layers_from", 0)
    at = range(first, first + config["num_hidden_layers"])
    return [(config["indexer_types"][i], config["mlp_layer_types"][i])
            for i in at]


def held_share(config: dict) -> float:
    """The share of a row's routed experts that is held here, on average."""
    return (config["n_routed_experts"]
            / config["source_values"]["n_routed_experts"])


def forward_flops(config: dict, every_causal_pair: bool = False) -> float:
    """One image, one forward, on this chip. Per token and layer: the latent
    projections D·r_q + r_q·H·hd + D·(r_kv + rot) + r_kv·H·(nope + v) + H·v·D;
    attention 2·H·hd per pair attended to (selected, or with
    ``every_causal_pair`` what a masked-dense kernel multiplies); a ``full``
    layer's indexer r_q·J·d_I + D·d_I + D·J a token and J·d_I a causal pair; a
    dense MLP 3·D·F; a sparse one the router D·(its width), the shared expert
    and, of the num_experts_per_tok routed experts, the held share on average,
    3·D·F_e each; plus the patch projection in and the head out."""
    n, d, heads = tokens(config), config["hidden_size"], config["num_attention_heads"]
    hd, nope, rot, vd = (config["qk_head_dim"], config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    r_q, r_kv = config["q_lora_rank"], config["kv_lora_rank"]
    j, d_i = config["index_n_heads"], config["index_head_dim"]
    width = config["moe_intermediate_size"]
    c, p = config.get("in_chans", 3), config["patch_size"]
    attended = (causal_pairs(n) if every_causal_pair
                else selected_pairs(n, config["index_topk"]))
    macs = 2.0 * n * p * p * c * d
    for indexer, mlp in layer_kinds(config):
        macs += n * (d * r_q + r_q * heads * hd + d * (r_kv + rot)
                     + r_kv * heads * (nope + vd) + heads * vd * d)
        macs += heads * (hd + vd) * attended
        if indexer == "full":
            macs += n * (r_q * j * d_i + d * d_i + d * j)
            macs += j * d_i * causal_pairs(n)
        if mlp == "dense":
            macs += n * 3 * d * config["intermediate_size"]
        else:
            macs += n * (d * config["source_values"]["n_routed_experts"]
                         + 3 * d * width * config["n_shared_experts"]
                         + config["num_experts_per_tok"] * held_share(config)
                         * 3 * d * width)
    return 2.0 * macs


def flash_selected_fwd_cost(config: dict, images: int) -> dict:
    """One launch of the attention forward over the selection, ``images``
    images of all the heads at the TRUE token count: 2·(hd + v) operations a
    head and SELECTED pair; q, k, v read and the context written once in the
    compute type, and the int8 selection read once."""
    n, heads = tokens(config), config["num_attention_heads"]
    hd, vd = config["qk_head_dim"], config["v_head_dim"]
    pairs = selected_pairs(n, config["index_topk"])
    return {"flops": 2.0 * images * heads * (hd + vd) * pairs,
            "bytes": float(images * (2 * n * heads * (hd + vd)
                                     * _ACT[config["precision"]]
                                     + causal_pairs(n)))}


def dsa_index_cost(config: dict, images: int) -> dict:
    """One launch of the index scores: 2·J·d_I operations a causal pair; the
    index queries, the one index key and the head weights read once, the
    float32 scores of the causal pairs written once."""
    n, j, d_i = tokens(config), config["index_n_heads"], config["index_head_dim"]
    act = _ACT[config["precision"]]
    return {"flops": 2.0 * images * j * d_i * causal_pairs(n),
            "bytes": float(images * (n * (j * d_i + d_i) * act + n * j * 4
                                     + 4 * causal_pairs(n)))}


def dsa_select_cost(config: dict, images: int) -> dict:
    """One launch of the threshold selection: no matmul; the float32 scores of
    the causal pairs read once and their int8 selection written once
    (memory-bound by this count: the 32 passes of compare-and-count run on
    scores resident in VMEM and are the kernel's own business)."""
    return {"flops": 0.0,
            "bytes": float(images * 5 * causal_pairs(tokens(config)))}
