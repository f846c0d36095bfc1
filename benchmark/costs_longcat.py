"""Operations and bytes a ``model_type: longcat_flash`` configuration needs
(a double layer of two latent attentions and two dense MLPs, the expert layer
a shortcut round the second half, a router over experts with weights and
zero-compute ones), from shapes alone: what ``costs.py`` is for the ViT. A
file of its own because a ``model_config`` PR may edit no benchmark file
(PERF.md section 7 names the fold).

Matmul operations only (2 per multiply-add). Attention is counted for the
causal pairs at the head sizes the model has, ``nope + rot`` dims a score and
``vd`` a value. The experts are counted for the rows routed to the experts
held here; a pick of a zero-compute expert is a row times its weight and
counts nothing. The latent attention's launch is costed by
``costs_pangu.flash_latent_fwd_cost``, which reads the same published keys.
"""

from __future__ import annotations

from benchmark.costs import tokens
from benchmark.costs_glm import causal_pairs

_ACT = {"bfloat16": 2, "float32": 4}


def router_outputs(config: dict) -> int:
    """The router's width: the published experts with weights, then the
    zero-compute ones."""
    return (config["source_values"]["n_routed_experts"]
            + config["zero_expert_num"])


def held_share(config: dict) -> float:
    """The share of a row's picks that go to an expert held here, on average
    under a balanced router: ``n_routed_experts`` of all the router's
    outputs (16 / 768)."""
    return config["n_routed_experts"] / router_outputs(config)


def forward_parts(config: dict) -> dict:
    """One image, one forward, on this chip, by what does the work. Per token
    and published layer: ``projections`` of the TWO attentions, each D·r_q +
    r_q·H·(nope + rot) + D·(r_kv + rot) + r_kv·H·(nope + vd) + H·vd·D;
    ``attention`` H·(nope + rot + vd) a causal pair, twice; ``mlp`` the two
    dense MLPs 3·D·F each; ``router`` D·(its width); ``experts`` of the
    moe_topk picks the held share on average, 3·D·F_e each; ``stage`` the
    patch projection in and the head out."""
    n, d, heads = tokens(config), config["hidden_size"], config["num_attention_heads"]
    nope, rot, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    r_q, r_kv = config["q_lora_rank"], config["kv_lora_rank"]
    c, p, depth = config.get("in_chans", 3), config["patch_size"], config["num_layers"]
    macs = {
        "projections": depth * 2.0 * n * (
            d * r_q + r_q * heads * (nope + rot) + d * (r_kv + rot)
            + r_kv * heads * (nope + vd) + heads * vd * d),
        "attention": depth * 2.0 * heads * (nope + rot + vd) * causal_pairs(n),
        "mlp": depth * 2.0 * n * 3 * d * config["ffn_hidden_size"],
        "router": depth * float(n) * d * router_outputs(config),
        "experts": (depth * n * config["moe_topk"] * held_share(config)
                    * 3.0 * d * config["expert_ffn_hidden_size"]),
        "stage": 2.0 * n * p * p * c * d,
    }
    return {part: 2.0 * m for part, m in macs.items()}


def forward_flops(config: dict) -> float:
    """One image, one forward, on this chip: the sum of
    :func:`forward_parts`."""
    return sum(forward_parts(config).values())


def moe_gmm_cost(config: dict, rows: float, k: int, n: int,
                 products: int = 1) -> dict:
    """One launch of the grouped expert product: ``rows`` rows really routed
    to the experts held, each ``(k,) @ (k, n)``, ``products`` times over (2:
    the gate-up launch, which multiplies the rows it read once by two banks);
    rows read and results written once, and EACH HELD EXPERT'S WEIGHTS read
    once a launch, in the compute type. At ~144 rows an expert the weights
    are most of the bytes and the launch is bound by them (75.5 MB an expert
    for 10.9 GF over its three products: 144 operations a byte)."""
    return {"flops": 2.0 * products * rows * k * n,
            "bytes": float((rows * (k + n) + products
                            * config["n_routed_experts"] * k * n)
                           * _ACT[config["precision"]])}
