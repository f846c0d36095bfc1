"""HybridDenoiser — a language model's decoder stack as the x0 denoiser:
``(x_t, t) → x̂0`` with the input and output stage of ``DiffusionViT``
(``vit.embed_tokens`` / ``vit.pixel_head``: the same code, called by both),
the stack's layers and the final RMSNorm between them.

The trunk's sizes are read from ``trunk``, a mapping whose keys are those of
the language model's published ``config.json``, letter for letter, so a
configuration file carries the source's own keys. Its ``model_type`` chooses
the layer stack (:func:`stack_of`): ``jamba`` (the default; this module),
``laguna`` (``models/laguna.py``: window and full attention on shared K/V
heads with rotary positions and per-head gates, a dense MLP or top-k routed
experts of which this chip holds a share) or ``glm_moe_dsa``
(``models/glm.py``: latent attention over a learned per-query selection of
keys that some layers compute and the others borrow, sigmoid-scored
experts) or ``pangu_ultra_moe`` (``models/pangu.py``: the same latent
projections attending to every causal pair, value heads of another size than
the query/key heads, a second norm on every sub-layer's result) or
``nemotron_h`` (``models/nemotron.py``: one sub-layer a layer, its kind read
from a pattern string: Mamba-2 mixers whose matrix state is scanned over
chunks, attention without a position term, ungated experts in a latent of
their own width) or ``kimi_linear`` (``models/kimi.py``: the mixer kind read
from two published lists of layer numbers: delta attention, whose matrix
state decays by a vector and is corrected by the delta rule, or latent
attention with no rotation and no query latent; sigmoid-scored experts) or
``smallthinker`` (``models/smallthinker.py``: a router that reads the layer's
input before attention while its ReLU-gated experts, with no shared one, read
the normed stream after it; rotary window layers between position-free full
ones) or ``longcat_flash`` (``models/longcat.py``: a double layer — two latent
attentions with rank-rescaled latents and two dense MLPs — whose experts read
the stream after the first attention and are added after the second MLP; a
softmax router wider than the experts that have weights, the outputs past
them identities). One wrapper
serves all: what the samplers and the engine read of a
model, ``clone``, the refusals and ``__call__`` below.

The ``jamba`` stack: Mamba-1 state-space layers with a causal grouped-query
attention layer every ``attn_layer_period``, every layer followed by a gated
SiLU MLP, RMSNorm throughout. With x ∈ R^{L×hidden_size}, ε =
``rms_norm_eps``, no bias unless said:

* layer i: ``x += mixer_i(RMSNorm(x))``; ``x += W_down(SiLU(W_gate y) ⊙
  W_up y)``, ``y = RMSNorm(x)``, width ``intermediate_size``; after the last
  layer the final RMSNorm. ``mixer_i`` is attention where ``i %
  attn_layer_period == attn_layer_offset``, else Mamba.
* Mamba-1 mixer (d = ``mamba_expand``·hidden, s = ``mamba_d_state``,
  k = ``mamba_d_conv``, r = ``mamba_dt_rank``): ``[u, z] = x W_in``;
  ``u_t ← SiLU(b_c + Σ_j w_j ⊙ u_{t−k+1+j})`` (depthwise, causal:
  ``causal_conv_silu`` below, which all three state-space stacks call; the
  arithmetic is ``ops/short_conv.py``'s, one launch ``causal_conv`` on the
  TPU);
  ``[δ, B, C] = u W_x`` (r + s + s), each RMSNormed (the ``jamba`` modelling
  code's ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``);
  ``Δ = softplus(δ W_dt + b_dt)``; ``A = −exp(A_log)``; then
  ``ops.selective_scan`` and ``W_out``.
* attention: ``num_attention_heads`` query heads on ``num_key_value_heads``
  shared K/V heads, no position term, scale head_dim^−½, causal mask, softmax
  in float32. Dense XLA attention (two layers of 28 in Jamba2-3B, 0.4 % of
  the forward's FLOPs at 1,025 tokens); moving them onto the masked flash
  forward (``ops.flash_attention.masked_attention``, which the ``laguna``
  stack runs) changes this stack's program and is ROADMAP Reach's.

Every layer keeps its published causality: the scan, the convolution and
the mask run in raster order from the class token.

Parameters are stored in ``param_dtype`` and computed in ``dtype``; handed a
bfloat16 tree with ``dtype=bfloat16`` no program holds a float32 copy of it
(2.87 B parameters at Jamba2-3B's widths: 5.75 GB against 11.5).

What assumes ``Block``'s internals is refused by name (:data:`REFUSED`),
whatever the stack: ``quant``, ``fused``, the step caches, ``scan_blocks``,
``DiffusionViT``'s ``num_experts`` and ``moe_dispatch``, sequence parallelism
(``sp_mode``, ``seq_mesh``), ``use_flash``.
"""

from __future__ import annotations

import importlib
import math
from typing import Any, Mapping, Sequence

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp

from ddim_cold_tpu.models import vit
from ddim_cold_tpu.models.init import torch_default_uniform, trunc_normal
from ddim_cold_tpu.ops.selective_scan import selective_scan
from ddim_cold_tpu.ops.short_conv import causal_conv

Dtype = Any

#: options of ``DiffusionViT``, ``SamplerConfig`` and the yaml that reach into
#: ``Block``, and why this trunk has none of them
REFUSED = {
    "quant": "the int8 codec covers Block's qkv/proj/fc1/fc2 denses",
    "fused": "the fused trunk kernels are Block's attention and Mlp",
    "cache_mode": "the step caches skip and re-run Block ranges by index",
    "scan_blocks": "the layer kind depends on the index: no one scanned body",
    "num_experts": "num_experts and moe_dispatch put SwitchMlp into Block; "
                   "a trunk's experts are its own (trunk: num_experts)",
    "sp_mode": "the scan and the causal masks are sequential in the tokens",
    "use_flash": "a stack picks its attention itself: the jamba stack's two "
                 "layers are dense XLA attention, the laguna, glm_moe_dsa, "
                 "pangu_ultra_moe, nemotron_h, kimi_linear, smallthinker "
                 "and longcat_flash stacks run their flash forwards wherever "
                 "the backend is a TPU",
}
#: further spellings of the above, as the model, the sampler and the yaml have
#: them, each mapped to the option it is refused under
_ALIASES = {"flash_blocks": "use_flash", "moe_dispatch": "num_experts",
            "seq_mesh": "sp_mode",
            "seq_axis": "sp_mode", "sp_degree": "sp_mode",
            "cache_interval": "cache_mode",
            "capture_split": "cache_mode", "skip_blocks": "cache_mode",
            "block_delta": "cache_mode", "capture_tokens": "cache_mode",
            "token_cache": "cache_mode", "token_k": "cache_mode"}


def refuse(option: str) -> ValueError:
    name = _ALIASES.get(option, option)
    return ValueError(
        f"the hybrid trunk has no {name!r} ({option}): {REFUSED[name]}")


def refuse_any(options: Mapping[str, Any]) -> None:
    """Raise for the first of ``options`` (name → value) that is set and
    that :data:`REFUSED` names, under any of its spellings."""
    for option, value in options.items():
        unset = (value is None or value is False or value == "none"
                 or (option == "num_experts" and value == 1)
                 or (option == "moe_dispatch" and value == "einsum"))
        if _ALIASES.get(option, option) in REFUSED and not unset:
            raise refuse(option)


def sampler_config_refusal(config) -> str | None:
    """The option of a ``serve.SamplerConfig`` this trunk refuses, or None."""
    for option in ("quant", "fused"):
        if getattr(config, option):
            return option
    if config.cached:
        return "cache_mode"
    if config.sp_mode != "none" or config.sp_degree != 1:
        return "sp_mode"
    return None


class RMSNorm(nn.Module):
    eps: float
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (xf * scale.astype(jnp.float32)).astype(self.dtype)


def _dt_bias_init(lo: float = 1e-3, hi: float = 1e-1, floor: float = 0.0):
    """Mamba's published initialisation of ``dt_proj.bias``: the inverse
    softplus of a Δ drawn log-uniformly in [lo, hi], no smaller than
    ``floor``."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    states = jnp.arange(1, shape[1] + 1, dtype=jnp.float32)
    return jnp.broadcast_to(jnp.log(states), shape).astype(dtype)


def causal_conv_silu(module: nn.Module, u, taps: int, bias: bool,
                     l2_head_dim: int | None = None):
    """``u_t ← SiLU(b + Σ_j w_j ⊙ u_{t−taps+1+j})`` over ``u (n, L, d)``:
    the depthwise causal convolution of a mixer, in float32, result in the
    module's ``dtype``, with ``l2_head_dim`` each run of that many channels
    L2-normed behind it (``ops/short_conv.py``: one launch on the TPU, the
    written-out taps elsewhere); ``conv1d_kernel (taps, d)`` and, with
    ``bias``, ``conv1d_bias (d,)`` are ``module``'s parameters (called from
    its compact ``__call__``)."""
    d = u.shape[-1]
    w = module.param("conv1d_kernel", torch_default_uniform(taps), (taps, d),
                     module.param_dtype)
    b = module.param("conv1d_bias", nn.initializers.zeros_init(), (d,),
                     module.param_dtype) if bias else None
    return causal_conv(u, w, b, l2_head_dim=l2_head_dim, dtype=module.dtype)


class MambaMixer(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        d = c["mamba_expand"] * c["hidden_size"]
        s, k, r = c["mamba_d_state"], c["mamba_d_conv"], c["mamba_dt_rank"]
        dense = lambda feats, bias, name: nn.Dense(
            feats, use_bias=bias, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        norm = lambda name: RMSNorm(c["rms_norm_eps"], self.dtype,
                                    self.param_dtype, name=name)
        u, z = jnp.split(dense(2 * d, c["mamba_proj_bias"], "in_proj")(x), 2, -1)

        u = causal_conv_silu(self, u, k, c["mamba_conv_bias"])

        delta, B, C = jnp.split(dense(r + 2 * s, False, "x_proj")(u),
                                (r, r + s), -1)
        delta, B, C = (norm("dt_layernorm")(delta), norm("b_layernorm")(B),
                       norm("c_layernorm")(C))
        delta = nn.Dense(d, dtype=self.dtype, param_dtype=self.param_dtype,
                         kernel_init=trunc_normal(std=0.02),
                         bias_init=_dt_bias_init(), name="dt_proj")(delta)
        delta = jax.nn.softplus(delta.astype(jnp.float32)).astype(self.dtype)
        A = -jnp.exp(self.param("A_log", _a_log_init, (d, s),
                                self.param_dtype).astype(jnp.float32))
        D = self.param("D", nn.initializers.ones_init(), (d,), self.param_dtype)
        y = selective_scan(u, delta, A, B, C, D, z)
        return dense(c["hidden_size"], c["mamba_proj_bias"], "out_proj")(y)


class CausalAttention(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        n, L, width = x.shape
        heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
        hd = width // heads
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        # query head h = g·(heads/kv) + r reads K/V head g
        q = dense(heads * hd, "q_proj")(x).reshape(n, L, kv, heads // kv, hd)
        k = dense(kv * hd, "k_proj")(x).reshape(n, L, kv, hd)
        v = dense(kv * hd, "v_proj")(x).reshape(n, L, kv, hd)
        logits = jnp.einsum("bngrd,bmgd->bgrnm", q, k).astype(jnp.float32)
        causal = jnp.tril(jnp.ones((L, L), bool))
        logits = jnp.where(causal, logits * hd ** -0.5, -jnp.inf)
        attn = jax.nn.softmax(logits, axis=-1).astype(self.dtype)
        out = jnp.einsum("bgrnm,bmgd->bngrd", attn, v).reshape(n, L, heads * hd)
        return dense(width, "o_proj")(out)


class GatedMlp(nn.Module):
    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        hidden = (jax.nn.silu(dense(c["intermediate_size"], "gate_proj")(x))
                  * dense(c["intermediate_size"], "up_proj")(x))
        return dense(c["hidden_size"], "down_proj")(hidden)


class SquaredReluMlp(nn.Module):
    """The ungated MLP ``W_down relu(W_up x)²`` (``mlp_hidden_act: relu2``),
    sized as :class:`GatedMlp` is."""

    trunk: Mapping[str, Any]
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.trunk
        dense = lambda feats, name: nn.Dense(
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, kernel_init=trunc_normal(std=0.02),
            name=name)
        hidden = jnp.square(jax.nn.relu(
            dense(c["intermediate_size"], "up_proj")(x)))
        return dense(c["hidden_size"], "down_proj")(hidden)


def is_attention_layer(trunk: Mapping[str, Any], i: int) -> bool:
    return i % trunk["attn_layer_period"] == trunk["attn_layer_offset"]


class HybridLayer(nn.Module):
    trunk: Mapping[str, Any]
    attention: bool
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kw = dict(trunk=self.trunk, dtype=self.dtype,
                  param_dtype=self.param_dtype)
        norm = lambda name: RMSNorm(self.trunk["rms_norm_eps"], self.dtype,
                                    self.param_dtype, name=name)
        # the sub-layer's own norm inside its scope: obs.scopes puts every
        # instruction of a layer in it
        if self.attention:
            with jax.named_scope("trunk/attn"):
                x = x + CausalAttention(**kw, name="self_attn")(
                    norm("input_layernorm")(x))
        else:
            with jax.named_scope("trunk/mamba"):
                x = x + MambaMixer(**kw, name="mamba")(
                    norm("input_layernorm")(x))
        with jax.named_scope("trunk/mlp"):
            return x + GatedMlp(**kw, name="feed_forward")(
                norm("pre_ff_layernorm")(x))


def check_trunk(c: Mapping[str, Any]) -> None:
    """What the ``jamba`` stack cannot run, refused at construction."""
    if c.get("num_experts", 1) != 1:
        raise ValueError(
            f"the jamba stack has no 'num_experts' ({c['num_experts']}): "
            "every MLP of this stack is dense")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {c['hidden_act']!r}: this trunk's "
                         "MLP and mixers are written for 'silu'")
    if c.get("sliding_window") is not None:
        raise ValueError("sliding_window: the attention layers here "
                         "attend to every earlier token")
    if c["hidden_size"] % c["num_attention_heads"] or (
            c["num_attention_heads"] % c["num_key_value_heads"]):
        raise ValueError(
            "hidden_size must divide into num_attention_heads, and those "
            "into num_key_value_heads")


def layer(trunk, i: int, dtype, param_dtype, name: str) -> nn.Module:
    """Layer ``i`` of the ``jamba`` stack."""
    return HybridLayer(trunk, is_attention_layer(trunk, i), dtype, param_dtype,
                       name=name)


def depth_of(trunk: Mapping[str, Any]) -> int:
    """How many layers the stack has here, under the key its ``config.json``
    has: ``num_hidden_layers``, or ``longcat_flash``'s ``num_layers``."""
    return (trunk["num_hidden_layers"] if "num_hidden_layers" in trunk
            else trunk["num_layers"])


def norm_eps(trunk: Mapping[str, Any]) -> float:
    """ε of the stack's RMSNorms under the key its ``config.json`` has:
    ``rms_norm_eps``, or ``nemotron_h``'s ``layer_norm_epsilon``."""
    return (trunk["rms_norm_eps"] if "rms_norm_eps" in trunk
            else trunk["layer_norm_epsilon"])


#: ``model_type`` → the module under ``ddim_cold_tpu.models`` that holds the
#: stack's ``check_trunk`` and ``layer`` (``hybrid``: this one)
STACKS = {"jamba": "hybrid", "laguna": "laguna", "glm_moe_dsa": "glm",
          "pangu_ultra_moe": "pangu", "nemotron_h": "nemotron",
          "kimi_linear": "kimi", "smallthinker": "smallthinker",
          "longcat_flash": "longcat"}


def stack_of(trunk: Mapping[str, Any]) -> tuple:
    """``(check_trunk, layer)`` of ``trunk``'s layer stack, by the published
    ``model_type`` (:data:`STACKS`)."""
    model_type = trunk.get("model_type", "jamba")
    if model_type not in STACKS:
        raise ValueError(f"no layer stack for model_type {model_type!r}: "
                         f"{', '.join(map(repr, STACKS))} are written")
    stack = importlib.import_module(
        "ddim_cold_tpu.models." + STACKS[model_type])
    return stack.check_trunk, stack.layer


def _frozen(trunk: Mapping[str, Any]) -> flax.core.FrozenDict:
    """``trunk`` hashable, as jit's static ``model`` argument has to be: the
    published per-layer lists as tuples, nested groups frozen."""
    def freeze(v):
        if isinstance(v, Mapping):
            return flax.core.FrozenDict({k: freeze(x) for k, x in v.items()})
        return tuple(freeze(x) for x in v) if isinstance(v, (list, tuple)) else v

    return freeze(trunk)


class HybridDenoiser(nn.Module):
    """``(x_t, t) → x̂0``; NHWC in [−1, 1], ``t`` int32 per sample, as
    ``DiffusionViT``. Exposes what the samplers and the engine read of a
    model: ``apply``, ``img_size``, ``in_chans``, ``total_steps``,
    ``num_patches``, ``embed_dim``, ``num_heads``, ``depth``, ``dtype``,
    ``clone``."""

    trunk: Mapping[str, Any]
    img_size: Sequence[int] = (64, 64)
    patch_size: int = 8
    in_chans: int = 3
    total_steps: int = 2000
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def __post_init__(self):
        c = _frozen(self.trunk)
        object.__setattr__(self, "trunk", c)
        check, _ = stack_of(c)
        check(c)
        super().__post_init__()

    @property
    def embed_dim(self) -> int:
        return self.trunk["hidden_size"]

    @property
    def num_heads(self) -> int:
        return self.trunk["num_attention_heads"]

    @property
    def depth(self) -> int:
        return depth_of(self.trunk)

    @property
    def num_patches(self) -> int:
        return ((self.img_size[0] // self.patch_size)
                * (self.img_size[1] // self.patch_size))

    def refuse_sampler_config(self, config) -> None:
        """``serve.Engine`` asks before it queues or compiles ``config``."""
        option = sampler_config_refusal(config)
        if option is not None:
            raise refuse(option)

    def clone(self, **updates):
        refuse_any(updates)
        return super().clone(**updates)

    @nn.compact
    def __call__(self, x: jax.Array, t: jax.Array, deterministic: bool = True,
                 **hooks) -> jax.Array:
        """``deterministic`` is accepted for the callers that pass it: this
        trunk has no dropout. ``hooks``: ``DiffusionViT``'s cache, probe and
        pipeline-stage arguments, none of which exists here."""
        refuse_any(hooks)
        if hooks:
            raise ValueError(f"the hybrid trunk takes no {sorted(hooks)}: "
                             "the probe and the pipeline stages are Block's")
        tokens = vit.embed_tokens(self, x, t, drop_rate=0.0,
                                  deterministic=True,
                                  param_dtype=self.param_dtype)
        _, layer_of = stack_of(self.trunk)
        # a layer that returns a pair hands its second item to the next one
        # (glm_moe_dsa: the key selection a layer without an indexer borrows)
        handed = ()
        for i in range(self.depth):
            out = layer_of(self.trunk, i, self.dtype, self.param_dtype,
                           name=f"layers_{i}")(tokens, *handed)
            tokens, handed = ((out[0], out[1:]) if isinstance(out, tuple)
                              else (out, ()))
        tokens = RMSNorm(norm_eps(self.trunk), self.dtype,
                         self.param_dtype, name="final_layernorm")(tokens)
        return vit.pixel_head(self, tokens, param_dtype=self.param_dtype)
