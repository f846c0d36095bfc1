"""DiffusionViT — the x0-predicting Vision Transformer backbone, TPU-first.

Re-implements the reference's ``DiffusionVisionTransformer`` (ViT.py:158-218;
the trainer imports the identical copy in ViT_draft2drawing.py:175-238 — the
build keeps ONE module, SURVEY.md quirk #6) as a Flax linen module:

* NHWC image layout (TPU-native; the torch reference is NCHW — the checkpoint
  converter in utils/checkpoint.py handles the transpose).
* Patch embedding as reshape + Dense instead of Conv2d: for kernel=stride=p
  the two are identical linear maps, and the reshape+matmul form feeds the MXU
  one large GEMM with no im2col.
* Attention as einsum with float32 softmax; mlp_ratio defaults to 1.0 and
  qkv_bias to True per the reference ctor defaults (ViT.py:160-162).
* Time conditioning: a learned ``Embed(total_steps, dim)`` row added to every
  token together with the learned positional embedding (ViT.py:204-205).
* Output head predicts the clean image x̂0 directly: Linear(dim → C·p²) then
  un-patchify with the exact pixel mapping of the reference's
  ``view/permute(0,5,1,3,2,4)/view`` (ViT.py:214-217).
* Stochastic depth linearly scaled 0 → drop_path_rate across blocks
  (ViT.py:176), active only in training; dropout 0.1 on pos/attn/proj/mlp.

Compute dtype is configurable (bfloat16 replaces the reference's CUDA AMP);
parameters always live in float32.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ddim_cold_tpu.models.init import torch_default_uniform, trunc_normal
from ddim_cold_tpu.obs import metrics
from ddim_cold_tpu.ops.quant import gelu_exact

Dtype = Any

#: which path each trace of a :class:`Block` took for its token-wise half
#: (``kernels.block_tokenwise``, keyed ``kernel`` / ``xla``)
_kernels = metrics.scope("kernels")

#: Model configurations appearing in the reference (SURVEY.md §2 table).
MODEL_CONFIGS = {
    # reference ViT.py:277
    "oxford_flower_64": dict(
        img_size=(64, 64), patch_size=4, embed_dim=256, depth=6, num_heads=4
    ),
    # reference ViT.py:274 / 20220822.yaml:12-15 / ViT_draft2drawing.py:342
    "vit_tiny": dict(
        img_size=(64, 64), patch_size=8, embed_dim=384, depth=7, num_heads=12
    ),
    # checkpoint name only (README.md:28-29); config absent upstream — both
    # plausible patch sizes are provided, selectable by state-dict shapes.
    "oxford_flower_200_p4": dict(
        img_size=(200, 200), patch_size=4, embed_dim=256, depth=6, num_heads=4
    ),
    "oxford_flower_200_p8": dict(
        img_size=(200, 200), patch_size=8, embed_dim=384, depth=7, num_heads=12
    ),
}


def positionalencoding1d(d_model: int, length: int) -> np.ndarray:
    """Sinusoidal 1-D positional encoding (reference ViT_draft2drawing.py:140-156).

    Kept as an option for large-image configs (>64px), where the reference
    sketches swapping the learned pos_embed for this fixed table
    (ViT_draft2drawing.py:191-193).
    """
    if d_model % 2 != 0:
        raise ValueError(f"Cannot use sin/cos positional encoding with odd dim {d_model}")
    pe = np.zeros((length, d_model), dtype=np.float32)
    position = np.arange(0, length, dtype=np.float32)[:, None]
    div_term = np.exp(np.arange(0, d_model, 2, dtype=np.float32) * -(math.log(10000.0) / d_model))
    pe[:, 0::2] = np.sin(position * div_term)
    pe[:, 1::2] = np.cos(position * div_term)
    return pe


class _DenseParams(nn.Module):
    """Declares an ``nn.Dense``'s ``{kernel, bias}`` leaves — same names,
    shapes, dtypes and initializers as Mlp's denses, at the same module path
    when given the same ``name`` — WITHOUT computing the matmul. The float
    fused-Mlp path consumes the raw leaves (ops/quant.mlp_pallas), and the
    identical param structure keeps a fused and an unfused model
    interchangeable on one param tree."""

    features: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, in_features: int):
        kernel = self.param("kernel", trunc_normal(std=0.02),
                            (in_features, self.features))
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           (self.features,)) if self.use_bias else None)
        return kernel, bias


class _NormParams(nn.Module):
    """Declares an ``nn.LayerNorm``'s ``{scale, bias}`` leaves, as
    :class:`_DenseParams` does a Dense's."""

    @nn.compact
    def __call__(self, features: int):
        return (self.param("scale", nn.initializers.ones_init(), (features,)),
                self.param("bias", nn.initializers.zeros_init(), (features,)))


class _AttnParams(nn.Module):
    """Declares :class:`Attention`'s two Denses under this module's name."""

    dim: int
    qkv_bias: bool

    @nn.compact
    def __call__(self):
        return (_DenseParams(3 * self.dim, use_bias=self.qkv_bias,
                             name="qkv")(self.dim),
                _DenseParams(self.dim, name="proj")(self.dim))


class _MlpParams(nn.Module):
    """Declares :class:`Mlp`'s two Denses under this module's name."""

    hidden: int
    dim: int

    @nn.compact
    def __call__(self):
        return (_DenseParams(self.hidden, name="fc1")(self.dim),
                _DenseParams(self.dim, name="fc2")(self.hidden))


class Mlp(nn.Module):
    """2-layer GELU MLP with dropout after both linears (reference ViT.py:74-90)."""

    hidden_features: int
    out_features: int
    drop: float = 0.0
    dtype: Dtype = jnp.float32
    quant: Optional[str] = None  # None | "xla" | "pallas" | "w8a8" (ops/quant.py)
    fused: bool = False  # whole fc1 → GELU → fc2 chain as ONE Pallas kernel
    # (ops/quant.mlp_pallas) — the (M, hidden) activation never exists in HBM

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        # fused trunk Mlp: inference path — the inter-linear dropouts must be
        # inactive (training with drop > 0 falls through to the unfused
        # composition, which applies them), and quant="xla" explicitly opts
        # out of Pallas kernels. The param holders declare the exact leaves
        # the unfused denses would, so both paths share one tree.
        if (self.fused and self.quant != "xla"
                and (deterministic or self.drop == 0.0)):
            from ddim_cold_tpu.ops import tuning
            from ddim_cold_tpu.ops.quant import QuantParams, mlp_pallas

            x = x.astype(self.dtype)
            in_features = x.shape[-1]
            if self.quant:
                w1, s1, b1 = QuantParams(
                    self.hidden_features, name="fc1")(in_features)
                w2, s2, b2 = QuantParams(
                    self.out_features, name="fc2")(self.hidden_features)
                mode = self.quant  # "pallas" (w8a16) | "w8a8"
                act_dt = jnp.int8 if self.quant == "w8a8" else x.dtype
            else:
                w1, b1 = _DenseParams(
                    self.hidden_features, name="fc1")(in_features)
                w2, b2 = _DenseParams(
                    self.out_features, name="fc2")(self.hidden_features)
                s1 = s2 = None
                mode = None
                act_dt = x.dtype
            bm = tuning.mlp_block_m(in_features, self.hidden_features, act_dt,
                                    quant=self.quant is not None)
            return mlp_pallas(x, w1, b1, w2, b2, scale1=s1, scale2=s2,
                              mode=mode, block_m=bm)

        if self.quant:
            from ddim_cold_tpu.ops.quant import QuantDense

            dense = lambda feat, name: QuantDense(
                feat, dtype=self.dtype, mode=self.quant, name=name)
        else:
            dense = lambda feat, name: nn.Dense(
                feat,
                dtype=self.dtype,
                kernel_init=trunc_normal(std=0.02),
                bias_init=nn.initializers.zeros_init(),
                name=name,
            )
        x = dense(self.hidden_features, "fc1")(x)
        x = gelu_exact(x)
        x = nn.Dropout(self.drop, deterministic=deterministic)(x)
        x = dense(self.out_features, "fc2")(x)
        x = nn.Dropout(self.drop, deterministic=deterministic)(x)
        return x


class Attention(nn.Module):
    """Multi-head self-attention, fused-QKV (reference ViT.py:93-117).

    Returns ``(x, attn)`` like the reference so the attention-probe path
    (Block.return_attention) stays expressible — EXCEPT when the Pallas
    fused kernel runs (``use_flash`` on, ``need_weights=False``), which
    never materializes the weights and returns ``(x, None)``; active
    attention-dropout is an error there, not a switch back to the einsum.
    Callers that need the weights must pass
    ``need_weights=True`` (Block does this for its probe path). Softmax runs
    in float32 regardless of compute dtype; the einsum layout keeps the two
    contractions as plain batched GEMMs for the MXU.
    """

    dim: int
    num_heads: int = 8
    qkv_bias: bool = False
    qk_scale: Optional[float] = None
    attn_drop: float = 0.0
    proj_drop: float = 0.0
    dtype: Dtype = jnp.float32
    # False = dense einsum; True = Pallas fused kernel; "xla" = pure-XLA
    # blockwise online-softmax (no kernel to reject, bounded memory)
    use_flash: "bool | str" = False
    # Pallas kernel block sizes (block_q, block_kv); None = the kernel
    # chooses from the shape (a head's K and V as one VMEM-resident chunk
    # where that fits, else streamed: ops/flash_attention._fwd_blocks). An
    # override for experiments — block_kv >= N asks for the resident form.
    # Applies to the plain flash path and ulysses' local flash attention;
    # ring sp has its own per-device chunking and ignores it.
    flash_blocks: Optional[tuple] = None
    # sequence parallelism: rotate K/V blocks around `seq_axis` of `seq_mesh`
    # (parallel/ring_attention.py); `batch_axis` keeps dp sharding composed,
    # `head_axis` keeps tensor-parallel head sharding effective inside the ring.
    # `sp_mode` picks the strategy: "ring" (ppermute K/V rotation) or
    # "ulysses" (all-to-all head↔seq reshard, parallel/ulysses.py).
    seq_mesh: Optional[Mesh] = None
    seq_axis: Optional[str] = None
    batch_axis: Optional[str] = None
    head_axis: Optional[str] = None
    sp_mode: str = "ring"
    # manual-collective mode (pipe×sp composition): the module is ALREADY
    # inside a shard_map whose manual axes include ``seq_axis`` (the
    # pipeline executor, parallel/pipeline.py) — call the inner sp kernel
    # (``sp_mode``: ring rotation or the ulysses all-to-all pair) directly
    # on the local shard instead of wrapping a new shard_map.
    # ``seq_valid_len`` is the unpadded global sequence length (ring masks
    # the padding via kv_valid; ulysses slices it off between its two
    # all-to-alls); ``seq_varying_axes`` names every manual axis the
    # activations vary over, for the ring accumulators' vma typing
    # (ulysses needs none — its body is stateless).
    seq_manual: bool = False
    seq_valid_len: Optional[int] = None
    seq_varying_axes: Optional[tuple] = None
    quant: Optional[str] = None  # w8a16 qkv/proj kernels (ops/quant.py)
    fused: bool = False  # qkv dequant-GEMM → flash → proj dequant-GEMM as
    # ONE Pallas kernel (ops/flash_attention.fused_trunk_attention); needs
    # quant in ("pallas", "w8a8") — the dequant producer IS the fusion

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 need_weights: bool = True):
        B, N, C = x.shape
        head_dim = C // self.num_heads
        scale = self.qk_scale or head_dim**-0.5

        # Flash/ring/fused paths never materialize the O(N²) weights, so they
        # require inactive attention-dropout (else fall back to einsum) and
        # no weight probing.
        weightless_ok = not need_weights and (deterministic or self.attn_drop == 0.0)
        seq_parallel = self.seq_mesh is not None and self.seq_axis is not None

        # fused sampler trunk: the qkv dequant-matmul runs INSIDE the flash
        # kernel as producer and the proj dequant-matmul consumes the
        # attention output in place — the (B, N, 3C) qkv and (B, N, C)
        # context activations never round-trip through HBM. Inference only
        # (no VJP); the probe path (need_weights=True) and sp fall through
        # to the unfused composition below, whose QuantDense declares the
        # identical param leaves — one tree serves both.
        if (self.fused and self.quant in ("pallas", "w8a8")
                and not seq_parallel and not self.seq_manual
                and weightless_ok):
            from ddim_cold_tpu.ops import tuning
            from ddim_cold_tpu.ops.flash_attention import fused_trunk_attention
            from ddim_cold_tpu.ops.quant import QuantParams

            w_qkv, s_qkv, b_qkv = QuantParams(
                3 * self.dim, use_bias=self.qkv_bias, name="qkv")(C)
            w_proj, s_proj, b_proj = QuantParams(
                self.dim, use_bias=True, name="proj")(C)
            # explicit flash_blocks win (they also pin the unfused path's kv
            # chunking — SAME block_kv is what makes fused≡unfused bitwise);
            # otherwise the committed autotune table for this geometry
            act_dt = jnp.int8 if self.quant == "w8a8" else self.dtype
            blocks = self.flash_blocks or tuning.attn_blocks(
                N, C, self.num_heads, act_dt)
            out = fused_trunk_attention(
                x.astype(self.dtype), w_qkv, s_qkv, b_qkv,
                w_proj, s_proj, b_proj,
                num_heads=self.num_heads, scale=scale,
                block_q=blocks[0], block_kv=blocks[1],
                mode="w8a8" if self.quant == "w8a8" else "pallas")
            out = nn.Dropout(self.proj_drop, deterministic=deterministic)(out)
            return out, None

        if self.quant:
            from ddim_cold_tpu.ops.quant import QuantDense

            dense = lambda feat, use_bias, name: QuantDense(
                feat, use_bias=use_bias, dtype=self.dtype, mode=self.quant,
                name=name)
        else:
            dense = lambda feat, use_bias, name: nn.Dense(
                feat,
                use_bias=use_bias,
                dtype=self.dtype,
                kernel_init=trunc_normal(std=0.02),
                bias_init=nn.initializers.zeros_init(),
                name=name,
            )
        packed = dense(3 * self.dim, self.qkv_bias, "qkv")(x)  # (B, N, 3C)
        # unpack order (3, heads, head_dim) matches the torch reshape
        # (B,N,3,H,hd) so converted checkpoints line up slice-for-slice. The
        # flash kernel relies on the same order: it reads q, k, v out of
        # ``packed`` where the GEMM wrote them, at column offsets 0, C and 2C
        # with head h of each at columns [h·hd, (h+1)·hd)
        # (ops/flash_attention.flash_attention_qkv); the slices below are
        # for the paths that want q, k, v apart.
        qkv = packed.reshape(B, N, 3, self.num_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B, N, H, hd)

        if seq_parallel and not need_weights and not weightless_ok:
            # falling back to dense here would silently materialize the full
            # O(N²) global attention matrix — the exact thing sp exists to
            # avoid. Configs must zero attn_drop (trainer.build_model does).
            # (need_weights=True — the probe path — deliberately still falls
            # through to the dense global einsum.)
            raise ValueError(
                "sequence-parallel attention cannot apply attention-dropout "
                f"(attn_drop={self.attn_drop} active in training); set "
                "attn_drop_rate=0.0 on the model")
        if self.use_flash and not need_weights and not weightless_ok:
            # same rule without sp: a model built for the kernel must not
            # train on the dense einsum with nobody told (at N=2501 that is
            # the O(N²) matrix per layer the kernel exists to avoid)
            raise ValueError(
                f"use_flash={self.use_flash!r} cannot apply attention-dropout "
                f"(attn_drop={self.attn_drop} active in training) — the "
                "kernel never materializes the weights; set "
                "attn_drop_rate=0.0 on the model (trainer.build_model does)")
        if self.seq_manual and not weightless_ok:
            # no dense fallback exists inside the manual region — a local
            # einsum would silently attend block-diagonally
            raise ValueError(
                "manual sequence-parallel attention cannot apply "
                "attention-dropout or return weights — set "
                "attn_drop_rate=0.0 and need_weights=False")
        if self.seq_manual:
            # inside an enclosing manual shard_map (pipeline executor,
            # pipe×sp): x is the LOCAL (B', N/sp, C) shard; run the inner
            # sp kernel over the already-manual seq axis. A tp 'model'
            # axis, if any, stays GSPMD-auto via the param specs. Padding
            # tokens (dim padded up to the axis size) are masked (ring) or
            # sliced between the all-to-alls (ulysses) via seq_valid_len.
            if self.sp_mode == "ulysses":
                from ddim_cold_tpu.parallel.ulysses import ulysses_attention

                out = ulysses_attention(
                    q, k, v, axis_name=self.seq_axis,
                    n_valid=self.seq_valid_len, scale=scale,
                    use_flash=self.use_flash, flash_blocks=self.flash_blocks,
                ).astype(self.dtype)
            else:
                from ddim_cold_tpu.parallel.ring_attention import ring_attention

                valid = None
                if self.seq_valid_len is not None:
                    pos = (jax.lax.axis_index(self.seq_axis) * N
                           + jnp.arange(N))
                    valid = jnp.broadcast_to(
                        (pos < self.seq_valid_len)[None, :], (B, N))
                out = ring_attention(
                    q, k, v, valid, axis_name=self.seq_axis, scale=scale,
                    varying_axes=self.seq_varying_axes,
                ).astype(self.dtype)
            attn = None
        elif seq_parallel and weightless_ok:
            if self.sp_mode == "ulysses":
                from ddim_cold_tpu.parallel.ulysses import ulysses_self_attention

                # tp composition: the all-to-all splits each tp group's
                # LOCAL heads over the seq axis (ulysses.py head_axis)
                out = ulysses_self_attention(
                    q, k, v, self.seq_mesh,
                    axis=self.seq_axis, batch_axis=self.batch_axis,
                    head_axis=self.head_axis,
                    scale=scale, use_flash=self.use_flash,
                    flash_blocks=self.flash_blocks,
                ).astype(self.dtype)
            else:
                from ddim_cold_tpu.parallel.ring_attention import ring_self_attention

                out = ring_self_attention(
                    q, k, v, self.seq_mesh,
                    axis=self.seq_axis, batch_axis=self.batch_axis,
                    head_axis=self.head_axis, scale=scale,
                ).astype(self.dtype)
            attn = None
        elif self.use_flash and weightless_ok:
            if self.use_flash == "xla":
                # pure-XLA blockwise path: no Pallas to reject, bounded
                # memory — the safety net / inference middle path (its scan
                # backward saves per-block carries, so prefer the kernel for
                # training where it lowers)
                from ddim_cold_tpu.ops.flash_attention import (
                    blockwise_attention_xla,
                )

                out = blockwise_attention_xla(
                    q, k, v, scale,
                    *((self.flash_blocks[1],) if self.flash_blocks else ())
                ).astype(self.dtype)
            else:
                from ddim_cold_tpu.ops.flash_attention import (
                    flash_attention_qkv,
                )

                # None defers to the kernel's own defaults — one source of truth
                out = flash_attention_qkv(
                    packed, self.num_heads, scale,
                    *(self.flash_blocks or ())).astype(self.dtype)
            attn = None
        else:
            logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * scale
            attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(self.dtype)
            attn = nn.Dropout(self.attn_drop, deterministic=deterministic)(attn)
            out = jnp.einsum("bhnm,bmhd->bnhd", attn, v)

        out = out.reshape(B, N, C)
        out = dense(self.dim, True, "proj")(out)
        out = nn.Dropout(self.proj_drop, deterministic=deterministic)(out)
        return out, attn


def _attend_packed(packed: jax.Array, num_heads: int, scale: float,
                   use_flash, flash_blocks, dtype) -> jax.Array:
    """:class:`Attention`'s three weightless inference paths over the packed
    ``(B, N, 3C)`` projection → the context ``(B, N, C)``: the flash kernel,
    the blockwise XLA path, the dense einsum — what ``Attention.__call__``
    runs between its two Denses when nothing is dropped, probed or sharded
    along the sequence. A second copy of those branches, kept in step by
    hand: ``Attention`` computes q, k, v apart before it branches, dead code
    on the flash path that ``analysis/memory_checks``' liveness walk counts,
    so folding the two moves the 200px programs' peaks and the guard on them
    (PERF.md section 7, PR 32)."""
    B, N, C = packed.shape[0], packed.shape[1], packed.shape[2] // 3
    if use_flash and use_flash != "xla":
        from ddim_cold_tpu.ops.flash_attention import flash_attention_qkv

        return flash_attention_qkv(
            packed, num_heads, scale, *(flash_blocks or ())).astype(dtype)
    qkv = packed.reshape(B, N, 3, num_heads, C // num_heads)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if use_flash == "xla":
        from ddim_cold_tpu.ops.flash_attention import blockwise_attention_xla

        out = blockwise_attention_xla(
            q, k, v, scale,
            *((flash_blocks[1],) if flash_blocks else ())).astype(dtype)
    else:
        logits = jnp.einsum("bnhd,bmhd->bhnm", q, k) * scale
        attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dtype)
        out = jnp.einsum("bhnm,bmhd->bnhd", attn, v)
    return out.reshape(B, N, C)


class Block(nn.Module):
    """Pre-LN transformer block with stochastic-depth residuals (reference
    ViT.py:120-138). On the inference path (``deterministic``, nothing probed,
    quantised, routed or sharded along the sequence, ``dim`` in whole lanes)
    the token-wise half — everything but attention — runs as the two
    weight-resident kernels of ``ops/block_kernels.py`` on the same parameter
    tree; anything else, training and ``init`` included, runs the composition
    below. ``kernels.block_tokenwise`` counts which, once a trace."""

    dim: int
    num_heads: int
    mlp_ratio: float = 4.0
    qkv_bias: bool = False
    qk_scale: Optional[float] = None
    drop: float = 0.0
    attn_drop: float = 0.0
    drop_path: float = 0.0
    dtype: Dtype = jnp.float32
    use_flash: "bool | str" = False  # False | True (Pallas) | "xla" (blockwise)
    flash_blocks: Optional[tuple] = None
    seq_mesh: Optional[Mesh] = None
    seq_axis: Optional[str] = None
    batch_axis: Optional[str] = None
    head_axis: Optional[str] = None
    sp_mode: str = "ring"
    # manual-collective sp (pipe×sp; see Attention.seq_manual)
    seq_manual: bool = False
    seq_valid_len: Optional[int] = None
    seq_varying_axes: Optional[tuple] = None
    num_experts: int = 1  # >1: Switch-MoE MLP (models/moe.py, 'expert' axis)
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"  # routing impl: "einsum" | "index" (moe.py)
    quant: Optional[str] = None  # w8a16 trunk denses (ops/quant.py)
    fused: bool = False  # fused trunk kernels (Attention + Mlp megakernels)

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 return_attention: bool = False,
                 dp_rate: Optional[jax.Array] = None):
        if self.quant and self.num_experts > 1:
            raise ValueError(
                "quant covers the dense trunk only — the Switch-MoE expert "
                "banks have no quantized path (set num_experts=1)")
        hidden = int(self.dim * self.mlp_ratio)
        rows = None
        if (deterministic and not return_attention and self.quant is None
                and self.num_experts == 1 and self.seq_mesh is None
                and not self.seq_manual and x.dtype == self.dtype
                and not self.is_initializing()):
            from ddim_cold_tpu.ops import block_kernels

            rows = block_kernels.row_block(
                x.shape[1], self.dim, hidden, self.dtype)
        _kernels.inc("kernels.block_tokenwise",
                     key="xla" if rows is None else "kernel")
        if rows is not None:
            (w_qkv, b_qkv), (w_proj, b_proj) = _AttnParams(
                self.dim, self.qkv_bias, name="attn")()
            (w_fc1, b_fc1), (w_fc2, b_fc2) = _MlpParams(
                hidden, self.dim, name="mlp")()
            with jax.named_scope("trunk/attn"):
                packed = block_kernels.ln_qkv(
                    x, *_NormParams(name="norm1")(self.dim), w_qkv, b_qkv,
                    1e-5, rows)
                ctx = _attend_packed(
                    packed, self.num_heads,
                    self.qk_scale or (self.dim // self.num_heads) ** -0.5,
                    self.use_flash, self.flash_blocks, self.dtype)
            # block_tail also holds attention's output projection and its
            # residual: one launch, under the layer most of it belongs to
            with jax.named_scope("trunk/mlp"):
                return block_kernels.block_tail(
                    ctx, x, w_proj, b_proj,
                    *_NormParams(name="norm2")(self.dim),
                    w_fc1, b_fc1, w_fc2, b_fc2, 1e-5, rows)
        ln = lambda name: nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name=name)
        attention = Attention(
            dim=self.dim,
            num_heads=self.num_heads,
            qkv_bias=self.qkv_bias,
            qk_scale=self.qk_scale,
            attn_drop=self.attn_drop,
            proj_drop=self.drop,
            dtype=self.dtype,
            use_flash=self.use_flash,
            flash_blocks=self.flash_blocks,
            seq_mesh=self.seq_mesh,
            seq_axis=self.seq_axis,
            batch_axis=self.batch_axis,
            head_axis=self.head_axis,
            sp_mode=self.sp_mode,
            seq_manual=self.seq_manual,
            seq_valid_len=self.seq_valid_len,
            seq_varying_axes=self.seq_varying_axes,
            quant=self.quant,
            fused=self.fused,
            name="attn",
        )
        with jax.named_scope("trunk/attn"):
            y, attn = attention(ln("norm1")(x), deterministic=deterministic,
                                need_weights=return_attention)
        if return_attention:
            return attn

        # per-sample stochastic depth (reference ViT.py:52-71): Bernoulli(keep)
        # mask broadcast over all but the batch dim, survivors scaled 1/keep —
        # exactly nn.Dropout with broadcast_dims. Under nn.scan the rate
        # arrives as a traced per-block scalar (``dp_rate``) — no Python
        # branching on it allowed, so the mask is drawn explicitly.
        if dp_rate is None:
            residual = nn.Dropout(self.drop_path, broadcast_dims=(1, 2),
                                  deterministic=deterministic)
        elif deterministic:
            residual = lambda y: y
        else:
            def residual(y, _rate=dp_rate):
                keep = 1.0 - _rate
                mask = jax.random.bernoulli(
                    self.make_rng("dropout"), keep, (y.shape[0], 1, 1))
                return jnp.where(mask, y / keep, jnp.zeros_like(y)).astype(y.dtype)

        with jax.named_scope("trunk/attn"):
            x = x + residual(y)
        if self.num_experts > 1:
            from ddim_cold_tpu.models.moe import SwitchMlp

            mlp = SwitchMlp(
                num_experts=self.num_experts,
                hidden_features=int(self.dim * self.mlp_ratio),
                out_features=self.dim,
                capacity_factor=self.moe_capacity_factor,
                drop=self.drop,
                dtype=self.dtype,
                dispatch=self.moe_dispatch,
                name="moe",
            )
        else:
            mlp = Mlp(
                hidden_features=int(self.dim * self.mlp_ratio),
                out_features=self.dim,
                drop=self.drop,
                dtype=self.dtype,
                quant=self.quant,
                fused=self.fused,
                name="mlp",
            )
        with jax.named_scope("trunk/moe" if self.num_experts > 1
                             else "trunk/mlp"):
            y = mlp(ln("norm2")(x), deterministic=deterministic)
            x = x + residual(y)
        return x


def block_template(model: "DiffusionViT", *, seq_manual_axis=None,
                   seq_valid_len=None, seq_varying_axes=None) -> "Block":
    """Unbound single-layer Block matching ``model``'s scan_blocks layout —
    the pipeline executor (parallel/pipeline.py) applies it functionally per
    stage layer with slices of the stacked ``blocks`` params (drop-path rate
    arrives traced). Module-level fn: constructing a child inside an unbound
    module method trips flax's parent tracking.

    ``seq_manual_axis`` builds the pipe×sp variant: attention runs the inner
    ring kernel over that (already-manual) axis on the local shard."""
    return Block(
        dim=model.embed_dim, num_heads=model.num_heads, mlp_ratio=model.mlp_ratio,
        qkv_bias=model.qkv_bias, qk_scale=model.qk_scale, drop=model.drop_rate,
        attn_drop=model.attn_drop_rate, drop_path=0.0, dtype=model.dtype,
        use_flash=model.use_flash, flash_blocks=model.flash_blocks,
        sp_mode=model.sp_mode,
        seq_manual=seq_manual_axis is not None, seq_axis=seq_manual_axis,
        seq_valid_len=seq_valid_len, seq_varying_axes=seq_varying_axes,
        num_experts=model.num_experts,
        moe_capacity_factor=model.moe_capacity_factor,
        moe_dispatch=model.moe_dispatch,
    )


class _ScanShell(nn.Module):
    """Scan-compatible adapter around Block: ``(carry, (det, dp_rate)) →
    (carry, None)``. ``nn.scan`` over this stacks every block's params on a
    leading depth axis — one compiled block regardless of depth, and the
    substrate pipeline parallelism shards stages from."""

    blk: "Block"

    @nn.compact
    def __call__(self, x, deterministic, dp_rate):
        return self.blk(x, deterministic, dp_rate=dp_rate), None


class PatchEmbed(nn.Module):
    """Image → patch tokens as one GEMM (reference ViT.py:141-155 uses Conv2d).

    For kernel=stride=p a convolution is exactly a linear map on flattened
    patches; the reshape+Dense form is the MXU-friendly expression. The patch
    feature order (row, col, channel — channel fastest) matches the torch conv
    weight layout after ``W.transpose(2,3,1,0).reshape(p²C, E)`` so converted
    checkpoints are bit-identical.

    Init: torch Conv2d default (kaiming_uniform a=√5) — the reference's
    ``_init_weights`` skips Conv2d (models/init.py docstring).
    """

    patch_size: int
    embed_dim: int
    in_chans: int = 3
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        B, H, W, C = x.shape
        p = self.patch_size
        hp, wp = H // p, W // p
        fan_in = C * p * p
        x = x.reshape(B, hp, p, wp, p, C)
        x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, hp, wp, p, p, C)
        x = x.reshape(B, hp * wp, p * p * C)
        x = nn.Dense(
            self.embed_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=torch_default_uniform(fan_in),
            bias_init=torch_default_uniform(fan_in),
            name="proj",
        )(x)
        return x


def embed_tokens(mod: nn.Module, x: jax.Array, t: jax.Array, *,
                 drop_rate: float, deterministic: bool,
                 use_sincos_pos: bool = False,
                 param_dtype: Dtype = jnp.float32) -> jax.Array:
    """The denoisers' input stage, declared in ``mod``'s scope (call it from
    ``mod``'s compact ``__call__``): ``patch_embed``, ``cls_token``,
    ``time_embed``, ``pos_embed``, ``pos_drop`` — one definition for every
    trunk (``DiffusionViT`` here, ``models/hybrid.py``). ``mod`` supplies
    ``patch_size``, ``embed_dim``, ``in_chans``, ``num_patches``,
    ``total_steps`` and ``dtype``."""
    B, E, N = x.shape[0], mod.embed_dim, mod.num_patches
    x = x.astype(mod.dtype)
    tokens = PatchEmbed(
        patch_size=mod.patch_size,
        embed_dim=E,
        in_chans=mod.in_chans,
        dtype=mod.dtype,
        param_dtype=param_dtype,
        name="patch_embed",
    )(x)

    cls_token = mod.param("cls_token", trunc_normal(std=0.02), (1, 1, E),
                          param_dtype)
    tokens = jnp.concatenate(
        [jnp.broadcast_to(cls_token.astype(mod.dtype), (B, 1, E)), tokens], axis=1
    )

    # time conditioning: one learned row per step, added to EVERY token
    # (cls included) together with the positional embedding (ViT.py:204-205).
    time_embed = nn.Embed(
        mod.total_steps,
        E,
        embedding_init=trunc_normal(std=0.02),
        dtype=mod.dtype,
        param_dtype=param_dtype,
        name="time_embed",
    )(t.astype(jnp.int32))[:, None, :]

    if use_sincos_pos:
        pos_embed = jnp.asarray(positionalencoding1d(E, N + 1))[None]
    else:
        pos_embed = mod.param("pos_embed", trunc_normal(std=0.02),
                              (1, N + 1, E), param_dtype)
    tokens = tokens + pos_embed.astype(mod.dtype) + time_embed
    return nn.Dropout(drop_rate, deterministic=deterministic,
                      name="pos_drop")(tokens)


def pixel_head(mod: nn.Module, tokens: jax.Array, *,
               param_dtype: Dtype = jnp.float32) -> jax.Array:
    """The denoisers' output stage on normed tokens, in ``mod``'s scope:
    ``head`` (width → C·p²), the class token dropped, un-patchified, float32."""
    tokens = nn.Dense(
        mod.in_chans * mod.patch_size**2,
        dtype=mod.dtype,
        param_dtype=param_dtype,
        kernel_init=trunc_normal(std=0.02),
        bias_init=nn.initializers.zeros_init(),
        name="head",
    )(tokens)
    return unpatchify(tokens[:, 1:, :], mod.img_size, mod.patch_size,
                      mod.in_chans).astype(jnp.float32)


def unpatchify(x: jax.Array, img_size, patch_size: int,
               in_chans: int) -> jax.Array:
    """(B, N, p²C) → (B, H, W, C), exact reference pixel mapping.

    The torch path (ViT.py:214-217) views the feature dim as (p, p, C)
    with C fastest, then permute(0,5,1,3,2,4): pixel (i·p+a, j·p+b, c) ←
    feature a·pC + b·C + c of patch (i, j). NHWC equivalent below.
    """
    p, C = patch_size, in_chans
    H, W = img_size
    B = x.shape[0]
    x = x.reshape(B, H // p, W // p, p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, H/p, p, W/p, p, C)
    return x.reshape(B, H, W, C)


class DiffusionViT(nn.Module):
    """The diffusion backbone: ``(x_t, t) → x̂0`` (reference ViT.py:158-218).

    Inputs are NHWC in [−1, 1]; ``t`` is an int32 vector of per-sample steps in
    [0, total_steps). Out-of-range steps produce NaN outputs (JAX fills
    out-of-bounds gathers) — the traced-code analogue of torch's IndexError.
    Constructor defaults mirror the reference ctor (ViT.py:160-162):
    mlp_ratio=1.0, qkv_bias=True, all drop rates 0.1, total_steps=2000.
    ``diff_step``-style cold configs keep the full 2000-row time-embedding
    table (SURVEY.md quirk #4) unless ``total_steps`` is overridden.
    """

    img_size: Sequence[int] = (64, 64)
    patch_size: int = 8
    in_chans: int = 3
    embed_dim: int = 256
    depth: int = 3
    num_heads: int = 4
    mlp_ratio: float = 1.0
    qkv_bias: bool = True
    qk_scale: Optional[float] = None
    drop_rate: float = 0.1
    attn_drop_rate: float = 0.1
    drop_path_rate: float = 0.1
    total_steps: int = 2000
    dtype: Dtype = jnp.float32
    use_sincos_pos: bool = False  # fixed sinusoidal pos table for >64px configs (C7)
    use_flash: "bool | str" = False  # False=dense | True=Pallas fused | "xla"=
    # pure-XLA blockwise online-softmax (long-seq configs; "xla" is the
    # Mosaic-free safety net)
    flash_blocks: Optional[tuple] = None  # (block_q, block_kv) kernel tuning
    remat: bool = False  # jax.checkpoint each block: recompute activations in
    # backward instead of holding depth× residuals in HBM (big-config training)
    # sequence parallelism (ring attention over `seq_axis` of `seq_mesh`;
    # `batch_axis` composes with dp sharding) — sequences beyond one chip
    seq_mesh: Optional[Mesh] = None
    seq_axis: Optional[str] = None
    batch_axis: Optional[str] = None
    head_axis: Optional[str] = None  # tp axis for head-sharded ring attention
    sp_mode: str = "ring"  # "ring" | "ulysses" (all-to-all head resharding)
    scan_blocks: bool = False  # nn.scan over depth: params stacked on a
    # leading layer axis (O(1) compile in depth; pipeline-parallel substrate)
    num_experts: int = 1  # >1: Switch-MoE MLP per block (models/moe.py);
    # expert params shard over an 'expert' mesh axis. Composes with
    # scan_blocks (the scan stacks the sown aux losses on the layer axis)
    # AND with pipe (the pipeline stage body re-sows: each block call's aux
    # is accumulated across the schedule, bubble steps masked, and returned
    # through the pipelined apply's mutable=["losses"] path — pipeline.py).
    moe_capacity_factor: float = 1.25
    moe_dispatch: str = "einsum"  # see models/moe.py: "index" removes the
    # O(N^2*cf) one-hot dispatch tensors (long-sequence configs)
    quant: Optional[str] = None  # w8a16 trunk inference (ops/quant.py):
    # None = float kernels (the training path, bit-identical to before);
    # "xla" | "pallas" = per-output-channel int8 qkv/proj/fc1/fc2 consumed
    # from a quantize_params tree; embeddings/norms/patch/head stay float.
    # Part of the module hash, so jit/AOT program caches key on it.
    fused: bool = False  # fused sampler-trunk megakernels (inference): with
    # quant="pallas"/"w8a8" the attention runs qkv-dequant → flash → proj as
    # ONE kernel and the Mlp as another (ops/flash_attention.py, ops/quant.py);
    # with quant=None only the float fused Mlp applies. Declares the SAME
    # param leaves as the unfused composition — one tree serves both — and
    # the training/probe/sp paths silently fall back to it.

    @property
    def num_patches(self) -> int:
        return (self.img_size[0] // self.patch_size) * (self.img_size[1] // self.patch_size)


    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        t: jax.Array,
        deterministic: bool = True,
        return_attention_layer: Optional[int] = None,
        stage: str = "full",
        tokens: Optional[jax.Array] = None,
        skip_blocks: Optional[tuple] = None,
        block_delta: Optional[jax.Array] = None,
        capture_split: Optional[int] = None,
        capture_tokens: bool = False,
        token_cache: Optional[tuple] = None,
        token_k: Optional[int] = None,
    ) -> jax.Array:
        """``stage`` partitions the forward for pipeline parallelism
        (parallel/pipeline.py): ``"embed"`` returns the token sequence after
        patch/pos/time embedding; ``"head"`` takes ``tokens`` (the trunk
        output, supplied by the pipeline) and runs final-LN → head →
        un-patchify; ``"full"`` is the normal forward.

        Step-cache hooks (ops/step_cache.py, Δ-DiT-style training-free
        sampler acceleration):

        * ``capture_split=s`` (static, 1 ≤ s < depth) — a *refresh* forward:
          run every block and additionally return the cumulative residual
          deltas of the front (blocks [0, s)) and rear (blocks [s, depth))
          trunk halves, ``(x̂0, (delta_front, delta_rear))``. Each delta is
          the (B, N+1, E) token-stream displacement the half contributes;
          because blocks are residual, the sum over a contiguous range is
          exactly ``tokens_out − tokens_in`` of that range.
        * ``skip_blocks=(lo, hi)`` + ``block_delta`` (static range, traced
          delta) — a *reuse* forward: blocks [lo, hi) are never executed;
          their cached cumulative delta is added to the token stream where
          block ``lo`` would have run. The skipped blocks' parameters are
          untouched (flax ``apply`` tolerates unused params), so reuse steps
          pay only the remaining blocks' FLOPs.

        Both are static trace-time decisions — no device branching — and are
        mutually exclusive with each other, with ``scan_blocks`` (one scanned
        body cannot statically drop layers), with the attention probe, and
        with partial ``stage`` forwards.

        Token-cache hooks (JiT-style spatial caching, arXiv:2603.10744 —
        ``cache_mode="token"`` in ops/step_cache.py):

        * ``capture_tokens=True`` — a *refresh* forward: run every block on
          every token and return ``(x̂0, (ref_in, trunk_delta))`` where
          ``ref_in`` is the post-embed token stream (the reference each
          later step measures per-token change against) and ``trunk_delta``
          is the (B, N+1, E) trunk displacement ``trunk_out − ref_in``.
        * ``token_cache=(ref_in, trunk_delta)`` + ``token_k=k`` (static k)
          — a *reuse* forward: score each token by its squared change vs
          ``ref_in``, force the CLS token live, gather the top-k changed
          tokens (indices SORTED into position order so k = N+1 degenerates
          to the identity permutation and the step is bitwise the plain
          forward), run the full trunk on only those k tokens, and scatter
          the results into the cached stream ``tokens + trunk_delta``.
          Returns ``(x̂0, (new_ref, new_delta))`` with the recomputed rows
          refreshed in both cache leaves. Reuse steps pay the trunk at
          sequence length k instead of N+1.

        The token hooks carry the same static restrictions as the block-
        delta hooks and are mutually exclusive with them (one cache family
        per forward)."""
        if self.quant is not None:
            from ddim_cold_tpu.ops.quant import QUANT_MODES

            if self.quant not in QUANT_MODES:
                raise ValueError(f"quant must be None or one of {QUANT_MODES}, "
                                 f"got {self.quant!r}")
            if self.scan_blocks:
                # the stacked (depth, in, out) kernel layout would need a
                # per-layer scale axis the codec doesn't model; quant serves
                # the unrolled inference path (which the samplers use)
                raise ValueError("quant requires scan_blocks=False")
        if self.fused and self.quant == "xla":
            raise ValueError(
                "fused=True requests the Pallas fused trunk kernels but "
                "quant='xla' explicitly opts out of Pallas — use "
                "quant='pallas' or 'w8a8' (or quant=None for the float "
                "fused Mlp alone)")
        if skip_blocks is not None or capture_split is not None:
            if self.scan_blocks:
                raise ValueError(
                    "step caching (skip_blocks/capture_split) requires "
                    "scan_blocks=False — one scanned block body cannot "
                    "statically drop layers")
            if stage != "full":
                raise ValueError("step caching composes with stage='full' only")
            if return_attention_layer is not None:
                raise ValueError("step caching excludes the attention probe")
        if skip_blocks is not None and capture_split is not None:
            raise ValueError(
                "skip_blocks (reuse step) and capture_split (refresh step) "
                "are distinct cache branches — pass one or the other")
        if skip_blocks is not None:
            lo, hi = skip_blocks
            if not (0 <= lo < hi <= self.depth):
                raise ValueError(f"skip_blocks {skip_blocks} outside "
                                 f"[0, {self.depth})")
            if block_delta is None:
                raise ValueError("skip_blocks requires the cached block_delta")
        if capture_split is not None and not (1 <= capture_split < self.depth):
            raise ValueError(f"capture_split {capture_split} must split "
                             f"depth {self.depth} into two non-empty halves")
        if capture_tokens or token_cache is not None:
            if self.scan_blocks:
                raise ValueError(
                    "token caching (capture_tokens/token_cache) requires "
                    "scan_blocks=False — the gathered subset changes the "
                    "scanned body's shape")
            if stage != "full":
                raise ValueError("token caching composes with stage='full' only")
            if return_attention_layer is not None:
                raise ValueError("token caching excludes the attention probe")
            if skip_blocks is not None or capture_split is not None:
                raise ValueError(
                    "token caching (capture_tokens/token_cache) and block-"
                    "delta caching (skip_blocks/capture_split) are distinct "
                    "cache families — pass one or the other")
        if capture_tokens and token_cache is not None:
            raise ValueError(
                "capture_tokens (refresh step) and token_cache (reuse step) "
                "are distinct cache branches — pass one or the other")
        if token_cache is not None:
            if token_k is None or not (1 <= token_k <= self.num_patches + 1):
                raise ValueError(
                    f"token_cache requires static token_k in "
                    f"[1, {self.num_patches + 1}], got {token_k!r}")
        elif token_k is not None:
            raise ValueError("token_k only applies with token_cache")
        B = x.shape[0]
        E = self.embed_dim
        N = self.num_patches

        if stage == "head":
            if tokens is None:
                raise ValueError('stage="head" requires tokens')
            tokens = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm")(tokens)
            return pixel_head(self, tokens)

        tokens = embed_tokens(self, x, t, drop_rate=self.drop_rate,
                              deterministic=deterministic,
                              use_sincos_pos=self.use_sincos_pos)
        if stage == "embed":
            return tokens

        stream_in = tokens  # post-embed stream — the token-cache reference
        live = None
        if token_cache is not None:
            ref_in, trunk_delta = token_cache
            sub_in = tokens
            # static degenerate k = N+1: every token is live, so the gather/
            # scatter would be the identity — elide it at trace time, making
            # this branch op-for-op the plain trunk (the BITWISE contract:
            # fusion around a gather rounds differently inside a scan body)
            if token_k < N + 1:
                # per-token squared change vs the stream each token was last
                # recomputed at; reductions in f32 so bf16 streams rank stably
                scores = jnp.sum(
                    jnp.square((tokens - ref_in).astype(jnp.float32)), axis=-1)
                # CLS attends globally and feeds nothing to unpatchify's
                # pixels directly, but every live token attends TO it — keep
                # it fresh
                scores = scores.at[:, 0].set(jnp.finfo(jnp.float32).max)
                _, live = jax.lax.top_k(scores, token_k)  # (B, k) per-row
                # sorted into position order so the gathered subsequence
                # keeps the stream's relative layout
                live = jnp.sort(live, axis=-1)
                sub_in = jnp.take_along_axis(tokens, live[:, :, None], axis=1)
            tokens = sub_in  # the trunk below runs at sequence length k

        # stochastic depth decay rule: linspace(0, rate, depth) (ViT.py:176)
        dpr = np.linspace(0.0, self.drop_path_rate, self.depth)
        if self.scan_blocks:
            if return_attention_layer is not None:
                raise ValueError("attention probe requires scan_blocks=False")
            blk = Block(
                dim=E, num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                qkv_bias=self.qkv_bias, qk_scale=self.qk_scale,
                drop=self.drop_rate, attn_drop=self.attn_drop_rate,
                drop_path=0.0,  # rate arrives traced per layer (dp_rate)
                dtype=self.dtype, use_flash=self.use_flash,
                flash_blocks=self.flash_blocks,
                seq_mesh=self.seq_mesh, seq_axis=self.seq_axis,
                batch_axis=self.batch_axis, head_axis=self.head_axis,
                sp_mode=self.sp_mode,
                num_experts=self.num_experts,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_dispatch=self.moe_dispatch,
                fused=self.fused,  # quant is refused above — float fused Mlp
                # the shell's field module binds to THIS scope, not the
                # shell's — name it so params land under "blocks"
                name="blocks",
            )
            shell = _ScanShell if not self.remat else nn.remat(
                _ScanShell, static_argnums=(2,))
            scan = nn.scan(
                shell,
                # 'losses' scanned on the layer axis keeps the Switch-MoE
                # aux loss (sown per block, models/moe.py) — previously the
                # MoE×scan_blocks combination was refused because the sown
                # values were dropped (VERDICT r4 weak #6)
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, 0),
                length=self.depth,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(blk)
            tokens, _ = scan(tokens, deterministic,
                             jnp.asarray(dpr, jnp.float32))
        else:
            # deterministic (argnum 2; 0 is the module) is a Python bool
            # steering trace-time structure — static under jax.checkpoint.
            block_cls = nn.remat(Block, static_argnums=(2,)) if self.remat else Block
            lo, hi = skip_blocks if skip_blocks is not None else (0, 0)
            tokens_in = tokens if capture_split is not None else None
            tokens_mid = None
            for i in range(self.depth):
                if skip_blocks is not None and lo <= i < hi:
                    if i == lo:
                        tokens = tokens + block_delta.astype(self.dtype)
                    continue
                blk_kwargs = dict(
                    dim=E,
                    num_heads=self.num_heads,
                    mlp_ratio=self.mlp_ratio,
                    qkv_bias=self.qkv_bias,
                    qk_scale=self.qk_scale,
                    drop=self.drop_rate,
                    attn_drop=self.attn_drop_rate,
                    drop_path=float(dpr[i]),
                    dtype=self.dtype,
                    use_flash=self.use_flash,
                    flash_blocks=self.flash_blocks,
                    seq_mesh=self.seq_mesh,
                    seq_axis=self.seq_axis,
                    batch_axis=self.batch_axis,
                    head_axis=self.head_axis,
                    sp_mode=self.sp_mode,
                    num_experts=self.num_experts,
                    moe_capacity_factor=self.moe_capacity_factor,
                    moe_dispatch=self.moe_dispatch,
                    quant=self.quant,
                    fused=self.fused,
                )
                probe = (return_attention_layer is not None
                         and i == return_attention_layer % self.depth)
                if probe:
                    # attention probe (reference Block.return_attention,
                    # ViT.py:132-135) — forward-only, so remat would be pure
                    # overhead: probe a plain Block (same name ⇒ same params).
                    return Block(**blk_kwargs, name=f"blocks_{i}")(
                        tokens, deterministic=deterministic, return_attention=True)
                # positional deterministic: jax.checkpoint static_argnums
                # covers positionals only; Dropout branches on it in Python.
                tokens = block_cls(**blk_kwargs, name=f"blocks_{i}")(tokens, deterministic)
                if capture_split is not None and i == capture_split - 1:
                    tokens_mid = tokens

        if token_cache is not None:
            sub_out = tokens  # (B, k, E) — trunk output of the live subset
            if live is None:  # degenerate k = N+1 — full overwrite, no scatter
                new_ref = sub_in
                new_delta = (sub_out - sub_in).astype(trunk_delta.dtype)
            else:
                brow = jnp.arange(B)[:, None]
                # stale tokens: last trunk output ≈ current embed + cached
                # trunk displacement; live rows get this step's true output
                tokens = stream_in + trunk_delta.astype(self.dtype)
                tokens = tokens.at[brow, live].set(sub_out)
                new_ref = ref_in.at[brow, live].set(sub_in)
                new_delta = trunk_delta.at[brow, live].set(
                    (sub_out - sub_in).astype(trunk_delta.dtype))

        trunk_out = tokens  # pre-norm trunk output — the delta reference point
        tokens = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="norm")(tokens)
        out = pixel_head(self, tokens)
        if capture_split is not None:
            return out, (tokens_mid - tokens_in, trunk_out - tokens_mid)
        if capture_tokens:
            return out, (stream_in, trunk_out - stream_in)
        if token_cache is not None:
            return out, (new_ref, new_delta)
        return out

    def unpatchify(self, x: jax.Array) -> jax.Array:
        """(B, N, p²C) → (B, H, W, C): :func:`unpatchify` at this model's sizes."""
        return unpatchify(x, self.img_size, self.patch_size, self.in_chans)


def sp_clone(model: DiffusionViT, mesh, *, sp_mode: str = "ulysses",
             seq_axis: str = "seq", batch_axis: str = "data",
             head_axis=None) -> DiffusionViT:
    """The sequence-parallel variant of ``model`` for sampling over ``mesh``
    — the SAME clone the serve engine builds per sp config (engine, direct
    callers, and the graftcheck sweep all route through here so the
    strategy resolution can never diverge between them).

    Resolution: ``sp_mode='ulysses'`` needs the tp-local head count
    divisible by the seq axis (parallel/ulysses.py raises
    SeqParallelConfigError otherwise), so it falls back to the ring — which
    has no head constraint — instead of failing at trace time. Patch tokens
    end up sequence-sharded inside the attention shard_map; the CLS/time
    conditioning stays replicated like every other non-sequence activation.
    """
    parts = int(mesh.shape[seq_axis])
    tp = int(mesh.shape[head_axis]) if head_axis else 1
    if sp_mode == "ulysses" and (model.num_heads // tp) % parts:
        sp_mode = "ring"
    return model.clone(seq_mesh=mesh, seq_axis=seq_axis,
                       batch_axis=batch_axis, head_axis=head_axis,
                       sp_mode=sp_mode)
