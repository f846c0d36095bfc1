"""W8A16 post-training quantization for the ViT trunk (the param-traffic lever).

PERF.md's north-star analysis puts the 200px/k=20 sampler past the
attention-HBM wall (flash kernel); the next costs are trunk GEMM time and
parameter bytes over the link. Training-free weight-only quantization is the
standard diffusion-transformer answer (Efficient Diffusion Models survey,
arXiv:2502.06805): **symmetric per-output-channel int8 weights, bf16
activations** (w8a16) for the four trunk GEMMs per block — attention
``qkv``/``proj`` and Mlp ``fc1``/``fc2``. Embeddings, layernorms, the patch
projection and the output head stay in float (small, and the head sets pixel
accuracy).

Pieces:

* ``quantize_weight`` / ``dequantize_weight`` — the per-output-channel
  symmetric codec: ``scale = max|w|/127`` per output column, values clipped
  to [−127, 127] (the −128 code is unused, keeping the codec symmetric).
* ``quantize_params`` — one-shot transform of a DiffusionViT param tree:
  each trunk dense's ``kernel`` leaf becomes ``{w_int8, scale}`` IN PLACE
  (same module paths, bias untouched), so ``parallel/sharding.py``'s
  module-name keyed specs and the serving engine's pre-sharded param flow
  apply unchanged, and the tree ships ≈4× fewer trunk-param bytes.
* ``dequant_matmul`` — the w8a16 GEMM, two implementations behind one
  signature:

  - ``mode="xla"``: ``lax.dot_general`` on the int8 weights upcast to the
    activation dtype with ``preferred_element_type=f32`` accumulation; XLA
    fuses the int8→bf16 convert into the matmul read and the per-column
    scale multiply into the epilogue — no dequantized weight copy in HBM.
  - ``mode="pallas"``: a fused dequant-matmul kernel (grid over M/N tiles,
    K streamed innermost through a VMEM f32 accumulator, scale applied once
    at emit). Same backend policy as ops/flash_attention.py
    (``kernel_interpret``): TPU compiles the kernel, CPU runs it in
    interpreter mode (tests exercise the real code path), any other backend
    raises — ``mode="xla"`` is the form to ask for there.

* ``QuantDense`` — the flax module models/vit.py swaps in for ``nn.Dense``
  when ``model.quant`` is set; declares exactly the ``{w_int8, scale[, bias]}``
  leaves ``quantize_params`` produces.
* ``calibrate`` — per-layer max-abs quantization error stats, so a bad layer
  in the paired Fréchet guard (eval/fid.quantized_sampler_guard) is
  attributable to its scale, not hunted by bisection.

Both matmul paths accumulate in f32 and apply scale/bias in f32, so
``mode="xla"`` and ``mode="pallas"`` agree to f32 round-off and either can
stand in for the other in tests.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.ops.flash_attention import kernel_interpret
from ddim_cold_tpu.utils import profiling

#: quantization revision, reported by ``eval/fid.quantized_sampler_guard``
#: (``quant_rev``). "w8a16-pcq-v1" = per-output-channel
#: symmetric int8 weights, [−127, 127] codes, f32-accumulated dequant matmul.
#: "w8a16-fused-v2" adds the fused trunk kernels (mlp_pallas here, the fused
#: attention in ops/flash_attention.py) and the optional "w8a8" activation
#: mode (per-tensor dynamic int8 activations, int32 MXU accumulation). The
#: weight codec is unchanged from v1 — int8 param trees need no re-quantize.
QUANT_REV = "w8a16-fused-v2"

#: dequant_matmul modes a model/SamplerConfig may request. "w8a8" = int8
#: weights AND int8 activations (per-tensor dynamic scale, round-to-nearest
#: [−127, 127] codes) — FID-guard gated (eval/fid.quantized_sampler_guard);
#: the weight tree is the same w8a16 tree, only the GEMM feed changes.
QUANT_MODES = ("xla", "pallas", "w8a8")

#: trunk modules whose ``kernel`` is quantized, keyed by parent module name —
#: the same (parent, leaf) addressing parallel/sharding.py's _spec_for uses.
#: NOTE ``proj`` alone is ambiguous (patch_embed's dense is also "proj");
#: the parent-name key is what keeps the patch projection in float.
_TRUNK_DENSE = {"attn": ("qkv", "proj"), "mlp": ("fc1", "fc2")}

_LANE = 128  # TPU lane width: last dim of VMEM tiles
_INT8_SUBLANE = 32  # int8 min tile is (32, 128): K blocks must be 32-aligned


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------

def quantize_weight(kernel: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization of a (in, out) kernel.

    ``scale[j] = max_i |kernel[i, j]| / 127`` (1.0 for all-zero columns so
    dequantization never divides by zero); codes are round-to-nearest-even
    and clipped to [−127, 127]. Round-trip error is ≤ scale/2 per channel by
    construction (asserted in tests/test_quant.py).
    """
    k32 = jnp.asarray(kernel, jnp.float32)
    amax = jnp.max(jnp.abs(k32), axis=tuple(range(k32.ndim - 1)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(k32 / scale), -127.0, 127.0)
    return codes.astype(jnp.int8), scale


def dequantize_weight(w_int8: jax.Array, scale: jax.Array,
                      dtype: Any = jnp.float32) -> jax.Array:
    return (w_int8.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# param-tree transform
# ---------------------------------------------------------------------------

def _is_trunk_dense(path: tuple[str, ...]) -> bool:
    return (len(path) >= 2 and path[-1] in _TRUNK_DENSE.get(path[-2], ()))


def _walk(tree, path=()):
    """Yield ``(path, module_dict)`` for every trunk dense holding a kernel."""
    if not isinstance(tree, dict) and not hasattr(tree, "items"):
        return
    for name, sub in tree.items():
        sub_path = path + (name,)
        if _is_trunk_dense(sub_path) and hasattr(sub, "items") and "kernel" in sub:
            yield sub_path, sub
        else:
            yield from _walk(sub, sub_path)


def quantize_params(params):
    """One-shot w8a16 transform of a DiffusionViT ``params`` tree.

    Every trunk dense (``attn/{qkv,proj}``, ``mlp/{fc1,fc2}``) has its
    ``kernel`` replaced by ``{w_int8, scale}``; biases and every non-trunk
    leaf pass through untouched. The tree topology (module paths) is
    preserved, so partition-spec derivation and the engine's param flow see
    the same structure. The result is what ``model.clone(quant=...)``'s
    forward consumes (models/vit.py routes the trunk through
    :class:`QuantDense`).
    """
    def rec(tree, path=()):
        if not hasattr(tree, "items"):
            return tree
        out = {}
        for name, sub in tree.items():
            sub_path = path + (name,)
            if (_is_trunk_dense(sub_path) and hasattr(sub, "items")
                    and "kernel" in sub):
                w_int8, scale = quantize_weight(sub["kernel"])
                mod = {k: v for k, v in sub.items() if k != "kernel"}
                mod["w_int8"], mod["scale"] = w_int8, scale
                out[name] = mod
            else:
                out[name] = rec(sub, sub_path)
        return out

    return rec(params)


def is_quantized(params) -> bool:
    """True when the tree carries at least one ``w_int8`` trunk leaf."""
    found = []

    def rec(tree):
        if hasattr(tree, "items"):
            for name, sub in tree.items():
                if name == "w_int8":
                    found.append(True)
                rec(sub)

    rec(params)
    return bool(found)


def param_bytes(params) -> int:
    """Total bytes of every array leaf — the H2D param-traffic number the
    serving engine reports (int8 trunks ship ≈4× fewer)."""
    return int(sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(params)))


def calibrate(params) -> dict:
    """Per-layer quantization error stats: for every trunk dense, the
    worst-case absolute weight error, the worst error relative to the
    channel's own scale (≤ 0.5 by construction — a larger value means the
    codec is broken for that layer) and the scale range. Keys are
    '/'-joined module paths, so a bad layer in the paired Fréchet guard is
    attributable by name."""
    stats = {}
    for path, mod in _walk(params):
        w_int8, scale = quantize_weight(mod["kernel"])
        err = jnp.abs(jnp.asarray(mod["kernel"], jnp.float32)
                      - w_int8.astype(jnp.float32) * scale)
        stats["/".join(path)] = {
            "max_abs_err": float(jnp.max(err)),
            "max_err_over_scale": float(jnp.max(err / scale)),
            "scale_min": float(jnp.min(scale)),
            "scale_max": float(jnp.max(scale)),
            "shape": tuple(int(d) for d in mod["kernel"].shape),
        }
    return stats


# ---------------------------------------------------------------------------
# w8a16 matmul — XLA path
# ---------------------------------------------------------------------------

def _dequant_matmul_xla(x: jax.Array, w_int8: jax.Array, scale: jax.Array,
                        bias: Optional[jax.Array] = None) -> jax.Array:
    """``x @ (w_int8 * scale)`` without materializing the dequantized weight:
    the int8→activation-dtype convert fuses into the matmul operand read and
    the per-column scale (+ optional bias) into the f32 epilogue.
    Accumulation is f32 (``preferred_element_type``), the w8a16 contract."""
    w = w_int8.astype(x.dtype)
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y * scale
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# w8a8 — dynamic activation quantization
# ---------------------------------------------------------------------------

def quantize_act(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-TENSOR symmetric dynamic int8 quantization of an activation:
    ``scale = max|x|/127`` (1.0 for an all-zero tensor), round-to-nearest
    codes clipped to [−127, 127] — the activation half of the "w8a8" mode.
    Per-tensor (not per-channel): the scale is one scalar folded into the
    weight's per-column scales at the GEMM epilogue, so the int8×int8 MXU
    path needs no extra per-element work."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(xf / scale), -127.0, 127.0)
    return codes.astype(jnp.int8), scale


def _dequant_matmul_w8a8(x: jax.Array, w_int8: jax.Array, scale: jax.Array,
                         bias: Optional[jax.Array] = None) -> jax.Array:
    """int8×int8 GEMM with int32 MXU accumulation: activations quantized
    on the fly (per-tensor dynamic scale), both scales (+ optional bias)
    applied once in the f32 epilogue. The unfused "w8a8" reference the
    fused kernels are guard-checked against."""
    xi, xs = quantize_act(x)
    y = jax.lax.dot_general(
        xi, w_int8, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = y.astype(jnp.float32) * (xs * scale)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# w8a16 matmul — Pallas fused kernel
# ---------------------------------------------------------------------------

def _mm_kernel(*refs, n_k: int, has_bias: bool):
    """One (m-tile, n-tile, k-chunk) program: dequantize this int8 weight
    chunk to the activation dtype in VMEM, fold its partial product into the
    f32 accumulator, and on the last chunk apply the per-column scale (and
    bias, when the caller fuses it) once and emit. K is the innermost
    (sequential) grid axis, so the scratch accumulator carries across chunks
    of one output tile.

    The bias rides INSIDE the kernel (not as a caller-side epilogue) so the
    ``acc·s + b`` contraction happens at the same point in every path: the
    fused trunk kernels keep their scale-multiply and bias-add adjacent, and
    XLA:CPU contracts adjacent multiply+add into a single-rounding fma —
    with the add on the other side of the kernel boundary the unfused path
    would round twice and the f32 bitwise-parity contract would break by one
    ulp (tests/test_fusion.py pins the contract)."""
    if has_bias:
        x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, o_ref, acc_ref = refs
        b_ref = None
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                      # (bm, bk) activation dtype
    w = w_ref[...].astype(x.dtype)      # (bk, bn) int8 → activation dtype
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k_i == n_k - 1)
    def _emit():
        y = acc_ref[...] * s_ref[0]
        if has_bias:
            y = y + b_ref[0]
        o_ref[...] = y


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def _dequant_matmul_pallas(x2d: jax.Array, w_int8: jax.Array, scale: jax.Array,
                           bias: Optional[jax.Array] = None,
                           *, block_m: int = 256, block_n: int = 512,
                           block_k: int = 512) -> jax.Array:
    """Fused dequant-matmul on a 2-D ``(M, K) @ (K, N)`` problem.

    Tiling honors the TPU tile rules: K blocks are lane-width (128) aligned
    (covering the int8 (32, 128) min tile on the weight's sublane dim), N
    blocks lane-aligned, M blocks sublane (8) aligned. Zero-padding is
    inert — padded K rows of the weight contribute zero partial products,
    padded M/N rows/columns are sliced off the output.
    """
    M, K = x2d.shape
    _, N = w_int8.shape
    # pad-or-clamp to Mosaic-legal blocks (ops/tiling.py): M is the
    # activation's sublane dim (8 at f32, 16 at bf16); N is a lane dim; K is
    # the activation's LANE dim and the int8 weight's SUBLANE dim at once,
    # so it must also divide by int8's 32-sublane unit (128 % 32 == 0 —
    # folded in explicitly so the constraint survives a lane-width change)
    bm = tiling.legal_block(block_m, M, x2d.dtype)
    bn = tiling.legal_block(block_n, N, jnp.float32, lane=True)
    bk = tiling.legal_block(block_k, K, x2d.dtype, lane=True,
                            min_unit=jnp.int8)
    xp = _pad_axis(_pad_axis(x2d, 0, _round_up(M, bm)), 1, _round_up(K, bk))
    wp = _pad_axis(_pad_axis(w_int8, 0, _round_up(K, bk)), 1, _round_up(N, bn))
    sp = _pad_axis(scale.astype(jnp.float32)[None, :], 1, _round_up(N, bn))
    n_k = xp.shape[1] // bk

    inputs = [xp, wp, sp]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
    ]
    if bias is not None:
        inputs.append(_pad_axis(bias.astype(jnp.float32)[None, :], 1,
                                _round_up(N, bn)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))

    with profiling.scope("dequant_matmul/pallas"):
        out = pl.pallas_call(
            functools.partial(_mm_kernel, n_k=n_k,
                              has_bias=bias is not None),
            grid=(xp.shape[0] // bm, wp.shape[1] // bn, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]),
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=kernel_interpret(),
        )(*inputs)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# public matmul entry
# ---------------------------------------------------------------------------

def dequant_matmul(x: jax.Array, w_int8: jax.Array, scale: jax.Array,
                   *, bias: Optional[jax.Array] = None,
                   mode: str = "xla") -> jax.Array:
    """Quantized matmul over the last axis of ``x``: ``x @ (w_int8·scale)
    [+ bias]`` with f32 accumulation; returns f32 (callers cast to the
    compute dtype — one epilogue for every mode). The bias is fused into
    the kernel epilogue rather than added by the caller so the scale·acc+b
    contraction point is identical across the unfused and fused trunk paths
    (see ``_mm_kernel``). ``mode="pallas"`` runs the fused w8a16 kernel
    (``kernel_interpret`` backend policy — never the XLA form in its
    place). ``mode="w8a8"`` quantizes
    the activation too (per-tensor dynamic scale, int8×int8 GEMM) — the
    unfused reference for the fused w8a8 kernels."""
    if mode not in QUANT_MODES:
        raise ValueError(f"quant mode must be one of {QUANT_MODES}, got {mode!r}")
    if w_int8.dtype != jnp.int8:
        raise ValueError(f"w_int8 must be int8, got {w_int8.dtype}")
    if mode == "w8a8":
        return _dequant_matmul_w8a8(x, w_int8, scale, bias)
    if mode == "pallas":
        lead = x.shape[:-1]
        y = _dequant_matmul_pallas(x.reshape(-1, x.shape[-1]), w_int8,
                                   scale, bias)
        return y.reshape(*lead, w_int8.shape[-1])
    return _dequant_matmul_xla(x, w_int8, scale, bias)


# ---------------------------------------------------------------------------
# fused Mlp kernel (matmul → bias → exact GELU → matmul)
# ---------------------------------------------------------------------------

#: erf(x) ≈ x·P(x²)/Q(x²) on [−4, 4] — the float32 rational approximation
#: Eigen and XLA evaluate (coefficients highest degree first; |error| < 5e-7,
#: pinned in tests/test_quant.py)
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)


def _horner(coeffs, x):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def gelu_exact(x: jax.Array) -> jax.Array:
    """Exact (erf) GELU, ``x/2 · (1 + erf(x/√2))``, computed in float32 and
    returned in ``x``'s dtype — THE activation of every Mlp in the repo
    (models/vit.py, models/moe.py, the fused kernel below), so the fused and
    unfused trunks stay bitwise equal. erf is spelled out from mul/add/div
    because the Pallas TPU lowering has neither ``erf`` nor the ``erfc``
    that ``jax.nn.gelu(approximate=False)`` goes through."""
    xf = x.astype(jnp.float32)
    z = jnp.clip(xf * (0.5 ** 0.5), -4.0, 4.0)
    z2 = z * z
    erf = z * _horner(_ERF_P, z2) / _horner(_ERF_Q, z2)
    return (0.5 * xf * (1.0 + erf)).astype(x.dtype)


def gelu_exact_newton(x: jax.Array) -> jax.Array:
    """:func:`gelu_exact` for a Pallas body that sits on its vector work: the
    same rational erf, float32 inside, the input's dtype out, with the
    quotient taken as the EUP's reciprocal refined by two Newton steps (exact
    to float32 rounding whatever the seed: the interpreter's is a bfloat16
    reciprocal). The VPU's float32 divide costs ``block_tail``
    (ops/block_kernels.py) 0.30 ms of 2.46 a launch at the 200px sampler
    cell's shape (PERF.md section 6, PR 32)."""
    xf = x.astype(jnp.float32)
    z = jnp.clip(xf * (0.5 ** 0.5), -4.0, 4.0)
    z2 = z * z
    q = _horner(_ERF_Q, z2)
    r = pl.reciprocal(q, approx=True)
    r = r * (2.0 - q * r)
    r = r * (2.0 - q * r)
    return (0.5 * xf * (1.0 + z * _horner(_ERF_P, z2) * r)).astype(x.dtype)


def _mlp_kernel(*refs, quant: bool, w8a8: bool, has_b2: bool, cdt):
    """One M-tile program of the fused Mlp: fc1 GEMM into the f32 scratch
    accumulator, bias + exact (erf) GELU in VMEM, fc2 GEMM straight out —
    the (M, hidden) activation never exists in HBM. Weights ride whole-array
    VMEM blocks (trunk Mlp weights are ≤ a few hundred KiB); ``quant``
    selects int8 weights dequantized at the MXU feed (w8a16), ``w8a8``
    additionally feeds int8 activations (int32 accumulation, per-tensor
    scale pre-folded by the wrapper; the hidden activation requantizes per
    M-tile). Numerics mirror the unfused ``Dense → gelu → Dense`` /
    ``QuantDense → gelu → QuantDense`` compositions term for term."""
    b2_ref = None
    if quant and has_b2:
        (x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, b2_ref,
         o_ref, acc_ref) = refs
    elif quant:
        x_ref, w1_ref, s1_ref, b1_ref, w2_ref, s2_ref, o_ref, acc_ref = refs
    elif has_b2:
        x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_ref = refs
        s1_ref = s2_ref = None
    else:
        x_ref, w1_ref, b1_ref, w2_ref, o_ref, acc_ref = refs
        s1_ref = s2_ref = None
    x = x_ref[...]  # (bm, K) compute dtype (w8a8: int8)
    if w8a8:
        y1 = jax.lax.dot_general(
            x, w1_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32) * s1_ref[0]
    elif quant:
        y1 = jax.lax.dot_general(
            x, w1_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * s1_ref[0]
    else:
        y1 = jax.lax.dot_general(
            x, w1_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = y1 + b1_ref[0]  # f32 accumulator, f32 bias epilogue
    h = gelu_exact(acc_ref[...].astype(cdt))
    if w8a8:
        amax = jnp.max(jnp.abs(h.astype(jnp.float32)))
        hs = jnp.where(amax > 0, amax / 127.0, 1.0)
        hi = jnp.clip(jnp.round(h.astype(jnp.float32) / hs),
                      -127.0, 127.0).astype(jnp.int8)
        y2 = jax.lax.dot_general(
            hi, w2_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        y2 = y2 * (hs * s2_ref[0])
    elif quant:
        y2 = jax.lax.dot_general(
            h, w2_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * s2_ref[0]
    else:
        y2 = jax.lax.dot_general(
            h, w2_ref[...].astype(cdt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    if has_b2:
        # fc2 bias fused at the scale-multiply (same contraction point as
        # the unfused QuantDense / Dense epilogue — see _mm_kernel)
        y2 = y2 + b2_ref[0]
    o_ref[...] = y2  # f32; the wrapper casts to the compute dtype


def mlp_pallas(x, w1, b1, w2, b2, *, scale1=None, scale2=None,
               mode: Optional[str] = None, block_m: int = 256) -> jax.Array:
    """Fused Mlp trunk ``x @ w1 + b1 → exact GELU → @ w2 + b2`` as ONE
    Pallas kernel — replaces the two ``nn.Dense`` + ``nn.gelu`` ops in
    ``Mlp.__call__`` behind the same capability gating as the flash kernel.

    ``mode=None``: float weights (``w1``/``w2`` are the dense kernels).
    ``mode="pallas"``: w8a16 — int8 weights with per-column f32 scales.
    ``mode="w8a8"``: int8 weights AND per-tensor dynamic int8 activations.
    Returns ``x.dtype``, full bias epilogues included; backend policy as
    ``kernel_interpret``."""
    if mode not in (None, "pallas", "w8a8"):
        raise ValueError(f"mlp_pallas mode must be None, 'pallas' or "
                         f"'w8a8', got {mode!r}")
    quant = mode is not None
    if quant and (scale1 is None or scale2 is None):
        raise ValueError(f"mode={mode!r} needs scale1/scale2 (the w8a16 "
                         "per-column weight scales)")
    cdt = x.dtype
    lead, K = x.shape[:-1], x.shape[-1]
    Hf, Nout = w1.shape[-1], w2.shape[-1]
    interpret = kernel_interpret()

    if mode == "w8a8":
        xi, xs = quantize_act(x)
        x2d = xi.reshape(-1, K)
        s1_eff = scale1.astype(jnp.float32) * xs
    else:
        x2d = x.reshape(-1, K)
        s1_eff = None if scale1 is None else scale1.astype(jnp.float32)
    M = x2d.shape[0]
    bm = tiling.legal_block(block_m, M, x2d.dtype)
    xp = _pad_axis(x2d, 0, _round_up(M, bm))

    inputs = [xp, w1]
    in_specs = [pl.BlockSpec((bm, K), lambda i: (i, 0)),
                pl.BlockSpec((K, Hf), lambda i: (0, 0))]
    if quant:
        inputs.append(s1_eff[None, :])
        in_specs.append(pl.BlockSpec((1, Hf), lambda i: (0, 0)))
    inputs.append(b1.astype(jnp.float32)[None, :])
    in_specs.append(pl.BlockSpec((1, Hf), lambda i: (0, 0)))
    inputs.append(w2)
    in_specs.append(pl.BlockSpec((Hf, Nout), lambda i: (0, 0)))
    if quant:
        inputs.append(scale2.astype(jnp.float32)[None, :])
        in_specs.append(pl.BlockSpec((1, Nout), lambda i: (0, 0)))
    if b2 is not None:
        inputs.append(b2.astype(jnp.float32)[None, :])
        in_specs.append(pl.BlockSpec((1, Nout), lambda i: (0, 0)))

    with profiling.scope("mlp/pallas"):
        out = pl.pallas_call(
            functools.partial(_mlp_kernel, quant=quant,
                              w8a8=mode == "w8a8",
                              has_b2=b2 is not None, cdt=cdt),
            grid=(xp.shape[0] // bm,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, Nout), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((xp.shape[0], Nout), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bm, Hf), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(*inputs)
    return out[:M].astype(cdt).reshape(*lead, Nout)


class QuantParams(nn.Module):
    """Declares the ``{w_int8, scale[, bias]}`` leaves of a :class:`QuantDense`
    WITHOUT computing the matmul — the fused trunk kernels consume the raw
    leaves. Same param names, shapes, dtypes and initializers as QuantDense
    (and the same module path when given the same ``name``), so a fused and
    an unfused model share one param tree interchangeably and
    ``quantize_params`` output loads into either."""

    features: int
    use_bias: bool = True

    @nn.compact
    def __call__(self, in_features: int):
        w_int8 = self.param("w_int8", nn.initializers.zeros_init(),
                            (in_features, self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.features,), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           (self.features,), jnp.float32)
                if self.use_bias else None)
        return w_int8, scale, bias


class QuantDense(nn.Module):
    """Drop-in for ``nn.Dense`` over a quantized kernel: declares the
    ``{w_int8, scale[, bias]}`` leaves ``quantize_params`` produces (same
    module path/name as the dense it replaces) and computes the w8a16 matmul.
    Zero-init params make ``model.init`` legal on a quant model, but the
    intended flow is quantizing a trained float tree."""

    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    mode: str = "xla"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        w_int8 = self.param("w_int8", nn.initializers.zeros_init(),
                            (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.features,), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros_init(),
                           (self.features,), jnp.float32)
                if self.use_bias else None)
        # bias fused into the matmul epilogue — the contraction point must
        # match the fused trunk kernels' (see _mm_kernel docstring)
        y = dequant_matmul(x.astype(self.dtype), w_int8, scale, bias=bias,
                           mode=self.mode)
        return y.astype(self.dtype)
