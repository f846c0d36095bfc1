"""Analytic FLOP accounting for the DiffusionViT — the MFU denominator.

The reference never measures utilization (its only perf record is wall-clock
``time_cost`` lines, multi_gpu_trainer.py:135-138); to say how far a step is
from the chip's ceiling we count the model's matmul FLOPs analytically and
divide by (peak · step_time). Elementwise/softmax/LN work is ignored — on TPU
those ride the VPU and are fused into the GEMM pipeline; standard MFU practice
counts MXU FLOPs only.

Peak numbers are per-chip bf16 dense (not sparse) from published TPU specs,
keyed by ``jax.devices()[0].device_kind``.
"""

from __future__ import annotations

#: bf16 dense peak TFLOP/s per chip by jax device_kind (prefix-matched).
PEAK_BF16_TFLOPS = {
    "TPU v6": 918.0,  # Trillium
    "TPU v5p": 459.0,
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5": 459.0,
    "TPU v4 lite": 138.0,  # v4i
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}

#: int8 dense peak TOP/s per chip — the MXU rate w8a16 trunk GEMMs are
#: entitled to (ops/quant.py). v5e/v6e double their bf16 rate at int8;
#: v4 and earlier have no faster int8 path, so their entry equals bf16 and
#: mixed-peak MFU degenerates to the plain number there.
PEAK_INT8_TOPS = {
    "TPU v6": 1836.0,  # Trillium
    "TPU v5p": 918.0,
    "TPU v5 lite": 393.0,  # v5e
    "TPU v5": 918.0,
    "TPU v4 lite": 138.0,
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 46.0,
}


#: HBM bandwidth GB/s per chip (published specs, same prefix-match keys as
#: the peak tables) — the roofline's other axis: a scope whose arithmetic
#: intensity sits below peak/bandwidth is bandwidth-bound no matter how the
#: kernel schedules its MXU passes.
HBM_GB_S = {
    "TPU v6": 1638.0,  # Trillium
    "TPU v5p": 2765.0,
    "TPU v5 lite": 819.0,  # v5e
    "TPU v5": 2765.0,
    "TPU v4 lite": 614.0,  # v4i
    "TPU v4": 1228.0,
    "TPU v3": 900.0,
    "TPU v2": 700.0,
}


#: per-core VMEM capacity in bytes (published specs / pallas guide; same
#: prefix-match keys). This is the budget every Pallas kernel's per-program
#: footprint — in/out blocks double-buffered by the pipeline, plus VMEM
#: scratch — must fit inside (graftcheck P002, analysis/kernel_checks.py).
VMEM_BYTES = {
    "TPU v6": 32 << 20,  # Trillium: 32 MiB
    "TPU v5p": 16 << 20,
    "TPU v5 lite": 16 << 20,  # v5e — the bench chip
    "TPU v5": 16 << 20,
    "TPU v4": 16 << 20,
    "TPU v3": 16 << 20,
    "TPU v2": 16 << 20,
}

#: per-chip HBM capacity in bytes (published specs) — the budget a served
#: program's statically estimated peak live bytes must fit inside
#: (graftcheck M001, analysis/memory_checks.py).
HBM_BYTES = {
    "TPU v6": 32 << 30,  # Trillium
    "TPU v5p": 95 << 30,
    "TPU v5 lite": 16 << 30,  # v5e — the bench chip
    "TPU v5": 95 << 30,
    "TPU v4 lite": 8 << 30,  # v4i
    "TPU v4": 32 << 30,
    "TPU v3": 32 << 30,
    "TPU v2": 16 << 30,
}


def _prefix_lookup(table: dict, device_kind: str) -> float | None:
    best = None
    for kind, peak in table.items():
        if device_kind.startswith(kind) and (best is None or len(kind) > best[0]):
            best = (len(kind), peak)
    return best[1] if best else None


def peak_tflops(device_kind: str) -> float | None:
    """Longest-prefix match of the device kind; None when unknown (CPU etc.)."""
    return _prefix_lookup(PEAK_BF16_TFLOPS, device_kind)


def require_peak_tflops(device_kind: str) -> float:
    """:func:`peak_tflops` for a path that MEASURES: an accelerator with no
    entry in the table is an error there, not an MFU of ``None``."""
    peak = peak_tflops(device_kind)
    if peak is None:
        raise LookupError(
            f"no peak TFLOP/s for device kind {device_kind!r} in "
            "utils/flops.PEAK_BF16_TFLOPS — add the chip's published peaks "
            "before measuring on it")
    return peak


def peak_int8_tops(device_kind: str) -> float | None:
    """int8 dense peak TOP/s; None when unknown."""
    return _prefix_lookup(PEAK_INT8_TOPS, device_kind)


def mixed_peak_tflops(device_kind: str, int8_fraction: float = 0.0) -> float | None:
    """Effective peak when ``int8_fraction`` of a step's matmul FLOPs run at
    the int8 rate and the rest at bf16 — the time-weighted harmonic mix
    (each fraction contributes its FLOPs/rate to the ideal step time).
    With no int8 table entry the whole step is charged at bf16 — MFU stays
    conservative rather than flattering."""
    bf16 = peak_tflops(device_kind)
    if bf16 is None:
        return None
    f = min(max(float(int8_fraction), 0.0), 1.0)
    if f == 0.0:
        return bf16
    int8 = peak_int8_tops(device_kind) or bf16
    return 1.0 / (f / int8 + (1.0 - f) / bf16)


def vmem_bytes(device_kind: str) -> int | None:
    """Per-core VMEM capacity in bytes; None when unknown (CPU etc.)."""
    v = _prefix_lookup(VMEM_BYTES, device_kind)
    return None if v is None else int(v)


def hbm_bytes(device_kind: str) -> int | None:
    """Per-chip HBM capacity in bytes; None when unknown (CPU etc.)."""
    v = _prefix_lookup(HBM_BYTES, device_kind)
    return None if v is None else int(v)


def hbm_gb_s(device_kind: str) -> float | None:
    """HBM bandwidth GB/s for the chip; None when unknown (CPU etc.)."""
    return _prefix_lookup(HBM_GB_S, device_kind)


def ridge_flops_per_byte(device_kind: str,
                         int8_fraction: float = 0.0) -> float | None:
    """The roofline ridge point: arithmetic intensity (FLOPs/byte) at which
    peak compute and peak HBM bandwidth take equal time. Scopes below it are
    HBM-bound, above it compute-bound. None when either peak is unknown."""
    peak = mixed_peak_tflops(device_kind, int8_fraction)
    bw = hbm_gb_s(device_kind)
    if peak is None or bw is None:
        return None
    return peak * 1e12 / (bw * 1e9)


def vit_forward_flops(*, img_size=(64, 64), patch_size=8, embed_dim=384,
                      depth=7, num_heads=12, mlp_ratio=1.0, in_chans=3) -> float:
    """Matmul FLOPs (2·MACs) for one image's forward pass.

    Per block (dim D, tokens N): qkv 3·N·D², attn scores+values 2·N²·D,
    proj N·D², MLP 2·N·D²·mlp_ratio. Plus patch-embed N·P²·C·D in and the
    head's N·D·P²·C out (ViT.py:158-218 structure).
    """
    H, W = img_size
    n = (H // patch_size) * (W // patch_size) + 1  # +1 cls token
    d = embed_dim
    per_block = 3 * n * d * d + 2 * n * n * d + n * d * d + 2 * n * d * d * mlp_ratio
    patch = n * (patch_size * patch_size * in_chans) * d  # embed + head are
    return 2.0 * (depth * per_block + 2 * patch)          # the same GEMM shape


def vit_trunk_gemm_fraction(*, img_size=(64, 64), patch_size=8, embed_dim=384,
                            depth=7, num_heads=12, mlp_ratio=1.0,
                            in_chans=3) -> float:
    """Fraction of the forward's matmul FLOPs in the quantized trunk denses
    (qkv + proj + MLP; attention score/value GEMMs and patch/head stay
    bf16) — the ``int8_fraction`` a w8a16 forward feeds ``mfu``, and the
    analytic-ceiling input for PERF.md's quantization section."""
    H, W = img_size
    n = (H // patch_size) * (W // patch_size) + 1
    d = embed_dim
    dense = depth * (3 * n * d * d + n * d * d + 2 * n * d * d * mlp_ratio)
    attn = depth * 2 * n * n * d
    patch = 2 * n * (patch_size * patch_size * in_chans) * d
    return dense / (dense + attn + patch)


def train_step_flops(batch: int, **model_kwargs) -> float:
    """fwd + bwd ≈ 3× forward (grads w.r.t. inputs and weights each cost one
    forward's worth of matmuls)."""
    return 3.0 * batch * vit_forward_flops(**model_kwargs)


def mfu(flops_per_step: float, step_seconds: float, device_kind: str,
        n_devices: int = 1, int8_fraction: float = 0.0) -> float | None:
    """``int8_fraction`` > 0 charges that share of the FLOPs at the chip's
    int8 peak (w8a16 trunks, ops/quant.py) — the denominator grows, so a
    quantized run's MFU stays honest instead of flattering."""
    peak = mixed_peak_tflops(device_kind, int8_fraction)
    if peak is None or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak * 1e12 * n_devices)
