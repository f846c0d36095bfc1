"""Analytic FLOP accounting (utils/flops.py) — the MFU denominator must be
trustworthy or every reported MFU is fiction."""

import numpy as np
import pytest

from ddim_cold_tpu.models import MODEL_CONFIGS
from ddim_cold_tpu.ops import tuning
from ddim_cold_tpu.utils import flops


def test_vit_forward_flops_counts_matmuls_exactly():
    """Hand-count for a tiny config: per block 6·N·D² + 2·N²·D MACs
    (qkv 3ND², proj ND², mlp 2ND² at ratio 1, attention 2N²D), plus the
    patch-embed and head GEMMs (N·P²C·D each); FLOPs = 2·MACs."""
    img, p, d, depth, ratio = (8, 8), 4, 16, 3, 1.0
    n = (8 // 4) * (8 // 4) + 1  # 5 tokens
    per_block = 6 * n * d * d + 2 * n * n * d
    embed_head = 2 * n * (p * p * 3) * d
    want = 2.0 * (depth * per_block + embed_head)
    got = flops.vit_forward_flops(img_size=img, patch_size=p, embed_dim=d,
                                  depth=depth, num_heads=2, mlp_ratio=ratio)
    assert got == want


def test_train_step_is_three_forwards():
    fwd = flops.vit_forward_flops(mlp_ratio=1.0, **MODEL_CONFIGS["vit_tiny"])
    assert flops.train_step_flops(32, mlp_ratio=1.0,
                                  **MODEL_CONFIGS["vit_tiny"]) == 3 * 32 * fwd


def test_vit_tiny_magnitude():
    """vit_tiny (7.2M params, 65 tokens) forward ≈ 0.87 GF — the PERF.md
    number; order-of-magnitude pin against accidental unit slips."""
    fwd = flops.vit_forward_flops(mlp_ratio=1.0, **MODEL_CONFIGS["vit_tiny"])
    assert 0.5e9 < fwd < 1.5e9


def test_peak_lookup_prefix_match():
    assert flops.peak_tflops("TPU v5 lite") == 197.0
    assert flops.peak_tflops("TPU v5p") == 459.0
    assert flops.peak_tflops("TPU v4") == 275.0
    assert flops.peak_tflops("TPU v6 lite") == 918.0
    assert flops.peak_tflops("cpu") is None


def test_mfu_math():
    # 1 TFLOP of work in 10 ms on a 100-TFLOP/s chip → 100 TF/s·s⁻¹... :
    # mfu = 1e12 / (0.01 · 100e12) = 1.0 exactly at peak
    assert np.isclose(flops.mfu(1e12, 0.01, "TPU v5 lite"),
                      1e12 / (0.01 * 197e12))
    assert flops.mfu(1e12, 0.0, "TPU v5 lite") is None
    assert flops.mfu(1e12, 0.01, "unknown-chip") is None


@pytest.mark.parametrize("lookup", [
    lambda kind: flops.require_peak_tflops(kind),
    lambda kind: tuning.attn_candidates(2501, 256, 4, "bfloat16",
                                        device_kind=kind),
    lambda kind: tuning.mlp_candidates(40016, 256, 256, 256, "bfloat16",
                                       device_kind=kind),
    lambda kind: tuning.dequant_candidates(40016, 256, 768, "bfloat16",
                                           device_kind=kind),
], ids=["peak", "attn_vmem", "mlp_vmem", "dequant_vmem"])
def test_unknown_device_kind_raises_on_measuring_paths(lookup):
    """A chip the tables do not know is an error where something is measured
    or tuned for it — never a default budget or an MFU of None."""
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        lookup("TPU v9 imaginary")
    lookup("TPU v5 lite")  # the chip there is: no error
