"""utils/flops.py — the chip's VMEM and HBM capacities by device kind: what
the kernels' VMEM model, the tuner and graftcheck's P002 / M001 budget
against. A wrong row admits a kernel the chip refuses, or refuses one it
runs."""

import pytest

from ddim_cold_tpu.ops import tuning
from ddim_cold_tpu.utils import flops

MIB, GIB = 1 << 20, 1 << 30


@pytest.mark.parametrize("kind,vmem,hbm", [
    # every kind of the tables, against the published figure
    ("TPU v2", 16 * MIB, 16 * GIB),
    ("TPU v3", 16 * MIB, 32 * GIB),
    ("TPU v4", 16 * MIB, 32 * GIB),
    ("TPU v4 lite", 16 * MIB, 8 * GIB),  # v4i: its own HBM row, v4's VMEM
    ("TPU v5", 16 * MIB, 95 * GIB),
    ("TPU v5 lite", 16 * MIB, 16 * GIB),  # v5e, the bench chip: not "TPU v5"
    ("TPU v5p", 16 * MIB, 95 * GIB),
    ("TPU v6", 32 * MIB, 32 * GIB),
    # the longest prefix wins; what follows it is ignored
    ("TPU v6 lite", 32 * MIB, 32 * GIB),
    ("TPU v5 litepod-8", 16 * MIB, 16 * GIB),
    # a kind the tables do not know: None, never a default budget
    ("cpu", None, None),
    ("TPU v9 imaginary", None, None),
    ("", None, None),
])
def test_capacities_by_device_kind(kind, vmem, hbm):
    assert flops.vmem_bytes(kind) == vmem
    assert flops.hbm_bytes(kind) == hbm
    for got in (flops.vmem_bytes(kind), flops.hbm_bytes(kind)):
        assert got is None or type(got) is int


def test_the_kernels_budget_is_the_bench_chips_row():
    """``flash_attention._SCOPED_VMEM_BYTES`` and graftcheck's default device
    kind read the v5e row: 16 MiB a core, 16 GiB a chip."""
    from ddim_cold_tpu.analysis import memory_checks
    from ddim_cold_tpu.ops import flash_attention as fa

    assert tuning.DEVICE_KIND == memory_checks.DEVICE_KIND == "TPU v5 lite"
    assert fa._SCOPED_VMEM_BYTES == flops.vmem_bytes("TPU v5 lite") == 16 * MIB
    assert flops.hbm_bytes(memory_checks.DEVICE_KIND) == 16 * GIB


@pytest.mark.parametrize("lookup", [
    lambda kind: tuning.attn_candidates(2501, 256, 4, "bfloat16",
                                        device_kind=kind),
    lambda kind: tuning.mlp_candidates(40016, 256, 256, 256, "bfloat16",
                                       device_kind=kind),
    lambda kind: tuning.dequant_candidates(40016, 256, 768, "bfloat16",
                                           device_kind=kind),
], ids=["attn_vmem", "mlp_vmem", "dequant_vmem"])
def test_unknown_device_kind_raises_on_measuring_paths(lookup):
    """A chip the tables do not know is an error where something is tuned for
    it — never a default budget."""
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        lookup("TPU v9 imaginary")
    lookup("TPU v5 lite")  # the chip there is: no error
