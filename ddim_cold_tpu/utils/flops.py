"""The chip's two capacities, by ``jax.devices()[0].device_kind``: VMEM a
core (what a Pallas program's blocks and scratch must fit in) and HBM a chip
(what a served program's live bytes must fit in). Published specs,
longest-prefix matched; a kind the tables do not know gives ``None``.

The analytic FLOP counts and peak rates this module was named for moved to
``benchmark/costs.py`` (PR 23), which imports nothing from here.
"""

from __future__ import annotations

#: per-core VMEM capacity in bytes (published specs / pallas guide): the
#: budget every Pallas kernel's per-program footprint — in/out blocks
#: double-buffered by the pipeline, plus VMEM scratch — must fit inside
#: (graftcheck P002, analysis/kernel_checks.py).
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


def _prefix_lookup(table: dict, device_kind: str) -> int | None:
    best = None
    for kind, capacity in table.items():
        if device_kind.startswith(kind) and (best is None or len(kind) > best[0]):
            best = (len(kind), capacity)
    return best[1] if best else None


def vmem_bytes(device_kind: str) -> int | None:
    """Per-core VMEM capacity in bytes; None when unknown (CPU etc.)."""
    return _prefix_lookup(VMEM_BYTES, device_kind)


def hbm_bytes(device_kind: str) -> int | None:
    """Per-chip HBM capacity in bytes; None when unknown (CPU etc.)."""
    return _prefix_lookup(HBM_BYTES, device_kind)
