"""Block-shape autotuning for the fused sampler-trunk kernels at the
first-class 200px geometries.

The fused kernels (ops/flash_attention.fused_trunk_attention,
ops/quant.mlp_pallas) take block shapes the same way the unfused flash
kernel does — but the 200px geometries (f32/bf16 N=2501 for the p4 model,
bf16 N=626 for p8, the dual-dtype dequant K blocks) each have a different
P001-legal block space and a different VMEM frontier. This module:

* enumerates the candidate space for each kernel family under the rules
  graftcheck's kernels layer proves (ops/tiling.legal_block units, the P003
  padding-waste ceiling) and a static VMEM model that adds the kernel's own
  temporaries to P002's blocks-and-scratch count — so a candidate that
  enumerates here is not flagged by ``graftcheck --only P``. Whether the
  chip's compiler ACCEPTS it is the compiler's to say: its scoped-VMEM
  allocation follows its own tiling and no static formula reproduces it (the
  first table committed from the blocks-and-scratch count alone was refused
  at every N=2501 row). So the model is kept an upper bound on what the
  compiler reported, and every committed row is compiled for the chip in
  tests/test_chip_compile.py;
* scores candidates with a static cost model (fewest grid programs — each
  pays a launch and, for attention, one in-kernel k/v reprojection — subject
  to the VMEM and waste ceilings);
* pins the winners into the committed :data:`TUNED_BLOCKS` table, keyed by
  ``(device kind, dtype name, geometry tag)``. Lookups for absent keys fall
  back to ``NS_FLASH_BLOCKS`` (attention) / the kernel defaults (mlp), so
  un-tuned geometries and the CPU interpreter keep working unchanged;
* offers :func:`autotune_attn` / :func:`autotune_mlp` — on-device timing
  sweeps over the legal space — for regenerating the table in a hardware
  window (``python -m ddim_cold_tpu.ops.tuning`` prints the static sweep).

Provenance: the committed entries are STATIC-model picks (this module run on
CPU), accepted by the v5e compiler, never timed; ranking them on the chip
with ``autotune_*`` is open (ROADMAP Speed item 2) and any change lands as a
table diff with the timing evidence attached.

Constants ``WASTE_THRESHOLD``/``PIPELINE_BUFFERS``/``DEVICE_KIND`` mirror
analysis/kernel_checks.py (the P-rules); tests/test_fusion.py pins them
equal so the enumerator and the verifier cannot drift apart.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ddim_cold_tpu.ops import tiling
from ddim_cold_tpu.utils import flops as flops_util

#: default device the committed table is tuned for (the bench chip) —
#: mirrors analysis/kernel_checks.DEVICE_KIND (pinned by tests/test_fusion)
DEVICE_KIND = "TPU v5 lite"
#: padding-waste ceiling, mirrors kernel_checks.WASTE_THRESHOLD (P003)
WASTE_THRESHOLD = 1.25
#: pipeline double-buffering factor, mirrors kernel_checks.PIPELINE_BUFFERS
PIPELINE_BUFFERS = 2

_F32 = 4


def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def attn_geometry(n: int, c: int, heads: int) -> str:
    """Geometry tag for a fused-attention problem (tokens, embed, heads)."""
    return f"attn_n{n}_c{c}_h{heads}"


def mlp_geometry(c: int, hidden: int, *, quant: bool = True) -> str:
    """Geometry tag for a fused-Mlp problem (embed, hidden width). The
    weight layout is part of the geometry: int8 weights (``mlp_``) stage
    4× (f32) / 2× (bf16) smaller blocks than float weights (``mlpf_``), so
    the two layouts have different VMEM frontiers and tuned block_m."""
    return f"{'mlp' if quant else 'mlpf'}_c{c}_h{hidden}"


def dequant_geometry(m: int, k: int, n: int) -> str:
    """Geometry tag for a standalone dequant-matmul problem."""
    return f"dequant_m{m}_k{k}_n{n}"


# ---------------------------------------------------------------------------
# static VMEM models — mirror the kernels' scratch/block arithmetic exactly
# ---------------------------------------------------------------------------

def attn_vmem_bytes(bq: int, bkv: int, c: int, heads: int, act_dtype,
                    *, qkv_bias: bool = True,
                    compute_dtype=None) -> int:
    """Per-program VMEM footprint of ``fused_trunk_attention`` at blocks
    (bq, bkv): in/out blocks × PIPELINE_BUFFERS plus the scratch arrays (the
    accounting graftcheck P002 applies to the kernel entry) plus the
    kernel's own temporaries, which the compiler allocates in the same
    scoped VMEM and P002 cannot see from the call: one head's (bq, bkv)
    logits and probabilities in f32 with the cast fed to the MXU, and the
    chunk's (bkv, 2C) k/v projection (f32 result — after an int32 one under
    w8a8 — and its cast). The temporaries term is an upper bound on what
    the v5e compiler reported at 40 refused block pairs of the N=2501
    geometry (f32, bf16, w8a8); it is not exact — the compiler's own tiling
    makes the true figure irregular — so every committed row is also
    compiled for the chip (tests/test_chip_compile.py)."""
    act = _itemsize(act_dtype)
    cdt = _itemsize(compute_dtype if compute_dtype is not None else act_dtype)
    w8a8 = act == 1
    blocks = (bq * c * act            # x_q
              + bkv * c * act        # x_kv
              + c * 3 * c            # w_qkv int8
              + 3 * c * _F32         # s_qkv
              + (3 * c * _F32 if qkv_bias else 0)
              + c * c                # w_proj int8
              + c * _F32             # s_proj
              + bq * c * _F32)       # out (f32)
    scratch = (bq * c * cdt          # projected q
               + bq * c * _F32      # output accumulator
               + 2 * heads * bq * tiling.LANE * _F32)  # running max / denom
    temps = (bq * bkv * (2 * _F32 + cdt)
             + bkv * 2 * c * ((2 if w8a8 else 1) * _F32 + cdt))
    return PIPELINE_BUFFERS * blocks + scratch + temps


def mlp_vmem_bytes(bm: int, k: int, hidden: int, nout: int, act_dtype,
                   *, quant: bool = True) -> int:
    """Per-program VMEM footprint of ``mlp_pallas`` at M-block ``bm``:
    blocks and scratch as graftcheck P002 counts them, plus the kernel's
    temporaries in the same scoped VMEM — the (bm, hidden) activation in f32
    through the GELU and its cast, and the (bm, nout) f32 result (upper
    bound, see :func:`attn_vmem_bytes`)."""
    act = _itemsize(act_dtype)
    w = 1 if quant else act  # float weights are staged at the act dtype
    blocks = (bm * k * act
              + k * hidden * w + hidden * _F32       # w1 (+ b1)
              + (hidden * _F32 if quant else 0)      # s1
              + hidden * nout * w
              + (nout * _F32 if quant else 0)        # s2
              + bm * nout * _F32)                    # out (f32)
    scratch = bm * hidden * _F32
    temps = bm * hidden * (2 * _F32 + max(act, 2)) + bm * nout * _F32
    return PIPELINE_BUFFERS * blocks + scratch + temps


def dequant_vmem_bytes(bm: int, bn: int, bk: int, act_dtype) -> int:
    """Per-program VMEM footprint of ``_dequant_matmul_pallas``."""
    act = _itemsize(act_dtype)
    blocks = bm * bk * act + bk * bn + bn * _F32 + bm * bn * _F32
    return PIPELINE_BUFFERS * blocks + bm * bn * _F32


# ---------------------------------------------------------------------------
# legal candidate enumeration (the P001/P002/P003 space)
# ---------------------------------------------------------------------------

def _vmem_budget(device_kind: str) -> int:
    budget = flops_util.vmem_bytes(device_kind)
    if budget is None:
        raise LookupError(
            f"no VMEM capacity for device kind {device_kind!r} in "
            "utils/flops.VMEM_BYTES — add the chip there before tuning for it")
    return budget


def _waste_ok(n: int, block: int) -> bool:
    return tiling.round_up(n, block) / n <= WASTE_THRESHOLD


def _seq_block_candidates(n: int, dtype) -> list[int]:
    """Legal sequence-axis block sizes for an array dim of ``n``: every
    unit-multiple up to the unit-padded dim (the single-block case last)."""
    unit = tiling.sublane_unit(dtype)
    full = tiling.round_up(n, unit)
    out = []
    b = unit
    while b < full:
        if _waste_ok(n, b):
            out.append(b)
        b += unit
    out.append(full)  # single block spans the (unit-padded) dim
    return out


def attn_candidates(n: int, c: int, heads: int, act_dtype, *,
                    device_kind: str = DEVICE_KIND, qkv_bias: bool = True,
                    compute_dtype=None) -> list[tuple[int, int]]:
    """All (block_q, block_kv) pairs legal for ``fused_trunk_attention`` at
    this geometry: tile-unit multiples (P001), padding waste ≤ 1.25 on both
    sequence paddings (P003), double-buffered VMEM within the device budget
    (P002)."""
    budget = _vmem_budget(device_kind)
    cands = []
    for bq in _seq_block_candidates(n, act_dtype):
        for bkv in _seq_block_candidates(n, act_dtype):
            if attn_vmem_bytes(bq, bkv, c, heads, act_dtype,
                               qkv_bias=qkv_bias,
                               compute_dtype=compute_dtype) <= budget:
                cands.append((bq, bkv))
    return cands


def mlp_candidates(m: int, k: int, hidden: int, nout: int, act_dtype, *,
                   device_kind: str = DEVICE_KIND,
                   quant: bool = True) -> list[int]:
    """All legal ``block_m`` values for ``mlp_pallas`` at this geometry."""
    budget = _vmem_budget(device_kind)
    return [bm for bm in _seq_block_candidates(m, act_dtype)
            if mlp_vmem_bytes(bm, k, hidden, nout, act_dtype,
                              quant=quant) <= budget]


def dequant_candidates(m: int, k: int, n: int, act_dtype, *,
                       device_kind: str = DEVICE_KIND,
                       steps=(128, 256, 512, 1024, 2048)
                       ) -> list[tuple[int, int, int]]:
    """Legal (block_m, block_n, block_k) triples for the dequant matmul —
    the K axis is the dual-dtype case: the activation's LANE dim and the
    int8 weight's SUBLANE dim must both divide the one block
    (tiling.legal_block min_unit=jnp.int8)."""
    import jax.numpy as jnp

    budget = _vmem_budget(device_kind)
    cands = []
    bms = sorted({tiling.legal_block(s, m, act_dtype) for s in steps})
    bns = sorted({tiling.legal_block(s, n, jnp.float32, lane=True)
                  for s in steps})
    bks = sorted({tiling.legal_block(s, k, act_dtype, lane=True,
                                     min_unit=jnp.int8) for s in steps})
    for bm in bms:
        if not _waste_ok(m, bm):
            continue
        for bn in bns:
            for bk in bks:
                if dequant_vmem_bytes(bm, bn, bk, act_dtype) <= budget:
                    cands.append((bm, bn, bk))
    return cands


# ---------------------------------------------------------------------------
# static cost model + committed table
# ---------------------------------------------------------------------------

def pick_attn(n: int, c: int, heads: int, act_dtype, *,
              device_kind: str = DEVICE_KIND, qkv_bias: bool = True,
              compute_dtype=None) -> Optional[tuple[int, int]]:
    """Static pick: every (q-block, kv-chunk) program pays a launch and one
    in-kernel k/v reprojection GEMM, so take the legal pair with the fewest
    programs; among those the fewest q blocks (total reprojection work scales
    with their number), then the largest blocks."""
    cands = attn_candidates(n, c, heads, act_dtype,
                            device_kind=device_kind, qkv_bias=qkv_bias,
                            compute_dtype=compute_dtype)
    if not cands:
        return None
    n_blk = lambda b: tiling.round_up(n, b) // b  # noqa: E731
    return min(cands, key=lambda bqkv: (n_blk(bqkv[0]) * n_blk(bqkv[1]),
                                        n_blk(bqkv[0]),
                                        -bqkv[0], -bqkv[1]))


def pick_mlp(m: int, k: int, hidden: int, nout: int, act_dtype, *,
             device_kind: str = DEVICE_KIND, quant: bool = True
             ) -> Optional[int]:
    """Static pick: largest legal M block — fewest weight-block revisits."""
    cands = mlp_candidates(m, k, hidden, nout, act_dtype,
                           device_kind=device_kind, quant=quant)
    return max(cands) if cands else None


#: committed tuned blocks, keyed (device kind, dtype name, geometry tag).
#: Values: attention (block_q, block_kv); mlp (block_m,); dequant
#: (block_m, block_n, block_k). Static-model picks over the P001-legal
#: space (regenerate: ``python -m ddim_cold_tpu.ops.tuning``); absent keys
#: fall back to NS_FLASH_BLOCKS / kernel defaults (see lookup_*). The int8
#: rows are the w8a8 activations (weights are int8 in every fused row).
TUNED_BLOCKS: dict[tuple[str, str, str], tuple[int, ...]] = {
    # 200px/p4 north-star trunk (N=2501, C=256, H=4) — f32, bf16, w8a8
    ("TPU v5 lite", "float32", "attn_n2501_c256_h4"): (544, 840),
    ("TPU v5 lite", "bfloat16", "attn_n2501_c256_h4"): (512, 1264),
    ("TPU v5 lite", "int8", "attn_n2501_c256_h4"): (864, 512),
    # 200px/p8 trunk (N=626, C=384, H=12) — two q blocks, one kv chunk
    ("TPU v5 lite", "float32", "attn_n626_c384_h12"): (328, 632),
    ("TPU v5 lite", "bfloat16", "attn_n626_c384_h12"): (384, 640),
    ("TPU v5 lite", "int8", "attn_n626_c384_h12"): (320, 640),
    # fused Mlp at the sampler's flattened row count (16 rows × 2501 tokens)
    ("TPU v5 lite", "float32", "mlp_c256_h256"): (1784,),
    ("TPU v5 lite", "bfloat16", "mlp_c256_h256"): (2144,),
    ("TPU v5 lite", "int8", "mlp_c256_h256"): (2272,),
    ("TPU v5 lite", "float32", "mlp_c384_h384"): (1168,),
    ("TPU v5 lite", "bfloat16", "mlp_c384_h384"): (1392,),
    ("TPU v5 lite", "int8", "mlp_c384_h384"): (1504,),
    # float-weight Mlp (quant=None): weight blocks are 4×/2× larger than the
    # int8 rows above, so the VMEM frontier sits at a smaller block_m
    ("TPU v5 lite", "float32", "mlpf_c256_h256"): (1704,),
    ("TPU v5 lite", "bfloat16", "mlpf_c256_h256"): (2112,),
    ("TPU v5 lite", "float32", "mlpf_c384_h384"): (1040,),
    ("TPU v5 lite", "bfloat16", "mlpf_c384_h384"): (1344,),
    # standalone dequant matmul at the 200px qkv/proj shapes (provenance for
    # the _dequant_matmul_pallas defaults; the dual-dtype K legality case)
    ("TPU v5 lite", "bfloat16", "dequant_m40016_k256_n768"): (2048, 512, 256),
    ("TPU v5 lite", "bfloat16", "dequant_m40016_k256_n256"): (2048, 256, 256),
}


def lookup(device_kind: str, dtype, geometry: str
           ) -> Optional[tuple[int, ...]]:
    """Tuned blocks for (device kind, dtype, geometry), or None. The device
    kind is prefix-matched like utils/flops peak tables (a 'TPU v5 lite'
    entry serves 'TPU v5 lite core …' kinds)."""
    name = str(np.dtype(dtype))
    best = None
    for (kind, dt, geom), blocks in TUNED_BLOCKS.items():
        if dt == name and geom == geometry and device_kind.startswith(kind):
            if best is None or len(kind) > best[0]:
                best = (len(kind), blocks)
    return best[1] if best else None


def _local_device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def attn_blocks(n: int, c: int, heads: int, act_dtype, *,
                device_kind: Optional[str] = None) -> tuple[int, int]:
    """(block_q, block_kv) for a fused-attention problem: the tuned entry
    when the (device, dtype, geometry) key is present, else the
    ``NS_FLASH_BLOCKS`` fallback (which legal_block clamps to this N)."""
    from ddim_cold_tpu.ops.flash_attention import NS_FLASH_BLOCKS

    kind = device_kind if device_kind is not None else _local_device_kind()
    tuned = lookup(kind, act_dtype, attn_geometry(n, c, heads))
    if tuned is not None and len(tuned) == 2:
        return (int(tuned[0]), int(tuned[1]))
    return NS_FLASH_BLOCKS


def mlp_block_m(c: int, hidden: int, act_dtype, *,
                quant: bool = True, device_kind: Optional[str] = None,
                default: int = 256) -> int:
    """block_m for a fused-Mlp problem; kernel default when un-tuned.
    ``quant`` selects the weight-layout half of the geometry key (int8 vs
    float weights — see mlp_geometry)."""
    kind = device_kind if device_kind is not None else _local_device_kind()
    tuned = lookup(kind, act_dtype, mlp_geometry(c, hidden, quant=quant))
    if tuned is not None and len(tuned) == 1:
        return int(tuned[0])
    return default


# ---------------------------------------------------------------------------
# on-device timing sweeps (regenerate TUNED_BLOCKS in a hardware window)
# ---------------------------------------------------------------------------

def _time_fn(fn, *args, iters: int = 10) -> float:
    import time

    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def autotune_attn(batch: int, n: int, c: int, heads: int, act_dtype, *,
                  mode: str = "pallas", iters: int = 10) -> list[dict]:
    """Time ``fused_trunk_attention`` over the legal candidate space on the
    LOCAL device; returns candidates sorted fastest-first. Meant for a TPU
    window — on CPU the interpreter timing is not meaningful (the static
    pick stands in; see TUNED_BLOCKS provenance)."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.ops import flash_attention as fa

    kind = _local_device_kind()
    cdt = jnp.dtype(act_dtype) if mode != "w8a8" else jnp.float32
    xdt = jnp.int8 if mode == "w8a8" else cdt
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, n, c), jnp.float32).astype(cdt)
    w_qkv = jax.random.randint(key, (c, 3 * c), -127, 128, jnp.int8)
    w_proj = jax.random.randint(key, (c, c), -127, 128, jnp.int8)
    s_qkv = jnp.full((3 * c,), 1e-2, jnp.float32)
    s_proj = jnp.full((c,), 1e-2, jnp.float32)
    b = jnp.zeros((3 * c,), jnp.float32)
    bp = jnp.zeros((c,), jnp.float32)
    results = []
    for bq, bkv in attn_candidates(n, c, heads, xdt, device_kind=kind,
                                   compute_dtype=cdt):
        fn = jax.jit(lambda xx, _bq=bq, _bkv=bkv: fa.fused_trunk_attention(
            xx, w_qkv, s_qkv, b, w_proj, s_proj, bp, num_heads=heads,
            scale=(c // heads) ** -0.5, block_q=_bq, block_kv=_bkv,
            mode=mode))
        results.append({"block_q": bq, "block_kv": bkv,
                        "seconds": _time_fn(fn, x, iters=iters)})
    return sorted(results, key=lambda r: r["seconds"])


def autotune_mlp(m: int, k: int, hidden: int, act_dtype, *,
                 mode: Optional[str] = "pallas", iters: int = 10
                 ) -> list[dict]:
    """Time ``mlp_pallas`` over the legal block_m space on the LOCAL device;
    fastest first. Same hardware-window caveat as autotune_attn."""
    import jax
    import jax.numpy as jnp

    from ddim_cold_tpu.ops import quant as q

    kind = _local_device_kind()
    cdt = jnp.dtype(act_dtype)
    xdt = jnp.int8 if mode == "w8a8" else cdt
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, k), jnp.float32).astype(cdt)
    if mode is None:
        w1 = jax.random.normal(key, (k, hidden), jnp.float32)
        w2 = jax.random.normal(key, (hidden, k), jnp.float32)
        s1 = s2 = None
    else:
        w1 = jax.random.randint(key, (k, hidden), -127, 128, jnp.int8)
        w2 = jax.random.randint(key, (hidden, k), -127, 128, jnp.int8)
        s1 = jnp.full((hidden,), 1e-2, jnp.float32)
        s2 = jnp.full((k,), 1e-2, jnp.float32)
    b1 = jnp.zeros((hidden,), jnp.float32)
    b2 = jnp.zeros((k,), jnp.float32)
    results = []
    for bm in mlp_candidates(m, k, hidden, k, xdt, device_kind=kind,
                             quant=mode is not None):
        fn = jax.jit(lambda xx, _bm=bm: q.mlp_pallas(
            xx, w1, b1, w2, b2, scale1=s1, scale2=s2, mode=mode,
            block_m=_bm))
        results.append({"block_m": bm,
                        "seconds": _time_fn(fn, x, iters=iters)})
    return sorted(results, key=lambda r: r["seconds"])


def _main() -> None:  # pragma: no cover — table-regeneration helper
    """Print the static picks for every committed geometry (the TUNED_BLOCKS
    provenance): ``python -m ddim_cold_tpu.ops.tuning``."""
    import jax.numpy as jnp

    rows = 16  # analysis/entries.NS_ROWS
    geoms = [(2501, 256, 4), (626, 384, 12)]
    for n, c, h in geoms:
        for dt in (jnp.float32, jnp.bfloat16, jnp.int8):
            cdt = jnp.float32 if dt == jnp.int8 else dt
            print(attn_geometry(n, c, h), np.dtype(dt),
                  pick_attn(n, c, h, dt, compute_dtype=cdt))
        for dt in (jnp.float32, jnp.bfloat16, jnp.int8):
            print(mlp_geometry(c, c), np.dtype(dt),
                  pick_mlp(rows * n, c, c, c, dt))
        for dt in (jnp.float32, jnp.bfloat16):  # float weights: no int8 act
            print(mlp_geometry(c, c, quant=False), np.dtype(dt),
                  pick_mlp(rows * n, c, c, c, dt, quant=False))
    for nout in (768, 256):
        cands = dequant_candidates(rows * 2501, 256, nout, jnp.bfloat16)
        print(dequant_geometry(rows * 2501, 256, nout),
              "bfloat16", max(cands) if cands else None)


if __name__ == "__main__":  # pragma: no cover
    _main()
