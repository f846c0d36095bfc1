"""GRAFT-P001..P003 — static Pallas kernel-geometry verification.

The one class of failure that has actually burned a chip window is
statically decidable: the r04 north-star died on a 200px Mosaic
block-divisibility error that CPU interpret mode (what CI runs) does not
enforce. This layer walks every ``pallas_call`` eqn in the abstract traces
graftcheck already builds — the J006 serve sweep, the build/train entries,
and the first-class 200px kernel entries (``entries.kernel_entries``) — and
re-derives kernel legality from the raw eqn geometry, deliberately NOT by
calling ``ops/tiling.legal_block``: the pass must catch a call site that
bypassed (or a regression inside) the legalizer, so it keeps its own copy
of the Mosaic tile table and applies the rule to what the trace actually
contains.

**P001 — tile legality.** Per block mapping, each of the block's last two
dims must be a multiple of the dtype's minimum tile (sublane × lane: f32
(8, 128), bf16/f16 (16, 128), int8 (32, 128)) or span the whole array dim;
the array dim must additionally be a multiple of the block (the in-tree
pad-to-block-multiple policy — the exact invariant whose violation killed
r04), except on the sublane axis of the kernels named in
``RAGGED_SUBLANE_OK``, which are written for a token axis that ends inside
the last block. The dequant matmul's dual-dtype K constraint (activation lane dim AND
int8 weight sublane dim at once) needs no special case: the shared K block
size appears in two block mappings, each checked against its own dtype.
P001 also demands a fully STATIC grid: a ``np.int64`` grid entry silently
becomes a dynamic grid dim, making the geometry unprovable (and forfeiting
static scheduling) — the in-tree bug the first run of this pass found in
``tiling.legal_block``'s lcm arithmetic.

**P002 — VMEM fit.** Per program instance the pipeline holds each in/out
block double-buffered plus every ``pltpu.VMEM`` scratch operand; the sum
must fit the per-device-kind VMEM capacity (``utils/flops.VMEM_BYTES``).

**P003 — padding waste.** ``round_up(dim, block) / dim`` over the block
geometry — and, when the entry registers a logical token count (N=2501 at
200px; arrays reach the kernel pre-padded, so the eqn alone can't see the
logical extent), the padded extent over the LOGICAL one. A block choice
that inflates compute past the threshold is flagged before it burns chip
time.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from ddim_cold_tpu.analysis import jaxpr_checks
from ddim_cold_tpu.analysis.findings import Finding

#: the device kind the static budgets default to — the bench chip (v5e).
#: Proving fit on the smallest-VMEM/HBM kind we actually run keeps every
#: bigger chip safe for free.
DEVICE_KIND = "TPU v5 lite"

#: independent copy of the Mosaic minimum tile table, keyed by itemsize —
#: (sublane, lane). Deliberately NOT imported from ops/tiling: the pass
#: must re-derive legality so a legalizer regression is caught, not
#: trusted (tests cross-check the two tables agree).
MIN_TILE = {4: (8, 128), 2: (16, 128), 1: (32, 128)}

#: the Pallas pipeline keeps each in/out block double-buffered (copy-in of
#: block i+1 overlaps compute on block i)
PIPELINE_BUFFERS = 2

#: P003 threshold: padded compute over logical compute. The 200px flash
#: q-axis padding (2560/2501 at bq=512) is 1.024, the streamed-kv sweep
#: worst case (3072/2501 at bkv=1024) 1.228 — real geometry sits well
#: under; a careless 2048-block at N=2501 (4096/2501 = 1.64) trips it.
WASTE_THRESHOLD = 1.25


#: kernels written for a token axis that ends INSIDE the last block. Mosaic
#: accepts a partial final block whose shape is tile-legal: what a program
#: reads past the edge is unspecified and what it writes there is dropped
#: (compiled for TPU v5 lite and run on the chip at 2,501 tokens: PERF.md
#: section 6, PR 27 and PR 29). The body has to make the unspecified part
#: harmless — the flash forward masks K's columns and zeroes V's rows past
#: the sequence, and a q row past it feeds only its own, dropped, output row;
#: ``dq`` selects the ds columns of K/V rows past it to 0 and zeroes those K
#: rows, ``dkv`` zeroes the q and do rows past it and selects their lse and
#: delta (rows of arrays padded to the block, like every lane axis, which
#: ``dq`` fills only as far as its own q blocks reach) to 1e30 and 0;
#: ``dqkv``, the one launch of both where the sequence is resident for it,
#: does what ``dkv`` does on the same transposed tiles and zeroes the delta it
#: computes itself past the sequence (PERF.md section 6, PR 34);
#: ``ln_qkv`` and ``block_tail`` (ops/block_kernels.py) are row-wise from end
#: to end, so what a row past the image holds stays in that row and is
#: dropped with it (PERF.md section 6, PR 32). Every other kernel keeps the
#: pad-to-block-multiple policy, on both axes.
RAGGED_SUBLANE_OK = frozenset({"fwd", "dq", "dkv", "dqkv", "ln_qkv",
                               "block_tail"})


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclass
class BlockInfo:
    """One pallas_call operand's geometry: VMEM block vs backing array."""

    kind: str              # "in" / "out"
    index: int             # operand position within its kind
    block: tuple           # block shape (ints; squeezed dims already ints)
    array: tuple           # backing array shape
    dtype: np.dtype


@dataclass
class KernelCall:
    """One ``pallas_call`` eqn, flattened to checkable geometry."""

    name: str              # kernel function name (name_and_src_info)
    path: str              # repo-relative source file of the kernel
    line: int              # source line (display only)
    grid: tuple            # raw grid entries (ints, or dynamic-dim objects)
    blocks: list = field(default_factory=list)    # [BlockInfo]
    scratch: list = field(default_factory=list)   # [(shape, dtype)] VMEM

    @property
    def grid_static(self) -> bool:
        return all(isinstance(g, (int, np.integer)) for g in self.grid)

    def vmem_bytes(self) -> int:
        """Per-program-instance VMEM footprint: every in/out block held
        ``PIPELINE_BUFFERS``× by the pipeline, plus the scratch operands."""
        total = 0
        for b in self.blocks:
            total += PIPELINE_BUFFERS * int(
                np.prod(b.block or (1,))) * b.dtype.itemsize
        for shape, dtype in self.scratch:
            total += int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
        return total


def _rel_path(src: str, fallback: str) -> tuple[str, int]:
    """``"... a/b/ddim_cold_tpu/ops/quant.py:295"`` → repo-relative path +
    line; the enclosing entry's path when the src info is unparseable."""
    tail = src.rsplit(" ", 1)[-1] if src else ""
    path, line = tail, 0
    if ":" in tail:
        path, _, ln = tail.rpartition(":")
        line = int(ln) if ln.isdigit() else 0
    marker = "ddim_cold_tpu/"
    if marker in path:
        return marker + path.split(marker, 1)[1], line
    return fallback, 0


def iter_kernel_calls(closed, fallback_path: str):
    """Yield a :class:`KernelCall` for every ``pallas_call`` eqn in the
    trace (nested scan/pjit/cond bodies included)."""
    for eqn, _ in jaxpr_checks.iter_eqns(closed):
        if eqn.primitive.name != "pallas_call":
            continue
        # jax 0.9: the kernel body's jaxpr carries "<fn> at <path>:<line>";
        # ``name`` is only what a caller passed to pallas_call(name=...)
        kjaxpr = eqn.params["jaxpr"]
        src = kjaxpr.debug_info.func_src_info or ""
        name = eqn.params.get("name") or src.split(" at ")[0] or "pallas_call"
        path, line = _rel_path(src, fallback_path)
        gm = eqn.params["grid_mapping"]
        call = KernelCall(name=name, path=path, line=line,
                          grid=tuple(gm.grid))
        n_in, n_out = gm.num_inputs, gm.num_outputs
        for i, bm in enumerate(gm.block_mappings):
            sd = bm.array_aval
            # jax 0.9 canonicalizes BlockSpec dims to Blocked(block_size=n);
            # squeezed dims carry no size and are dropped, as before
            block = tuple(int(d.block_size) for d in bm.block_shape
                          if hasattr(d, "block_size"))
            call.blocks.append(BlockInfo(
                kind="in" if i < n_in else "out",
                index=i if i < n_in else i - n_in,
                block=block, array=tuple(sd.shape), dtype=np.dtype(sd.dtype)))
        n_scratch = gm.num_scratch_operands
        if n_scratch:
            for v in kjaxpr.invars[-n_scratch:]:
                aval = v.aval
                space = str(getattr(aval, "memory_space", "vmem")).lower()
                if "vmem" in space or space in ("none", "any"):
                    call.scratch.append(
                        (tuple(aval.shape), np.dtype(aval.dtype)))
        yield call


# ---------------------------------------------------------------------------
# P001 — Mosaic tile legality + static grid
# ---------------------------------------------------------------------------

def check_tile_legality(call: KernelCall, entry: str,
                        subject: str) -> list[Finding]:
    out: list[Finding] = []
    if not call.grid_static:
        dyn = [str(type(g).__name__) for g in call.grid
               if not isinstance(g, (int, np.integer))]
        out.append(Finding(
            "GRAFT-P001", call.path, f"{subject}:grid", call.line,
            f"kernel `{call.name}` in `{entry}` traced with a non-static "
            f"grid {call.grid} ({'/'.join(dyn)}) — a non-Python-int grid "
            "entry (np.int64 from block arithmetic) becomes a dynamic grid "
            "dim; cast every grid entry to int (tile legality is unprovable "
            "and static scheduling is forfeited)"))
    for b in call.blocks:
        if len(b.block) < 1 or b.dtype.itemsize not in MIN_TILE:
            continue
        sub_u, lane_u = MIN_TILE[b.dtype.itemsize]
        problems = []
        # (axis name, block dim, array dim, min unit) for the last two dims
        axes = [("lane", b.block[-1], b.array[-1], lane_u)]
        if len(b.block) >= 2 and len(b.array) >= 2:
            axes.append(("sublane", b.block[-2], b.array[-2], sub_u))
        for axis, blk, arr, unit in axes:
            if blk != arr and blk % unit:
                problems.append(
                    f"{axis} block {blk} is neither a multiple of the "
                    f"{b.dtype} min-tile unit {unit} nor the whole array "
                    f"dim {arr}")
            ragged_ok = (axis == "sublane" and blk % unit == 0
                         and call.name in RAGGED_SUBLANE_OK)
            if blk and arr % blk and not ragged_ok:
                problems.append(
                    f"{axis} array dim {arr} is not a multiple of block "
                    f"{blk} — a partial final block (the caller must pad "
                    "the array to a block multiple; the r04 Mosaic "
                    "rejection class)")
        if problems:
            out.append(Finding(
                "GRAFT-P001", call.path,
                f"{subject}:{b.kind}{b.index}", call.line,
                f"kernel `{call.name}` in `{entry}`, {b.kind}[{b.index}] "
                f"block {b.block} over {b.dtype}{b.array}: "
                + "; ".join(problems)))
    return out


# ---------------------------------------------------------------------------
# P002 — per-program VMEM fit
# ---------------------------------------------------------------------------
# A NECESSARY condition only: the traced call shows the pipelined blocks and
# the declared scratch, not the temporaries the kernel body makes, which the
# compiler places in the same scoped VMEM. A kernel can pass P002 and still
# be refused by the chip's compiler — tests/test_chip_compile.py asks it.

def check_vmem_fit(call: KernelCall, entry: str, subject: str, *,
                   device_kind: str = DEVICE_KIND,
                   budget_bytes: int | None = None) -> list[Finding]:
    from ddim_cold_tpu.utils import flops

    if budget_bytes is None:
        budget_bytes = flops.vmem_bytes(device_kind)
    if budget_bytes is None:
        return []
    used = call.vmem_bytes()
    if used <= budget_bytes:
        return []
    blocks = " + ".join(
        f"{b.kind}[{b.index}]{b.block}x{PIPELINE_BUFFERS}@{b.dtype}"
        for b in call.blocks)
    scratch = " + ".join(f"scratch{s}@{d}" for s, d in call.scratch) or "none"
    return [Finding(
        "GRAFT-P002", call.path, f"{subject}:vmem", call.line,
        f"kernel `{call.name}` in `{entry}` needs "
        f"{used / 2**20:.1f} MiB VMEM per program instance "
        f"({blocks}; {scratch}) — over the {device_kind} capacity of "
        f"{budget_bytes / 2**20:.0f} MiB; shrink the blocks or split the "
        "scratch")]


# ---------------------------------------------------------------------------
# P003 — grid/block padding waste at a registered geometry
# ---------------------------------------------------------------------------

def check_padding_waste(call: KernelCall, entry: str, subject: str, *,
                        logical: int | None = None,
                        threshold: float = WASTE_THRESHOLD) -> list[Finding]:
    """Worst padded-over-payload compute ratio across the call's block
    geometry. ``logical`` is the entry's registered logical extent (the
    true token count, e.g. N=2501 at 200px): arrays reach the kernel
    already padded, so any array dim in ``[logical, 2·logical)`` is read
    as that logical axis and charged against the UNPADDED extent."""
    worst, worst_why = 1.0, ""
    for b in call.blocks:
        n = min(len(b.block), len(b.array), 2)
        for k in range(1, n + 1):
            blk, arr = b.block[-k], b.array[-k]
            if not blk or not arr:
                continue
            padded = _round_up(arr, blk)
            base = arr
            if logical and logical <= arr < 2 * logical:
                base = logical
            ratio = padded / base
            if ratio > worst:
                worst = ratio
                worst_why = (f"{b.kind}[{b.index}] dim -{k}: block {blk} "
                             f"pads {base} → {padded}")
    if worst <= threshold:
        return []
    return [Finding(
        "GRAFT-P003", call.path, f"{subject}:pad", call.line,
        f"kernel `{call.name}` in `{entry}` wastes {100 * (worst - 1):.0f}% "
        f"of its compute on block padding ({worst_why}; threshold "
        f"{100 * (threshold - 1):.0f}%) — pick a block that divides the "
        "geometry more tightly")]


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def check_program(closed, entry: str, fallback_path: str, *,
                  logical: int | None = None,
                  device_kind: str = DEVICE_KIND,
                  vmem_budget: int | None = None,
                  waste_threshold: float = WASTE_THRESHOLD) -> list[Finding]:
    """P001 + P002 + P003 over every pallas_call in one traced program.
    Subjects are ``<entry>:<kernel>#<n>[:...]`` with ``n`` the per-(entry,
    kernel) occurrence counter — stable across unrelated edits."""
    findings: list[Finding] = []
    counts: Counter = Counter()
    for call in iter_kernel_calls(closed, fallback_path):
        counts[call.name] += 1
        subject = f"{entry}:{call.name}#{counts[call.name]}"
        findings += check_tile_legality(call, entry, subject)
        findings += check_vmem_fit(call, entry, subject,
                                   device_kind=device_kind,
                                   budget_bytes=vmem_budget)
        findings += check_padding_waste(call, entry, subject,
                                        logical=logical,
                                        threshold=waste_threshold)
    return findings


#: serve-sweep findings anchor where J006's do
ENGINE_PATH = "ddim_cold_tpu/serve/engine.py"


def run_kernel_checks(serve_traces: dict | None = None,
                      entry_traces: dict | None = None,
                      kernel_traces: dict | None = None,
                      device_kind: str = DEVICE_KIND) -> list[Finding]:
    """The kernels layer: every pallas_call in the serve sweep, the
    build/train entries, and the 200px kernel entries. The CLI hands over
    the traces the jaxpr layer already built (one trace either way);
    standalone (``--only P``) this traces its own world."""
    from ddim_cold_tpu.analysis import entries

    if serve_traces is None or entry_traces is None:
        ctx = entries.Context()
        if serve_traces is None:
            serve_traces = {}
            entries.serve_signatures(ctx, traces=serve_traces)
        if entry_traces is None:
            entry_traces = {e.name: (e, e.trace())
                            for e in entries.build_entries(ctx)}
    if kernel_traces is None:
        kernel_traces = entries.kernel_traces()
    findings: list[Finding] = []
    for subject in sorted(serve_traces):
        _config, closed = serve_traces[subject]
        findings += check_program(closed, subject, ENGINE_PATH,
                                  device_kind=device_kind)
    for group in (entry_traces, kernel_traces):
        for name in sorted(group):
            e, closed = group[name]
            findings += check_program(
                closed, name, e.path, device_kind=device_kind,
                logical=(e.meta or {}).get("tokens"))
    return findings
