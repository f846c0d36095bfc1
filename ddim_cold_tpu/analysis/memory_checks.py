"""GRAFT-M001/M002 — static peak-HBM budget analysis over traced programs.

xDiT-style multi-axis serving and the fused-kernel work both make
per-program memory budgets the scaling constraint, and the engine's AOT
model means every served program's residency is decided at trace time —
so prove it there. For each traced ``(SamplerConfig, bucket)`` program
(and the first-class 200px entries) the pass runs a donation-aware
liveness walk over the jaxpr and produces an upper bound on peak live HBM
bytes: resident params and the step cache are program inputs and are
counted from entry; a donated input (the engine donates every carry —
``pjit``'s ``donated_invars`` rides the eqn params, no lowering needed)
dies at its last use, a non-donated one stays live to the end; each eqn's
outputs join the live set as they materialize and operands leave it after
their last use; a nested scan/cond/pjit body contributes its own interior
peak above its boundary (one iteration's peak stands in for all — XLA
reuses the body's buffers across trips).

The walk ignores XLA fusion (two eqns XLA would fuse never materialize
the intermediate), so the bound is conservative: a program that passes
here fits on chip with room to spare; a program that fails is flagged
before it burns a hardware window.

**M001** — peak over the device HBM budget (``utils/flops.HBM_BYTES``,
default the bench v5e) at a registered geometry.

**M002** — bucket/sequence padding inflating residency: any traced aval
whose dim sits in ``[tokens, 2·tokens)`` is the padded token axis; its
extent over the logical token count beyond the threshold means the
program carries padding as if it were payload (the tile-padding worst
case stays well under; a pad-to-power-of-two class bug trips it). The
window only identifies a token axis when the token count is large enough
to be distinctive (``MIN_PAD_TOKENS``) — at the TINY sweep's 5 tokens,
batch and pixel dims land inside it, so the check abstains there and
bites at the registered 200px geometry (N=2501), where no other axis
comes near.
"""

from __future__ import annotations

import numpy as np
from jax.extend import core as jax_core

from ddim_cold_tpu.analysis.findings import Finding

#: the device kind the HBM budget defaults to — the bench chip (v5e, the
#: smallest-HBM kind we run; fitting there keeps every bigger chip safe)
DEVICE_KIND = "TPU v5 lite"

#: M002 threshold: padded token extent over the logical token count. The
#: in-tree worst case — the streamed-kv flash padding at 200px
#: (3072/2501 = 1.228) — passes; a pad-to-4096 class bug at N=2501
#: (1.64) fails.
PAD_THRESHOLD = 1.30

#: below this token count the [tokens, 2·tokens) window is ambiguous —
#: batch sizes and image pixel dims land inside it — so M002 abstains
#: rather than guess which dim is the token axis
MIN_PAD_TOKENS = 128

_SUB_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "branches",
                   "cond_jaxpr", "body_jaxpr")


def aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize


def _sub_jaxprs(eqn):
    for key in _SUB_JAXPR_KEYS:
        val = eqn.params.get(key)
        if val is None:
            continue
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            v = getattr(v, "jaxpr", v)  # ClosedJaxpr → Jaxpr
            if hasattr(v, "eqns"):
                yield v


def _inner_extra(eqn) -> int:
    """The interior peak a nested body adds ABOVE its boundary (the body's
    invars/consts are the eqn's operands, already counted by the caller's
    live set). Max over sub-jaxprs; cond/switch branches don't run
    together, so max is exact for them too."""
    extra = 0
    for sub in _sub_jaxprs(eqn):
        boundary = sum(aval_bytes(v.aval) for v in sub.invars)
        boundary += sum(aval_bytes(v.aval) for v in sub.constvars)
        extra = max(extra, _jaxpr_peak(sub) - boundary)
    return max(extra, 0)


def _jaxpr_peak(jaxpr, donated=()) -> int:
    """Peak live bytes over one jaxpr's straight-line schedule. ``donated``
    flags align with ``jaxpr.invars``; a donated invar dies at its last
    use, everything else the caller retains lives throughout."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n_eqns = len(jaxpr.eqns)
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, jax_core.Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, jax_core.Literal):
            last_use[v] = n_eqns  # program outputs live to the end
    donated = tuple(donated) + (False,) * (len(jaxpr.invars) - len(donated))
    running = 0
    for v in jaxpr.constvars:
        running += aval_bytes(v.aval)
        last_use[v] = n_eqns  # consts are executable-resident
    for v, don in zip(jaxpr.invars, donated):
        running += aval_bytes(v.aval)
        if not don:
            last_use[v] = n_eqns
    peak = running
    for i, eqn in enumerate(jaxpr.eqns):
        # while the eqn runs: operands still live + the body's interior
        peak = max(peak, running + _inner_extra(eqn))
        for v in eqn.outvars:
            if v in last_use:  # unused outputs (DropVar) never materialize
                running += aval_bytes(v.aval)
        peak = max(peak, running)
        for v in {v for v in eqn.invars
                  if not isinstance(v, jax_core.Literal)}:
            if last_use.get(v) == i:
                running -= aval_bytes(v.aval)
    return peak


def peak_live_bytes(closed) -> int:
    """Upper bound on peak live HBM bytes for one traced program. A
    top-level single-``pjit`` trace (every jitted entry) is unwrapped so
    the body's ``donated_invars`` drive the walk — the outer wrapper would
    double-count each donated carry against its aliased output."""
    consts = sum(aval_bytes(getattr(c, "aval", c))
                 for c in getattr(closed, "consts", ()))
    jaxpr = closed.jaxpr
    if len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name == "jit":
        eqn = jaxpr.eqns[0]
        body = eqn.params["jaxpr"]
        don = eqn.params.get("donated_invars") or ()
        return consts + _jaxpr_peak(body, don)
    return consts + _jaxpr_peak(jaxpr)


def _iter_avals(closed):
    """Every traced aval: program inputs plus each eqn output, nested
    bodies included (their boundary vars are the enclosing operands)."""
    from ddim_cold_tpu.analysis import jaxpr_checks

    jaxpr = getattr(closed, "jaxpr", closed)
    for v in jaxpr.invars:
        yield v.aval
    for eqn, _ in jaxpr_checks.iter_eqns(jaxpr):
        for v in eqn.outvars:
            yield v.aval


# ---------------------------------------------------------------------------
# M001 — peak over the device HBM budget
# ---------------------------------------------------------------------------

def check_peak_hbm(closed, subject: str, path: str, *,
                   device_kind: str = DEVICE_KIND,
                   budget_bytes: int | None = None) -> list[Finding]:
    from ddim_cold_tpu.utils import flops

    if budget_bytes is None:
        budget_bytes = flops.hbm_bytes(device_kind)
    if budget_bytes is None:
        return []
    peak = peak_live_bytes(closed)
    if peak <= budget_bytes:
        return []
    return [Finding(
        "GRAFT-M001", path, f"{subject}:peak", 0,
        f"program `{subject}` peaks at {peak / 2**30:.2f} GiB live HBM "
        f"(donation-aware liveness bound) — over the {device_kind} budget "
        f"of {budget_bytes / 2**30:.0f} GiB; shrink the bucket, shard the "
        "program, or drop residuals")]


# ---------------------------------------------------------------------------
# M002 — padding inflating residency over the logical payload
# ---------------------------------------------------------------------------

def check_padding(closed, subject: str, path: str, *, tokens: int,
                  threshold: float = PAD_THRESHOLD) -> list[Finding]:
    if tokens < MIN_PAD_TOKENS:
        return []  # window too ambiguous to name a token axis — abstain
    worst, worst_shape = 1.0, None
    for aval in _iter_avals(closed):
        for dim in getattr(aval, "shape", ()):
            if tokens <= dim < 2 * tokens:
                ratio = dim / tokens
                if ratio > worst:
                    worst, worst_shape = ratio, tuple(aval.shape)
    if worst <= threshold:
        return []
    return [Finding(
        "GRAFT-M002", path, f"{subject}:pad", 0,
        f"program `{subject}` carries a token axis padded to "
        f"{100 * (worst - 1):.0f}% over the logical {tokens} tokens "
        f"(aval {worst_shape}; threshold {100 * (threshold - 1):.0f}%) — "
        "bucket/sp/tile padding is being paid as resident payload")]


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

#: serve-sweep findings anchor where J006's do
ENGINE_PATH = "ddim_cold_tpu/serve/engine.py"


def check_program(closed, subject: str, path: str, *, tokens: int,
                  device_kind: str = DEVICE_KIND,
                  budget_bytes: int | None = None,
                  threshold: float = PAD_THRESHOLD) -> list[Finding]:
    findings = check_peak_hbm(closed, subject, path,
                              device_kind=device_kind,
                              budget_bytes=budget_bytes)
    findings += check_padding(closed, subject, path, tokens=tokens,
                              threshold=threshold)
    return findings


def run_memory_checks(serve_traces: dict | None = None,
                      kernel_traces: dict | None = None,
                      device_kind: str = DEVICE_KIND) -> list[Finding]:
    """The memory layer: peak-HBM + padding budget per (SamplerConfig,
    bucket) sweep program and per 200px sampler entry. Reuses the CLI's
    shared traces; standalone (``--only M``) it traces its own world."""
    from ddim_cold_tpu.analysis import entries

    if serve_traces is None:
        serve_traces = {}
        entries.serve_signatures(entries.Context(), traces=serve_traces)
    if kernel_traces is None:
        kernel_traces = entries.kernel_traces()
    tiny_tokens = (entries.TINY["img_size"][0]
                   // entries.TINY["patch_size"]) ** 2 + 1
    findings: list[Finding] = []
    for subject in sorted(serve_traces):
        _config, closed = serve_traces[subject]
        findings += check_program(closed, subject, ENGINE_PATH,
                                  tokens=tiny_tokens,
                                  device_kind=device_kind)
    for name in sorted(kernel_traces):
        e, closed = kernel_traces[name]
        meta = e.meta or {}
        if not meta.get("memory"):
            continue  # pure kernel-geometry entries — P-rules cover them
        findings += check_program(closed, name, e.path,
                                  tokens=meta["tokens"],
                                  device_kind=device_kind)
    return findings
