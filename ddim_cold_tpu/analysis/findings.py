"""Finding records, the rule table, and the reviewed-baseline grammar.

A :class:`Finding` is one rule violation at one place. The ``(rule, path,
subject)`` triple is the finding's identity: line numbers drift with every
edit, so the baseline (the reviewed allowlist ``--baseline`` consumes and
``--fix-baseline`` regenerates) keys on the stable triple and carries the
line only for display. ``subject`` is chosen per rule to survive unrelated
edits — an entry-point name, an enclosing-function + callee pair, a fault
site, a '/'-joined param-leaf path.

Baseline grammar (one finding per line, ``#`` comments and blanks ignored)::

    <RULE-ID> <path> :: <subject>
    GRAFT-A002 ddim_cold_tpu/data/datasets.py :: _probe_uniform_u8:Exception

``--fix-baseline`` writes the file sorted and de-duplicated so regenerated
baselines diff cleanly under review.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

#: rule id → one-line description. Stable ids: tests, baselines and CI grep
#: these — never renumber, only append.
RULES = {
    "GRAFT-J001": "low-precision (bf16/f16) accumulation in a matmul/conv — "
                  "violates the bf16-trunk/f32-accumulate dtype policy",
    "GRAFT-J002": "weak-typed float output from a traced entry point — "
                  "promotion hazard and a jit-cache-miss (recompile) hazard",
    "GRAFT-J003": "donated buffer XLA would drop: no output matches the "
                  "donated aval's (shape, dtype), so donation frees nothing",
    "GRAFT-J004": "oversized constant baked into a traced program — HBM "
                  "bloat and a compile-cache poison (const bytes are keyed)",
    "GRAFT-J005": "host callback primitive inside a scanned sampler body — "
                  "forces host sync every step of the scan",
    "GRAFT-J006": "unstable or colliding abstract trace signature across the "
                  "serve sweep — breaks the zero-compiles-after-warmup "
                  "guarantee",
    "GRAFT-J007": "`while` primitive in a served sampler program — a "
                  "data-dependent trip count; the adaptive drift gate must "
                  "select branches INSIDE one static-trip scan, never "
                  "vary the loop itself",
    "GRAFT-A001": "wall-clock/stdlib-random call inside a jitted or scanned "
                  "function — nondeterminism the fault-replay contract "
                  "(utils/faults.py) forbids",
    "GRAFT-A002": "broad `except Exception`/bare `except` without a "
                  "`# noqa: BLE001` justification on the same line",
    "GRAFT-A003": "faults.fire() site violation: unregistered site name, "
                  "non-literal site, or duplicate (site, tag) pair",
    "GRAFT-A004": "device-array (jnp/jax) call in a host-only serve module — "
                  "would force a device sync inside row planning",
    "GRAFT-A005": "obs.metrics emit violation: unregistered metric name, "
                  "non-literal name, or duplicate (name, key) emit site",
    "GRAFT-S001": "trunk GEMM param leaf (qkv/proj/fc1/fc2 kernel|w_int8) "
                  "fell through to a replicated spec on a model-axis mesh",
    "GRAFT-S002": "param leaf without a usable PartitionSpec (structure "
                  "mismatch, rank overflow, or unknown mesh axis)",
    "GRAFT-T001": "shared attribute with a declared `# guarded-by:` lock "
                  "written (outside __init__) without holding the guard — "
                  "a data race on the worker-thread/submit path",
    "GRAFT-T002": "lock acquired while holding a lock of equal or higher "
                  "rank in the declared hierarchy (router < engine/fleet < "
                  "batching < obs) — an ordering inversion that can deadlock",
    "GRAFT-T003": "ticket resolution or user-visible callback invoked while "
                  "holding a lock — the callback can re-enter the serving "
                  "layer and deadlock (callbacks must fire outside locks)",
    "GRAFT-T004": "Event.wait()/Condition.wait() on one synchronizer while "
                  "holding a different lock — the notifier may need that "
                  "lock, wedging both threads",
    "GRAFT-T005": "unguarded lazy-init: check-then-set on a guarded shared "
                  "attribute without the lock (and without a re-check under "
                  "it) — double allocation under concurrent first use",
    "GRAFT-C001": "collective sequence diverges across program shards of "
                  "one mesh (collective under per-shard control flow "
                  "inside the manual shard_map region) — an SPMD deadlock; "
                  "every shard must issue the same collectives in the "
                  "same order per mesh axis",
    "GRAFT-C002": "collective over a mesh axis the program's mesh does not "
                  "define (or outside any mesh) — unlowerable or silently "
                  "wrong sp program",
    "GRAFT-P001": "Pallas block geometry violates the Mosaic tile rules "
                  "(min sublane×lane tile per dtype, whole-dim span, "
                  "block-divides-array) or the grid is not fully static — "
                  "the r04 on-chip rejection class, invisible to CPU "
                  "interpret mode",
    "GRAFT-P002": "Pallas kernel's per-program VMEM footprint (double-"
                  "buffered in/out blocks + VMEM scratch) exceeds the "
                  "device kind's VMEM capacity",
    "GRAFT-P003": "Pallas grid/block padding inflates kernel compute "
                  "beyond the waste threshold at a registered geometry",
    "GRAFT-M001": "traced program's donation-aware peak live HBM bound "
                  "exceeds the device kind's HBM budget",
    "GRAFT-M002": "bucket/sequence padding inflates a traced program's "
                  "resident token axis beyond the threshold over the "
                  "logical payload",
    "GRAFT-R001": "RPC frame-kind parity violation: a wire method/event "
                  "without a table entry, a table entry without a site, a "
                  "client/server table mismatch, or a health field missing "
                  "from a backend the fleet control plane reads",
    "GRAFT-R002": "exception-serialization hole: a serve/errors.py type "
                  "outside the wire codec (or failing round-trip), or a "
                  "protocol-module raise of an unregistered type that "
                  "would degrade to RequestFailedError on the wire",
    "GRAFT-R003": "rid lifecycle inversion: the client ticket registration "
                  "does not dominate the submit send — a done event racing "
                  "the response finds no ticket (the PR-19 race)",
    "GRAFT-R004": "unbounded read/send on the RPC wire: a length-prefixed "
                  "read or frame send without a MAX_FRAME_BYTES check, an "
                  "uncapped recv chunk, or a socket going deadline-free "
                  "before its validated handshake read",
    "GRAFT-R005": "wire chaos-site gap: the frame-send/dispatch choke "
                  "points don't fire their registered rpc.*/replica.* "
                  "fault sites (or the sites aren't registered at all)",
    "GRAFT-X001": "legal SamplerConfig program class with no serve-sweep "
                  "witness — it would reach production untraced and "
                  "unwarmed (the J006 completeness converse)",
    "GRAFT-X002": "config validation inconsistency: construction-time and "
                  "program-build gates disagree, a distill-producible "
                  "student count is unservable, or a frozen config is "
                  "mutated past the gate via object.__setattr__",
    "GRAFT-X003": "warm-set/entry-point config outside the legal lattice (or "
                  "warmed without a sweep witness) — serving would warm or "
                  "benchmark a program the lattice proofs never saw",
    "GRAFT-X004": "the hybrid trunk admits a config class that reaches into "
                  "Block (or refuses one that does not): it would fail on a "
                  "shape, or lose a program class it has",
}

#: rule-family letter (GRAFT-<X>NNN) → the CLI layer that emits it. The
#: partial --fix-baseline (--only) uses this to know which baseline lines a
#: layer run is authoritative for.
RULE_LAYERS = {"A": "ast", "J": "jaxpr", "S": "sharding",
               "T": "threads", "C": "collective",
               "P": "kernels", "M": "memory",
               "R": "protocol", "X": "config"}


def rule_layer(rule: str) -> str:
    """The CLI layer a rule id belongs to (``GRAFT-T001`` → ``threads``)."""
    return RULE_LAYERS[rule.split("-", 1)[1][0]]


@dataclass(frozen=True, order=True)
class Finding:
    """One rule violation. Identity (baseline key) is (rule, path, subject);
    ``line``/``message`` are display-only."""

    rule: str
    path: str          # repo-relative, '/'-separated
    subject: str       # stable short identifier within the file/check
    line: int = field(default=0, compare=True)
    message: str = field(default="", compare=False)

    @property
    def key(self) -> str:
        return f"{self.rule} {self.path} :: {self.subject}"

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{self.rule} {loc} [{self.subject}] {self.message}"


def load_baseline(path: str | None) -> set[str]:
    """Parse a baseline file into the set of suppressed finding keys. A
    missing file is an empty baseline (strict), never an error — CI can pass
    the flag unconditionally."""
    keys: set[str] = set()
    if not path or not os.path.isfile(path):
        return keys
    with open(path) as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if " :: " not in line or not line.split(" ", 1)[0] in RULES:
                raise ValueError(
                    f"{path}: malformed baseline line {line!r} "
                    "(expected '<RULE-ID> <path> :: <subject>')")
            keys.add(line)
    return keys


def write_baseline(path: str, findings: list[Finding],
                   extra_keys: set[str] | frozenset = frozenset()) -> int:
    """Regenerate the allowlist deterministically: header, then the sorted,
    de-duplicated keys of ``findings`` — reviewed diffs stay minimal.
    ``extra_keys`` are preserved verbatim alongside the regenerated keys —
    the partial refresh (``--fix-baseline --only``) passes the lines of
    layers it did NOT run, so adopting one rule family never churns the
    others' reviewed entries."""
    keys = sorted({f.key for f in findings} | set(extra_keys))
    with open(path, "w") as f:
        f.write("# graftcheck baseline — reviewed allowlist of known "
                "findings.\n")
        f.write("# One per line: <RULE-ID> <path> :: <subject>   "
                "(regenerate: graftcheck --fix-baseline)\n")
        for k in keys:
            f.write(k + "\n")
    return len(keys)
