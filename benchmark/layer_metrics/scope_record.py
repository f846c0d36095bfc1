"""Device time by layer: the traced window's instructions joined with the
program's own scope map (``ddim_cold_tpu.obs.scopes.scope_map()``).

A trace names instructions (``%fusion.123 = …``); which layer one belongs to
is in the compiled module, which only the program can ask for. Per device,
each ``XLA Ops`` event's self time (nested events taken out of their
``while`` / ``conditional`` / ``call`` as ``trace_reduce._self_times`` does,
but keyed by the instruction's FULL name, not ``short_name``'s ``fusion``)
goes to the layer the map gives that name, to ``outside`` where the map has
the instruction in no layer of the program's table, or to ``unmapped`` where
the map does not have it; then the mean over the cell's devices. Every
second of self time is in exactly one of them, so the shares of ``busy_s``
add up to 100.

The map is built once a process, after ``driver.close``: what it costs falls
in no window and in no ``setup_s``. A checkout whose program has no
``obs.scopes``, or a map that fails to build, reads 0.0 in every reader
(``run.py`` cannot leave a metric out: PERF.md section 7), the reason on
stderr. On stderr too, once a traced run: the layer x instruction table.
"""

from __future__ import annotations

import sys
import time
from collections import defaultdict

from benchmark import trace_reduce

OUTSIDE, UNMAPPED = "outside", "unmapped"
_map = None      # {full instruction name: {"layer", "direction", "mixed", ...}}
_printed = False


def _say(msg: str) -> None:
    print(f"[bench] scope_record: {msg}", file=sys.stderr, flush=True)


def scope_map() -> dict:
    """The program's map, built at the first call; ``{}`` where the program
    keeps none or it cannot be built."""
    global _map
    if _map is None:
        _map = {}
        try:
            from ddim_cold_tpu.obs import scopes
        except ImportError as e:
            _say(f"the program has no obs.scopes ({e}): every share reads 0.0")
            return _map
        from ddim_cold_tpu.obs import spans

        held = len(spans.layer_spans())
        t0 = time.perf_counter()
        try:
            _map = scopes.scope_map()
        except Exception as e:  # a reader may not take the result line with it
            _say(f"the scope map failed to build ({type(e).__name__}: {e}): "
                 "every share reads 0.0")
            return _map
        took = time.perf_counter() - t0
        # what JAX did meanwhile, by the program's own compile listener
        events: dict = defaultdict(lambda: [0, 0.0])
        for s in spans.layer_spans()[held:]:
            if s.name.startswith(("jax/", "scopes/")) and s.t1 is not None:
                events[s.name][0] += 1
                events[s.name][1] += (s.t1 - s.t0) / 1e9
        _say(f"scope map of {len(scopes.programs())} noted program(s) "
             f"{[p.name for p in scopes.programs()]}, {len(_map)} "
             f"instructions, built in {took:.3f} s; events meanwhile "
             f"(count, seconds): {dict(events)}")
    return _map


def full_name(text: str) -> str:
    """``%fusion.123 = (...) fusion(...)`` -> ``fusion.123``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def self_ns(events) -> dict:
    """{full instruction name: ns} of one device's ``XLA Ops`` events, each
    instruction's nested instructions taken out of it."""
    out: dict = defaultdict(float)
    stack: list = []  # [end, name, own ns]

    def pop():
        _, name, own = stack.pop()
        out[name] += max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, full_name(name), e - s])
    while stack:
        pop()
    return out


class Split:
    """The traced window's self time, in seconds a device: ``by_layer``
    (``outside`` and ``unmapped`` among the layers), ``bwd_s`` and
    ``mixed_s`` (mapped instructions of the backward, and fusions that hold
    another layer's instructions too), ``names`` (layer -> {short
    instruction name: seconds})."""

    def __init__(self, trace, mapping: dict):
        self.by_layer: dict = defaultdict(float)
        self.names: dict = defaultdict(lambda: defaultdict(float))
        self.bwd_s = self.mixed_s = 0.0
        scale = 1e-9 / max(trace.n_devices, 1)
        for ev in trace.devices.values():
            for name, ns in self_ns(ev["ops"]).items():
                entry = mapping.get(name)
                layer = UNMAPPED if entry is None else entry["layer"]
                self.by_layer[layer] += ns * scale
                self.names[layer][trace_reduce.short_name(name)] += ns * scale
                if entry is not None and entry["direction"] == "bwd":
                    self.bwd_s += ns * scale
                if entry is not None and entry["mixed"]:
                    self.mixed_s += ns * scale

    @property
    def attributed_s(self) -> float:
        return sum(s for layer, s in self.by_layer.items()
                   if layer not in (OUTSIDE, UNMAPPED))

    def table(self, busy_s: float, top: int = 5) -> str:
        lines = [f"layer x instruction, seconds of {busy_s:.6f} s busy "
                 f"(backward {self.bwd_s:.6f}, mixed fusions "
                 f"{self.mixed_s:.6f})"]
        for layer, total in sorted(self.by_layer.items(), key=lambda kv: -kv[1]):
            ranked = sorted(self.names[layer].items(), key=lambda kv: -kv[1])
            lines.append(
                f"  {layer:<10} {total:10.6f} {100.0 * total / busy_s:6.2f} %  "
                + ", ".join(f"{n} {s:.6f}" for n, s in ranked[:top]))
        return "\n".join(lines)


def split(view):
    """The view's :class:`Split` (made once a view, the table printed once a
    process), or ``None`` where there is no trace, no busy time or no map."""
    global _printed
    if view.trace is None or view.trace.busy_s <= 0:
        return None
    mapping = scope_map()
    if not mapping:
        return None
    got = getattr(view, "_scope_split", None)
    if got is None:
        got = view._scope_split = Split(view.trace, mapping)
        if not _printed:
            _printed = True
            _say(got.table(view.trace.busy_s))
    return got


def by_layer(view) -> dict:
    """{layer or "outside" or "unmapped": seconds}; ``{}`` without a map."""
    got = split(view)
    return dict(got.by_layer) if got else {}


def share(view, *layers: str) -> float:
    """Percent of ``busy_s`` on the instructions of ``layers``."""
    got = split(view)
    if got is None:
        return 0.0
    return 100.0 * sum(got.by_layer.get(l, 0.0) for l in layers) / view.trace.busy_s


def attributed_share(view) -> float:
    got = split(view)
    return 100.0 * got.attributed_s / view.trace.busy_s if got else 0.0


def backward_share(view) -> float:
    got = split(view)
    return 100.0 * got.bwd_s / view.trace.busy_s if got else 0.0
