"""From a profiler trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``)
to the numbers the result line and the per-layer readers use.

What a real trace of this machine looks like (looked at by hand, PR 23): one
plane per chip named ``/device:TPU:<id>`` with the lines ``XLA Modules`` (one
event per executed program), ``XLA Ops`` (one event per HLO instruction, the
body of a ``while`` nested inside the ``while``'s own event) and ``Async XLA
Ops`` (copies and collectives in flight); one ``/host:CPU`` plane whose lines
are host threads. All planes share one clock, nanoseconds from the start of
the session. An op event's name is the instruction's text,
``%fwd.36 = (bf16[64,2560,128]...) custom-call(...)``; a Pallas kernel shows
under the last part of its scope name (``flash_attention/fwd`` -> ``%fwd``).

The traced window is the benchmark's own ``bench/window`` annotation, found
on the host plane; device events are clipped to it. ``busy_s`` is, per
device, the union of its ``XLA Ops`` intervals inside the window, then the
mean over the cell's devices: above 0 where anything ran, never above
``window_s``, never a sum over chips. (``_union`` is the interval merge of the
program's ``obs/attrib._merged_busy``, copied.)
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "bench/window"
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
#: instructions that only hold other instructions; their time is their body's
WRAPPERS = ("while", "conditional", "call")


class Session:
    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.host_window_s = 0.0


@contextlib.contextmanager
def tracing(trace_dir: str):
    """Profile the body. Host threads keep their TraceMe events (the
    benchmark's spans are among them); the Python tracer is off, it slows
    the host and fills the trace."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # user annotations; level 2 also traces the runtime and slowed the loader threads
    session = Session(trace_dir)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield session
    finally:
        session.host_window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()


def short_name(text: str) -> str:
    """``%fwd.36 = (...) custom-call(...)`` -> ``fwd``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def _union(intervals) -> tuple[float, list]:
    """(total length, merged [(start, end)]) of intervals in any order."""
    if not intervals:
        return 0.0, []
    ivs = sorted(intervals)
    merged = [list(ivs[0])]
    for s, e in ivs[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _clip(events, lo, hi):
    out = []
    for s, e, name in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, name))
    return out


def _self_times(events) -> dict:
    """{short name: ns} with each instruction's nested instructions taken out
    of it, so that a ``while`` does not count its body twice."""
    out: dict = defaultdict(float)
    stack: list = []  # [end, name, own ns]

    def pop():
        end, name, own = stack.pop()
        out[name] += max(own, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short_name(name), e - s])
    while stack:
        pop()
    return out


def _top(seconds: dict, n: int = 10) -> list:
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return [[name, sec] for name, sec in ranked if sec > 0]


class Reduced:
    """One trace, reduced. Times in seconds; per-device quantities are kept
    per device and averaged over the cell's devices by the accessors."""

    def __init__(self, window_s: float, devices: dict, host_spans: list,
                 window_ns: tuple):
        self.window_s = window_s
        self.devices = devices        # id -> {"ops": [...], "async": [...]}
        self.host_spans = host_spans  # (start_ns, end_ns, name) of bench/*
        self.window_ns = window_ns
        self._busy = {d: _union([(s, e) for s, e, _ in ev["ops"]])
                      for d, ev in devices.items()}

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def busy_s(self) -> float:
        return sum(b[0] for b in self._busy.values()) / self.n_devices * 1e-9

    def op_seconds(self, match, lines=("ops",)) -> tuple[float, int]:
        """(seconds, events) of the instructions whose text ``match``
        accepts: per device the union of their intervals, mean over
        devices; the count is per device too."""
        total, count = 0.0, 0
        for ev in self.devices.values():
            hit = [(s, e) for line in lines for s, e, name in ev[line]
                   if match(name)]
            total += _union(hit)[0]
            count += len(hit)
        return total / self.n_devices * 1e-9, count // self.n_devices

    def self_seconds(self) -> dict:
        """{short instruction name: seconds}, mean over devices."""
        acc: dict = defaultdict(float)
        for ev in self.devices.values():
            for name, ns in _self_times(ev["ops"]).items():
                acc[name] += ns
        return {k: v / self.n_devices * 1e-9 for k, v in acc.items()
                if k not in WRAPPERS}

    def idle_gaps(self) -> dict:
        """{what the host was doing: idle seconds}, from the first device's
        gaps: each gap goes to the innermost ``bench/*`` span that covers
        its middle, or to ``(no span)``."""
        first = min(self.devices)
        lo, hi = self.window_ns
        merged = self._busy[first][1]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        acc: dict = defaultdict(float)
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            mid = (a + b) / 2
            cover = [(e - s, n) for s, e, n in self.host_spans
                     if s <= mid <= e and n != WINDOW]
            acc[min(cover)[1] if cover else "(no span)"] += (b - a) * 1e-9
        return acc

    def breakdown(self) -> dict:
        return {"device_ops": _top(self.self_seconds()),
                "idle_gaps": _top(self.idle_gaps())}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str, n_devices: int, host_window_s: float = 0.0) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw: dict = {}
    host_spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ev = {"ops": [], "async": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", ASYNC_LINE: "async"}.get(line.name)
                if key:
                    ev[key] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events]
            raw[int(m.group(1))] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events
                               if e.name.startswith("bench/")]
    window = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if window:
        lo, hi = window[0]
    else:  # the annotation was lost: the session's own span on the host clock
        starts = [s for ev in raw.values() for s, _, _ in ev["ops"]]
        lo = min(starts) if starts else 0.0
        hi = lo + host_window_s * 1e9
    # the cell's devices are the first n by id; one that ran nothing has no
    # plane and counts as idle for the whole window
    ids = sorted(raw)[:n_devices]
    ids += [max(ids, default=-1) + 1 + i for i in range(n_devices - len(ids))]
    devices = {d: {k: _clip(v, lo, hi) for k, v in
                   raw.get(d, {"ops": [], "async": []}).items()}
               for d in ids}
    return Reduced((hi - lo) * 1e-9, devices, host_spans, (lo, hi))


def reduce(session: Session, n_devices: int) -> Reduced:
    return reduce_file(find_xplane(session.trace_dir), n_devices,
                       session.host_window_s)
