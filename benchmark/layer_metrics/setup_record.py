"""Set-up as the readers of its stages see it: the stretch ``setup_s`` counts,
and the program's spans and events clipped to it.

The stretch runs from the instant ``run.py`` counts ``setup_s`` from (its
``_T0``, the first statement of the process's main module; without one, the
process's start as the OS has it, mapped onto the spans' clock) to the end of
the benchmark's last set-up span (``warmup`` of a sampler cell,
``first_steps`` of a training cell: ``view.spans.records``, same clock), so
that a profiler's start before a traced window is not in it. A span that
straddles either end counts for the part inside.

Every second of the stretch is in exactly one of four places:

    start  +  union of the program's spans and events  +  first-run wait
           +  unattributed                               =  the stretch

``start`` lasts until the program's first span or event opens (imports, the
runtime's start); the first-run wait is the part of the benchmark's own
``warmup`` / ``first_steps`` record that no span or event of the program
covers (the host blocked on the device's first run of the loaded programs,
and the fetches after it); unattributed is what is left (the benchmark's
weights, model and mesh construction). The stage metrics (compile, cache
load, trace and lowering, data, placement) are parts of the union and may
nest in one another.

``read`` of a stage returns ``None`` only when the program keeps no record at
all or its ring has dropped events; a stage that left no span of its name
took 0.0 s, which is what a program without that stage reads too.
"""

from __future__ import annotations

import collections
import os
import sys
import time

from benchmark.layer_metrics import program_record as rec

#: the benchmark's spans that close a driver's set-up
SETUP_SPANS = ("warmup", "first_steps")

#: a clipped span: what ``program_record.union_s`` and ``inside_s`` read
Clip = collections.namedtuple("Clip", "t0 t1")


def start_ns():
    """The instant ``setup_s`` counts from, in the spans' nanoseconds, or
    ``None`` where neither the main module nor the OS says."""
    t0 = getattr(sys.modules.get("__main__"), "_T0", None)
    if isinstance(t0, float):
        return int(t0 * 1e9)
    try:
        with open("/proc/self/stat") as f:
            # field 22, counted after the parenthesised command name
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None
    age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    return time.perf_counter_ns() - int(age * 1e9)


class Setup:
    """The stretch [``lo``, ``hi``] and the program's closed spans clipped
    to it (``spans``: ``(name, Clip)`` pairs), ``first`` the instant the
    earliest of them opened (``hi`` when there is none)."""

    def __init__(self, view, lo: int, closed: list):
        window_t0 = view.result["t0"]
        runs = [(int(s * 1e9), int(e * 1e9))
                for name, s, e in view.spans.records
                if name in SETUP_SPANS and e <= window_t0]
        self.lo = lo
        self.hi = max((e for _, e in runs), default=int(window_t0 * 1e9))
        self.spans = [(s.name, Clip(max(s.t0, lo), min(s.t1, self.hi)))
                      for s in closed if s.t1 > lo and s.t0 < self.hi]
        self.first = min((c.t0 for _, c in self.spans), default=self.hi)
        #: the benchmark's own record of the first run, inside the stretch
        #: and after the program's first span
        self.first_run = [Clip(max(s, self.first), e)
                          for s, e in runs if e > self.first]

    def named(self, *prefixes: str) -> list:
        """The clipped spans whose name starts with one of ``prefixes``."""
        return [c for name, c in self.spans if name.startswith(prefixes)]

    @property
    def stretch_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def start_s(self) -> float:
        return (self.first - self.lo) / 1e9

    @property
    def covered_s(self) -> float:
        """Seconds of the stretch under at least one span or event."""
        return rec.union_s(self.named(""))

    @property
    def first_run_wait_s(self) -> float:
        covered = [Clip(max(c.t0, r.t0), min(c.t1, r.t1))
                   for r in self.first_run for c in self.named("")
                   if c.t1 > r.t0 and c.t0 < r.t1]
        return rec.union_s(self.first_run) - rec.union_s(covered)

    @property
    def unattributed_s(self) -> float:
        return (self.stretch_s - self.start_s - self.covered_s
                - self.first_run_wait_s)


def of(view):
    """The run's :class:`Setup`, or ``None`` when the program keeps no
    record, the record has dropped events, or the stretch has no start."""
    closed = rec.closed("*")
    lo = start_ns()
    if (closed is None or rec.compile_events() is None or lo is None
            or view.result.get("t0") is None):
        return None
    return Setup(view, lo, closed)
