"""The program's one span recorder, on the clock everything else uses.

Every timestamp is a raw ``time.perf_counter_ns()`` reading: the same clock
as the benchmark's own spans and, through the mirror below, the same
timeline as a ``jax.profiler`` trace. Two kinds of span share the recorder,
its id counters and that clock:

* **Layer spans** (:func:`layer`, :func:`event`) mark where work crosses a
  layer boundary: a loader stage's work and waits, one sampler call, one
  engine batch stage, one JAX compile. They are coarse — a handful per
  batch, call or dispatch, never per image, token or scan step — and are
  ALWAYS recorded, into a ring of :data:`RING_LEN` spans (oldest dropped),
  so a long-lived process holds a bounded record. A layer span's parent is
  the innermost layer span open on its thread; its ``trace_id`` is its
  parent's, or the one its caller passes for a unit of work that crosses
  threads (one loader pipeline = one epoch's iteration).
* **Ticket traces** (:func:`begin`, :func:`record`) follow one serving
  request: created at ``Router.submit`` / ``Engine.submit`` and closed at
  delivery or terminal failure, with planning, assembly, dispatch, fetch,
  preview, hedges and failovers as spans under the one trace. Their cost
  grows with the request rate, so they stay opt-in (:func:`enable` /
  :class:`tracing`): off, every entry point checks one module bool and
  returns the falsy :data:`NULL` span, and outputs are byte-identical.

**The mirror.** ``utils/profiling.py`` installs a sink (:func:`set_sink`)
that, while a profiler session is live, writes every layer span that opens
into the session as ``ddim/<name>`` — on the host plane of the same
``.xplane.pb`` as the device's ops. This module stays host-only (graftcheck
A004: no jax import); with no sink, or no session, a span costs two clock
reads and one append.

Ids come from ``itertools.count`` — the same run produces the same ids.

Export: :func:`export_chrome` renders the ticket traces as Chrome
trace-event JSON (microseconds), :func:`export_jsonl` as one JSON object
per line (seconds).
"""

from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

__all__ = [
    "TraceContext", "Span", "NULL", "RING_LEN", "enable", "disable",
    "enabled", "tracing", "begin", "record", "spans", "clear",
    "layer", "event", "current", "new_trace_id", "layer_spans", "set_sink",
    "export_chrome", "export_jsonl",
]

#: layer spans kept (the ring's length): ~10 a training step, ~1,500 JAX
#: compile events a start-up — hours of a loop, bounded for a server
RING_LEN = 65536


@dataclass(frozen=True)
class TraceContext:
    """The propagatable part of a span — what rides a submit() call across
    the router→replica→engine boundary (and through hedges, which re-issue
    the same frozen call under the same trace)."""

    trace_id: int
    span_id: int


class Span:
    """One named, timed node of a trace; ``t0``/``t1`` are
    ``time.perf_counter_ns()`` readings. ``end()`` closes it (idempotent:
    first close wins, matching Ticket's first-resolution-wins rule); as a
    context manager it closes on exit."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "t0", "t1",
                 "attrs", "_rec", "_open", "_mirror")

    def __init__(self, rec, trace_id, span_id, parent_id, name, t0, attrs):
        self._rec = rec
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.t0 = t0
        self.t1 = None
        self.attrs = attrs
        self._open = None    # layer spans: their thread's open-span stack
        self._mirror = None  # what the sink returned; closed with the span

    @property
    def ctx(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    @property
    def ended(self) -> bool:
        return self.t1 is not None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def child(self, name: str, **attrs) -> "Span":
        return self._rec.begin(name, parent=self, **attrs)

    def end(self, **attrs) -> None:
        if self.t1 is not None:
            return
        self.attrs.update(attrs)
        self.t1 = time.perf_counter_ns()
        # a closed span stays in the ring: it lets go of its thread's stack
        # and of the profiler's annotation
        stack, mirror, self._open, self._mirror = (
            self._open, self._mirror, None, None)
        if stack is not None:
            stack.remove(self)
        if mirror is not None:
            mirror.__exit__(None, None, None)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def __bool__(self) -> bool:
        return True

    def __repr__(self) -> str:
        state = ("open" if self.t1 is None
                 else f"{(self.t1 - self.t0) / 1e9:.4f}s")
        return (f"Span({self.name!r}, trace={self.trace_id}, "
                f"id={self.span_id}, {state})")


class _NullSpan:
    """The disabled-tracing span: falsy, every operation a no-op, safe to
    thread anywhere a real span goes."""

    __slots__ = ()
    trace_id = span_id = parent_id = None
    name = ""
    t0 = t1 = None
    attrs: dict = {}
    ctx = None
    ended = True

    def set(self, **attrs):
        return self

    def child(self, name, **attrs):
        return self

    def end(self, **attrs):
        pass

    def __bool__(self):
        return False

    def __repr__(self):
        return "Span(<disabled>)"


NULL = _NullSpan()


class Recorder:
    """Process-local span store: the ticket traces in a list (opt-in, so as
    long as tracing was on), the layer spans in a ring."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list = []                          # guarded-by: _lock
        self._ring = collections.deque(maxlen=RING_LEN)  # guarded-by: _lock
        self._sink: Optional[Callable] = None           # guarded-by: _lock
        self._trace_ids = itertools.count(1)
        self._span_ids = itertools.count(1)
        self._tls = threading.local()  # .open: this thread's layer spans

    def new_trace_id(self) -> int:
        return next(self._trace_ids)

    def set_sink(self, sink: Optional[Callable]) -> None:
        """``sink(name)`` is called as each layer span opens; what it
        returns (or None) is closed with ``__exit__`` when the span ends —
        on the same thread, which is why ticket spans (closed by whichever
        thread delivers) are not mirrored. ``utils/profiling.py`` installs
        the profiler's."""
        with self._lock:
            self._sink = sink

    def _make(self, name, parent, trace_id, attrs) -> Span:
        if isinstance(parent, (Span, TraceContext)):
            parent_id = parent.span_id
            if trace_id is None:
                trace_id = parent.trace_id
        else:
            parent_id = None
            if trace_id is None:
                trace_id = next(self._trace_ids)
        return Span(self, trace_id, next(self._span_ids), parent_id, name,
                    time.perf_counter_ns(), attrs)

    # -- ticket traces ----------------------------------------------------
    def begin(self, name: str, parent=None, **attrs) -> Span:
        span = self._make(name, parent, None, attrs)
        with self._lock:
            self._spans.append(span)
        return span

    def record(self, parent, name: str, t0: int, t1: int, **attrs) -> Span:
        """Retroactively add a CLOSED span — how per-batch stage timings
        (assemble/dispatch/fetch measured once per batch) become one span
        per participating request without re-running the stage. In memory
        only: the batch's live layer span is what the profiler sees."""
        span = self._make(name, parent, None, attrs)
        span.t0, span.t1 = t0, t1
        with self._lock:
            self._spans.append(span)
        return span

    def spans(self) -> list:
        with self._lock:
            return list(self._spans)

    # -- layer spans ------------------------------------------------------
    def _open_stack(self) -> list:
        try:
            return self._tls.open
        except AttributeError:
            self._tls.open = []
            return self._tls.open

    def current(self) -> Optional[Span]:
        """The innermost layer span open on the calling thread."""
        stack = self._open_stack()
        return stack[-1] if stack else None

    def layer(self, name: str, parent=None, trace_id: Optional[int] = None,
              **attrs) -> Span:
        """Open a layer span (use as a context manager). Child of ``parent``
        or of the thread's innermost open layer span, and in that span's
        trace unless ``trace_id`` names another (a unit of work that
        crosses threads); with neither it starts a new trace."""
        stack = self._open_stack()
        if parent is None and stack:
            parent = stack[-1]
        sink = self._sink
        mirror = sink(name) if sink is not None else None
        span = self._make(name, parent, trace_id, attrs)
        span._mirror = mirror
        span._open = stack
        stack.append(span)
        with self._lock:
            self._ring.append(span)
        return span

    def event(self, name: str, t1: int, dur_ns: int, **attrs) -> Span:
        """A closed layer span for something reported after the fact (a JAX
        compile): ends at ``t1``, child of the thread's innermost open
        layer span. Not mirrored: it never was open."""
        span = self._make(name, self.current(), None, attrs)
        span.t0, span.t1 = t1 - dur_ns, t1
        with self._lock:
            self._ring.append(span)
        return span

    def layer_spans(self) -> list:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._ring.clear()

    # -- export -----------------------------------------------------------
    def export_chrome(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON of the ticket traces: complete ("X")
        events in microseconds of the recorder's clock, one timeline row
        (tid) per trace so a request's whole tree reads left-to-right. Open
        spans export with dur=0 and ``"open": true`` — visible, not lost."""
        events = []
        for s in self.spans():
            t1 = s.t1 if s.t1 is not None else s.t0
            args = {"span_id": s.span_id, "parent_id": s.parent_id}
            args.update(s.attrs)
            if s.t1 is None:
                args["open"] = True
            events.append({
                "name": s.name, "cat": "serve", "ph": "X",
                "ts": s.t0 / 1e3, "dur": (t1 - s.t0) / 1e3,
                "pid": 0, "tid": s.trace_id, "args": args,
            })
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    def export_jsonl(self, path: Optional[str] = None) -> list:
        """One row per ticket-trace span, ``t0``/``t1`` in seconds of the
        recorder's clock."""
        rows = [{
            "trace_id": s.trace_id, "span_id": s.span_id,
            "parent_id": s.parent_id, "name": s.name,
            "t0": s.t0 / 1e9,
            "t1": None if s.t1 is None else s.t1 / 1e9,
            "attrs": s.attrs,
        } for s in self.spans()]
        if path is not None:
            with open(path, "w") as f:
                for row in rows:
                    f.write(json.dumps(row) + "\n")
        return rows


_REC = Recorder()
_ENABLED = False


def recorder() -> Recorder:
    return _REC


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


class tracing:
    """``with obs.spans.tracing():`` — enable ticket traces for a scope,
    restore the previous state on exit (nesting-safe)."""

    def __enter__(self):
        self._prev = _ENABLED
        enable()
        return _REC

    def __exit__(self, *exc):
        if not self._prev:
            disable()
        return False


def begin(name: str, parent=None, **attrs):
    """Open a ticket-trace span (a new trace when ``parent`` is None).
    Returns :data:`NULL` when tracing is disabled — the one check every
    serving-path call site relies on for the zero-overhead contract."""
    if not _ENABLED:
        return NULL
    return _REC.begin(name, parent=parent, **attrs)


def record(parent, name: str, t0: int, t1: int, **attrs) -> None:
    if not _ENABLED or parent is None or parent is NULL:
        return
    _REC.record(parent, name, t0, t1, **attrs)


spans = _REC.spans
clear = _REC.clear
layer = _REC.layer
event = _REC.event
current = _REC.current
new_trace_id = _REC.new_trace_id
layer_spans = _REC.layer_spans
set_sink = _REC.set_sink
export_chrome = _REC.export_chrome
export_jsonl = _REC.export_jsonl
