"""Bounded-liveness guard for code that waits on the device.

A device call that never returns raises no exception to catch, and a process
parked in such a native call ignores cooperative shutdown. Callers
:meth:`~StallWatchdog.mark` before every potentially-silent device
interaction; a watchdog thread acts when no mark lands within the stall
budget. The serving engine uses the SOFT mode (fail the waiting tickets, keep
the process); one-shot scripts (scripts/fid_trend.py,
scripts/publish_run.py) use the hard mode: ``on_abort`` writes the partial
artifact, then ``os._exit(exit_code)`` — deliberate, because the main thread
is the one that is parked.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Optional


class StallWatchdog:
    """Abort the process when no :meth:`mark` lands within ``stall_s``.

    ``stall_s`` ≤ 0 disables the guard. ``budget_s`` on a mark stretches
    the deadline for the single window AFTER it — known-long silent
    operations (a first compile of a large program) must not be killed as
    stalled.

    ``exit_code=None`` selects SOFT mode for long-running in-process hosts
    (the serving engine): on stall the watchdog calls ``on_abort`` once and
    stops, WITHOUT ``os._exit`` — the abort hook unblocks waiters (fails
    their tickets) while the stalled native call stays parked on its own
    thread. One-shot scripts keep the hard default: their main thread IS the
    stalled one, so only process death frees anything.
    """

    def __init__(self, stall_s: float, *, exit_code: Optional[int] = 3,
                 on_abort: Optional[Callable[[str, float], None]] = None,
                 name: str = "watchdog"):
        self.stall_s = float(stall_s)
        self.exit_code = exit_code
        self.on_abort = on_abort
        self.name = name
        self._state = {"t": time.time(), "label": "start",  # guarded-by: _lock
                       "budget": None, "done": False}
        self._lock = threading.Lock()

    def mark(self, label: str, budget_s: Optional[float] = None) -> None:
        with self._lock:
            self._state.update(t=time.time(), label=label, budget=budget_s)

    def done(self) -> None:
        """Disarm — call when the script's artifact is fully written."""
        with self._lock:
            self._state["done"] = True

    def start(self) -> "StallWatchdog":
        if self.stall_s > 0:
            threading.Thread(target=self._run, daemon=True).start()
        return self

    def _run(self) -> None:
        while True:
            time.sleep(min(15.0, max(0.05, self.stall_s / 4)))
            with self._lock:
                if self._state["done"]:
                    return
                limit = max(self.stall_s, self._state["budget"] or 0.0)
                silent = time.time() - self._state["t"]
                label = self._state["label"]
            if silent > limit:
                print(f"[{self.name}] STALL: no progress for {silent:.0f}s "
                      f"(> {limit:.0f}s) after {label!r} — aborting with "
                      f"partial artifact (stall watchdog)",
                      file=sys.stderr, flush=True)
                if self.on_abort is not None:
                    try:
                        self.on_abort(label, silent)
                    except Exception as e:  # noqa: BLE001 — abort must abort
                        print(f"[{self.name}] on_abort failed: {e!r}",
                              file=sys.stderr, flush=True)
                if self.exit_code is None:  # soft mode: one-shot, no exit
                    self.done()
                    return
                os._exit(self.exit_code)
