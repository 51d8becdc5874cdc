"""Host spans and request stamps of the serving engine.

The engine is handed a :class:`Tracer` and never reads a clock itself
(``serving/`` is wall-clock-free by lint rule ``repo-tick-wallclock``,
the same pattern as ``EngineWatchdog``).  Every span enters
``jax.profiler.TraceAnnotation("engine." + name)``, so under
``jax.profiler`` the engine's phases sit on the profiler's host clock
beside the device ops they dispatch.  That is all a tracer without a
clock does; with a clock (``runtime.clock.SystemClock`` or
``ManualClock``) it also keeps

* each span name's total seconds and count,
* the last ``MAX_STEPS`` ``step`` spans, each with its phase split: the
  seconds of each span name inside it less the spans nested in that one
  (the step's own share under ``step``), so the phases sum to the step,
* a log of the last ``MAX_EVENTS`` request events ``(rid, event, t)``.

Nothing the engine decides reads the tracer, so served tokens and the
engine's counters are the same with any tracer or none.
"""

from __future__ import annotations

import collections
import contextlib

import jax

SPAN_PREFIX = "engine."
MAX_STEPS = 4096        # about 20 minutes of steps at the chip's ~0.3 s
MAX_EVENTS = 65536      # three stamps a request


class Tracer:
    """Spans and request events of one engine; see the module docstring."""

    def __init__(self, clock=None):
        self.clock = clock
        self.totals: collections.Counter = collections.Counter()
        self.counts: collections.Counter = collections.Counter()
        self.steps: collections.deque = collections.deque(maxlen=MAX_STEPS)
        self.events: collections.deque = collections.deque(maxlen=MAX_EVENTS)
        self._open: list = []           # [name, start, nested seconds]
        self._phases: dict | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            if self.clock is None:
                yield
                return
            if name == "step":
                self._phases = {}
            self._open.append([name, self.clock(), 0.0])
            try:
                yield
            finally:
                self._close()

    def _close(self) -> None:
        name, t0, nested = self._open.pop()
        dt = self.clock() - t0
        self.totals[name] += dt
        self.counts[name] += 1
        if self._open:
            self._open[-1][2] += dt
        if self._phases is not None:
            self._phases[name] = self._phases.get(name, 0.0) + dt - nested
        if name == "step":
            self.steps.append({"t": t0, "s": dt, "phases": self._phases})
            self._phases = None

    def event(self, rid: int, name: str) -> None:
        """Stamp request ``rid`` with ``name`` (``submit``, ``admit``,
        ``first_token``) at the clock's time; nothing without a clock."""
        if self.clock is not None:
            self.events.append((rid, name, self.clock()))

    def slow_steps(self, n: int = 8) -> list[dict]:
        """The ``n`` slowest steps kept, slowest first: start ``t``,
        seconds ``s`` and ``phases``."""
        return sorted(self.steps, key=lambda st: -st["s"])[:n]

    def records(self) -> dict:
        """Everything recorded, as plain JSON-ready data."""
        return {"spans": {n: {"s": self.totals[n], "n": self.counts[n]}
                          for n in self.totals},
                "steps": list(self.steps),
                "events": [list(e) for e in self.events]}
