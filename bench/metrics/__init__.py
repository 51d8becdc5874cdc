"""One reader per metric, ``<metric>.py`` with ``read(rec)``: the metric's
value from a run record, or None where the record holds nothing to read
it from (the harness then leaves the metric out of the result line).

A run record (``bench/run.py``) holds, on the harness's clock in seconds
from the window's opening: ``requests`` (each with ``due``, ``sent`` and
the ``times`` its output tokens reached the host), ``unsent_due``,
``window_s`` and ``setup_s``; the engine's ``counters`` over the window;
``pool``; ``memory_peak_bytes``; the configuration ``conf`` and the
chip's ``peaks``; and in traced runs ``trace`` (``bench/trace.py``, with
the engine's ``counters`` over the traced slice) and ``planes``."""

from __future__ import annotations

import numpy as np


def tokens_in_window(rec: dict) -> list[list[float]]:
    """Each request's token times inside the window (tokens of requests
    warmed up before it opened have negative times)."""
    w = rec["window_s"]
    return [[t for t in r["times"] if 0 <= t <= w] for r in rec["requests"]]


def percentile(values, q: float):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def program_s(rec: dict, name: str):
    """Device seconds of one XLA program in the traced slice."""
    tr = rec.get("trace")
    if not tr:
        return None
    s = tr["modules"].get(name, 0.0)
    return s if s > 0 else None


def traced(rec: dict, counter: str) -> int:
    """An engine counter over the traced slice of the window."""
    return rec["trace"]["counters"][counter]
