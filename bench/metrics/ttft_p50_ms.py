"""Median time to first token over every request due in the window, from
its scheduled send time; a request without a first token at the close
counts with its wait so far (ms)."""

from bench.metrics import percentile


def read(rec):
    w = rec["window_s"]
    waits = [(r["times"][0] if r["times"] and r["times"][0] <= w else w)
             - r["due"] for r in rec["requests"] if 0 <= r["due"] < w]
    waits += [w - d for d in rec["unsent_due"]]
    v = percentile(waits, 50)
    return None if v is None else v * 1e3
