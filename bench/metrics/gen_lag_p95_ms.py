"""Load generator: 95th percentile of how late requests were sent after
their scheduled time (ms); the harness steps the engine between sends, so
a long step shows here."""

from bench.metrics import percentile


def read(rec):
    v = percentile([r["sent"] - r["due"] for r in rec["requests"]
                    if r["due"] >= 0], 95)
    return None if v is None else v * 1e3
