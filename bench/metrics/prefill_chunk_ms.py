"""Model step, prefill: device time of the engine's prefill program per
prompt chunk in the traced slice of the window (ms)."""

from bench.metrics import program_s, traced


def read(rec):
    s = program_s(rec, "jit_prefill_fn")
    n = s and traced(rec, "prefill_chunks")
    return None if s is None or not n else 1e3 * s / n
