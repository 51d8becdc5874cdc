"""Model step, decode: device time of the engine's decode program per
decode step in the traced slice of the window (ms)."""

from bench.metrics import program_s, traced


def read(rec):
    s = program_s(rec, "jit_decode_fn")
    n = s and traced(rec, "decode_steps")
    return None if s is None or not n else 1e3 * s / n
