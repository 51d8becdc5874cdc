"""Model step, decode: the decode steps' model FLOPs (``bench/work.py``,
at the batch and fill they ran) over their device time at the chip's
bf16 peak (%)."""

from bench.metrics import program_s, traced
from bench.work import decode_step_flops


def read(rec):
    s = program_s(rec, "jit_decode_fn")
    if s is None or not traced(rec, "decode_tokens") or "peaks" not in rec:
        return None
    flops = decode_step_flops(rec["conf"], traced(rec, "decode_tokens"),
                              traced(rec, "decode_kv_tokens"))
    return 100.0 * flops / (s * rec["peaks"]["bf16_flops_per_s"])
