"""Model step, decode: the decode steps' model FLOPs (the arch family's
``work.decode_step_flops``, at the batch and fill they ran) over their
device time at the chip's bf16 peak (%)."""

from bench.archs import family
from bench.metrics import program_s, traced


def read(rec):
    s = program_s(rec, "jit_decode_fn")
    if s is None or not traced(rec, "decode_tokens") or "peaks" not in rec:
        return None
    conf = rec["conf"]
    flops = family(conf).work.decode_step_flops(conf, rec["trace"]["counters"])
    return 100.0 * flops / (s * rec["peaks"]["bf16_flops_per_s"])
