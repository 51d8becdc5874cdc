"""Kernels: the fused paged BESF decode kernel's least time (the larger of
its FLOPs at the bf16 peak and its bytes at HBM bandwidth, from the arch
family's ``work.besf_kernel_work`` at the fill it ran) over its device
time in the traced slice of the window (%)."""

from bench.archs import family, least_time_s

# The kernel's device op is named after the jitted wrapper that calls the
# Pallas kernel (``kernels/paged_decode.py``).
KERNEL = "paged_bitstopper_decode"


def kernel_s(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    s = sum(v for k, v in tr["ops"].items() if KERNEL in k)
    return s if s > 0 else None


def read(rec):
    s = kernel_s(rec)
    if s is None or "peaks" not in rec:
        return None
    conf = rec["conf"]
    flops, nbytes = family(conf).work.besf_kernel_work(
        conf, rec["trace"]["counters"])
    if not nbytes:
        return None
    t, _ = least_time_s(flops, nbytes, rec["peaks"])
    return 100.0 * t / s
