"""Device: peak bytes in use on the chip over the process, read after the
window and before the reference runs (GB)."""


def read(rec):
    b = rec.get("memory_peak_bytes")
    return b / 1e9 if b else None
