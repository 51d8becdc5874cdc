"""KV pool: most pages live at once in the window, as a share of the
pool's usable pages (page 0 is the never-written null page) (%)."""


def read(rec):
    p = rec["pool"]
    return 100.0 * p["peak_live_blocks"] / (p["pool_blocks"] - 1)
