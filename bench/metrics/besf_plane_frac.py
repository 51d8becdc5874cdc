"""Kernels: bit planes the fused decode kernel fetched, by its own counts,
over 12 for every page it reached, on the live cache at the window's
close (%)."""


def read(rec):
    p = rec.get("planes")
    return 100.0 * p["fetched"] / p["dense"] if p and p["dense"] else None
