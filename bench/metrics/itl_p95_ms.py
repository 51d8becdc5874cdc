"""95th percentile of every gap between consecutive output tokens of one
request, over all requests, inside the window (ms)."""

import numpy as np

from bench.metrics import percentile, tokens_in_window


def read(rec):
    gaps = [g for t in tokens_in_window(rec) for g in np.diff(t)]
    v = percentile(gaps, 95)
    return None if v is None else v * 1e3
