"""The benchmark: data files (configurations, traffic mixes, per-cell
correctness limits) plus one harness (``run.py``) that reads them."""
