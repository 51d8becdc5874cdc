"""Scheduler: share of prompt tokens served from cached prefix blocks
over the window (%)."""


def read(rec):
    c = rec["counters"]
    total = c["prefix_hit_tokens"] + c["prefill_tokens"]
    return 100.0 * c["prefix_hit_tokens"] / total if total else None
