"""Scheduler: mean number of rows that emitted a token per decode step."""


def read(rec):
    c = rec["counters"]
    return c["decode_tokens"] / c["decode_steps"] if c["decode_steps"] \
        else None
