"""Output tokens that reached the host inside the window, those of
unfinished requests included, per second of window."""

from bench.metrics import tokens_in_window


def read(rec):
    return sum(map(len, tokens_in_window(rec))) / rec["window_s"]
