"""Seconds from process start to the window's first due request: imports,
weights, engine, and the warm-up that compiles or loads every program."""


def read(rec):
    return rec["setup_s"]
